"""A configuration, a mix and a metric added as new files are found by
name: BENCHMARK.json gains entries, and no other file changes."""

import hashlib
import json
import shutil

from _paths import DATA, ROOT
from bench import spec, traffic


def _digests(root):
    return {p.relative_to(root): hashlib.sha256(p.read_bytes()).hexdigest()
            for p in sorted((root / "bench").rglob("*")) if p.is_file()}


def test_new_files_are_found_without_editing_existing_ones(tmp_path):
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    before = _digests(tmp_path)

    # the new configuration, mix, limits and metric: files of their own
    shutil.copy(DATA / "tiny.json", tmp_path / "bench/configs/tiny.json")
    shutil.copy(DATA / "tiny-rag.json", tmp_path / "bench/traffic/burst.json")
    (tmp_path / "bench/limits/tiny.burst.json").write_text(json.dumps(
        {k: {"limit": 1.0} for k in ("gap", "dH", "dSE", "dMI")}))
    (tmp_path / "bench/metrics/answers_per_request.py").write_text(
        "def read(ctx):\n"
        "    reqs = ctx['requests']\n"
        "    return sum(len(r) for r in reqs) / len(reqs) if reqs else None\n")
    bench = json.loads((tmp_path / "BENCHMARK.json").read_text())
    bench["configs"].append({"name": "tiny", "source": "test",
                             "file": "bench/configs/tiny.json",
                             "reduced": [], "why": "test"})
    bench["workloads"].append({"name": "tiny.burst", "config": "tiny",
                               "traffic": "burst", "chips": 1,
                               "why": "test"})
    bench["per_layer"].append({
        "name": "answers_per_request", "unit": "tokens", "better": "higher",
        "source": "host_clock", "layer": "scheduler", "moves": "tok_s",
        "workloads": ["tiny.burst"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))

    cell = spec.load_cell("tiny.burst", root=tmp_path)
    assert cell.config["hidden_size"] == 64
    assert cell.traffic["block"] == 4
    assert cell.limits["gap"]["limit"] == 1.0
    assert "answers_per_request" in [m["name"] for m in cell.per_layer]
    # a metric restricted to other cells is not this cell's
    assert "prefix_saved_share" not in [m["name"] for m in cell.per_layer]
    read = spec.metric_reader("answers_per_request", root=tmp_path)
    assert read({"requests": [[1, 2], [3, 4, 5, 6]]}) == 3.0
    assert read({"requests": []}) is None
    assert len(traffic.schedule(cell.traffic, 64, 5)) > 0

    after = _digests(tmp_path)
    assert {k: v for k, v in after.items() if k in before} == before


def test_every_named_file_exists():
    bench = spec.load_benchmark()
    for w in bench["workloads"]:
        cell = spec.load_cell(w["name"])
        assert (ROOT / "bench/references"
                / f"{cell.config['reference']}.py").is_file()
    for m in bench["per_layer"]:
        assert callable(spec.metric_reader(m["name"]))


def test_unknown_device_kind_is_an_error():
    assert spec.peak("TPU v5 lite")["bf16_flops"] == 197e12
    try:
        spec.peak("TPU v9 imaginary")
    except KeyError:
        return
    raise AssertionError("a device kind outside the peaks table passed")
