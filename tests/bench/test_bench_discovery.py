"""A configuration, a mix and a metric added as new files are found by
name: BENCHMARK.json gains entries, and no other file changes.  So is a
configuration of another architecture, with its family and reference."""

import hashlib
import json
import shutil

import jax
import pytest

from _paths import DATA, ROOT
from bench import run, serve, spec, traffic


def _digests(root):
    return {p.relative_to(root): hashlib.sha256(p.read_bytes()).hexdigest()
            for p in sorted((root / "bench").rglob("*")) if p.is_file()}


def _copy_bench(root):
    """The benchmark's files under ``root``, and their digests."""
    shutil.copytree(ROOT / "bench", root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(ROOT / "BENCHMARK.json", root / "BENCHMARK.json")
    return _digests(root)


def _add_architecture(root, name, family, reference):
    """A configuration of ``tiny.json``'s sizes whose ``"reference"`` is
    ``name``, with that family and reference, as new files only."""
    config = json.loads((DATA / "tiny.json").read_text())
    config["reference"] = name
    (root / f"bench/configs/{name}.json").write_text(json.dumps(config))
    shutil.copy(family, root / f"bench/families/{name}.py")
    shutil.copy(reference, root / f"bench/references/{name}.py")
    return config


def test_new_files_are_found_without_editing_existing_ones(tmp_path):
    before = _copy_bench(tmp_path)

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


def test_a_second_architecture_is_served_from_new_files(tmp_path):
    """A bias-free GQA family, its reference, configuration, mix and
    limits, added as files, run through ``run_cell`` and judged."""
    before = _copy_bench(tmp_path)
    nobias = DATA / "gqa_nobias"
    config = _add_architecture(tmp_path, "gqa_nobias",
                               nobias / "family.py", nobias / "reference.py")
    shutil.copy(DATA / "tiny-chat.json",
                tmp_path / "bench/traffic/tiny-chat.json")
    shutil.copy(DATA / "tiny-limits.json",
                tmp_path / "bench/limits/gqa-nobias.tiny-chat.json")
    bench = json.loads((tmp_path / "BENCHMARK.json").read_text())
    bench["configs"].append({"name": "gqa-nobias", "source": "test",
                             "file": "bench/configs/gqa_nobias.json",
                             "reduced": [], "why": "test"})
    bench["workloads"].append({"name": "gqa-nobias.tiny-chat",
                               "config": "gqa-nobias",
                               "traffic": "tiny-chat", "chips": 1,
                               "why": "test"})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))

    cfg, params = serve.program(config, 3, tmp_path)
    assert not cfg.qkv_bias
    assert set(params["blocks"]["attn"]) == {"wq", "wk", "wv", "wo"}
    cell = spec.load_cell("gqa-nobias.tiny-chat", root=tmp_path)
    out = run.run_cell(cell, 2**31 + 7, 2.0, False, jax.devices(),
                       log=lambda *a: None)
    assert out["correct"] is True, out["_lines"]
    assert out["attempted"] > 0 and out["failed"] == 0

    after = _digests(tmp_path)
    assert {k: v for k, v in after.items() if k in before} == before


def test_a_family_with_a_wrong_tree_is_refused(tmp_path):
    """The shared check against the program's ``init_params`` holds for
    every family: Qwen2's biases left out of a tree whose ArchConfig
    asks for them."""
    _copy_bench(tmp_path)
    family = tmp_path / "wrong_tree.py"
    family.write_text(
        (ROOT / "bench/families/qwen2.py").read_text()
        + "\n\n_tree = program_params\n\n\n"
        "def program_params(w, cfg):\n"
        "    params = _tree(w, cfg)\n"
        "    del params['blocks']['attn']['bq']\n"
        "    return params\n")
    config = _add_architecture(tmp_path, "wrong_tree", family,
                               ROOT / "bench/references/qwen2.py")
    with pytest.raises(ValueError, match="do not match"):
        serve.program(config, 3, tmp_path)


def test_every_named_file_exists():
    bench = spec.load_benchmark()
    for w in bench["workloads"]:
        cell = spec.load_cell(w["name"])
        for kind in ("families", "references"):
            assert (ROOT / "bench" / kind
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
