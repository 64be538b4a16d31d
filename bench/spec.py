"""Finds a cell's files by the names ``BENCHMARK.json`` gives them.

Nothing here knows a particular configuration, architecture, mix or
metric: a later change adds a cell by adding files and entries, never by
editing code.  A configuration of a new architecture brings:

* ``bench/configs/<config>.json``, whose ``"reference"`` names the
  architecture;
* ``bench/families/<reference>.py``: the program's ``ArchConfig``, the
  per-layer weights, the parameter tree and the body's FLOPs per token;
* ``bench/references/<reference>.py``: the plain forward that decides
  ``correct``;
* ``bench/traffic/<mix>.json`` unless it serves an existing mix,
  ``bench/limits/<cell>.json``, and optionally
  ``bench/metrics/<metric>.py``.
"""

from __future__ import annotations

import dataclasses
import functools
import importlib.util
import json
from pathlib import Path
from types import ModuleType

BENCH_DIR = Path(__file__).resolve().parent
ROOT = BENCH_DIR.parent


@dataclasses.dataclass(frozen=True)
class Cell:
    """One workload of ``BENCHMARK.json`` with everything it names."""

    name: str
    chips: int
    config: dict
    traffic: dict
    limits: dict
    end_to_end: list
    per_layer: list
    root: Path = ROOT


def load_benchmark(root: Path = ROOT) -> dict:
    return json.loads((root / "BENCHMARK.json").read_text())


def _cell_metrics(metrics: list, cell: str) -> list:
    return [m for m in metrics if cell in m.get("workloads", [cell])]


def load_cell(name: str, root: Path = ROOT) -> Cell:
    """The workload ``name`` with its configuration, mix and limits."""
    bench = load_benchmark(root)
    work = {w["name"]: w for w in bench["workloads"]}
    if name not in work:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json "
                       f"(have {sorted(work)})")
    w = work[name]
    configs = {c["name"]: c for c in bench["configs"]}
    config = json.loads((root / configs[w["config"]]["file"]).read_text())
    traffic = json.loads(
        (root / "bench" / "traffic" / f"{w['traffic']}.json").read_text())
    limits = json.loads(
        (root / "bench" / "limits" / f"{name}.json").read_text())
    return Cell(name=name, chips=int(w["chips"]), config=config,
                traffic=traffic,
                limits=limits,
                end_to_end=_cell_metrics(bench["end_to_end"], name),
                per_layer=_cell_metrics(bench["per_layer"], name),
                root=root)


def _load_module(path: Path, name: str) -> ModuleType:
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def metric_reader(name: str, root: Path = ROOT):
    """``read(ctx)`` of ``bench/metrics/<name>.py``."""
    path = root / "bench" / "metrics" / f"{name}.py"
    return _load_module(path, f"bench_metric_{len(name)}_{abs(hash(name))}"
                        ).read


def reference_module(name: str, root: Path = ROOT) -> ModuleType:
    """``bench/references/<name>.py``: a configuration's plain forward."""
    return _load_module(root / "bench" / "references" / f"{name}.py",
                        f"bench_reference_{name}")


@functools.cache
def family_module(name: str, root: Path = ROOT) -> ModuleType:
    """``bench/families/<name>.py``: what the harness needs of a
    configuration's architecture, for the name its ``"reference"`` key
    gives.  Loaded once per process: the work counts call it for every
    served token."""
    return _load_module(root / "bench" / "families" / f"{name}.py",
                        f"bench_family_{name}")


def peak(device_kind: str, root: Path = ROOT) -> dict:
    """The chip's peaks from ``bench/peaks.json``; a kind that is not in
    the table is an error, never a default."""
    table = json.loads((root / "bench" / "peaks.json").read_text())
    if device_kind not in table["devices"]:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"bench/peaks.json")
    return table["devices"][device_kind]
