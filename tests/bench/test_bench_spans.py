"""The readers of the engine loop's spans and counters.

``host_ms_per_iter``, ``decode_rows_mean`` and ``prefill_wait_p95_ms``
on hand-made contexts (which iterations and requests the window
selects; None where there is nothing to read, as from a program that
records no spans), then end to end through ``run.run_cell`` with a
trace on the tiny cell.
"""

import json
import math
from collections import namedtuple

import jax
import numpy as np
import pytest

from _paths import DATA
from bench import run, spec

NEW = ("host_ms_per_iter", "decode_rows_mean", "prefill_wait_p95_ms")
It = namedtuple("It", "t0 t1 wait_s rows")


class Req:
    def __init__(self, admitted, wait=None):
        self.history = [("queued", admitted - 1.0),
                        ("prefilling", admitted)]
        if wait is not None:
            self.prefill_wait_s = wait


def _ctx(iterations=(), requests=(), window=(10.0, 20.0), traced=None,
         spans=True):
    results = {"spans": {"phases": {}, "iterations": list(iterations),
                         "iterations_kept": 4096}} if spans else {}
    return {"results": results, "requests": list(requests),
            "window": window, "traced": traced or (window[0], window[0])}


def read(name, ctx):
    return spec.metric_reader(name)(ctx)


def test_host_ms_per_iter_reads_the_window_without_waits():
    its = [It(9.0, 9.5, 0.1, 4),          # began before the window
           It(10.0, 10.3, 0.25, 4),       # 50 ms on the host
           It(10.3, 10.4, 0.07, 0),       # 30 ms, a prefill-only pass
           It(19.9, 20.5, 0.2, 4)]        # began inside: 400 ms
    assert read("host_ms_per_iter", _ctx(its)) == pytest.approx(
        (50 + 30 + 400) / 3)


def test_host_ms_per_iter_leaves_out_the_trace_stop():
    its = [It(10.0, 10.3, 0.25, 4),
           It(10.3, 12.8, 0.25, 4),       # the profiler stopped in it
           It(12.8, 13.1, 0.25, 4)]
    got = read("host_ms_per_iter", _ctx(its, traced=(10.0, 11.0)))
    assert got == pytest.approx(50.0)


def test_decode_rows_mean_counts_the_scans_in_the_window():
    its = [It(9.0, 9.5, 0.1, 24), It(10.0, 10.3, 0.2, 3),
           It(10.3, 10.4, 0.05, 0), It(11.0, 11.3, 0.2, 5),
           It(20.0, 20.3, 0.2, 24)]
    assert read("decode_rows_mean", _ctx(its)) == pytest.approx(4.0)


def test_prefill_wait_p95_reads_the_requests_admitted_in_the_window():
    reqs = [Req(5.0, 9.0)] + [Req(10.0 + i, 0.1 * i) for i in range(10)] \
        + [Req(25.0, 9.0)]
    want = 1e3 * float(np.percentile([0.1 * i for i in range(10)], 95))
    assert read("prefill_wait_p95_ms", _ctx(requests=reqs)) == \
        pytest.approx(want)


@pytest.mark.parametrize("name", NEW)
def test_readers_find_nothing_in_an_empty_window(name):
    its = [It(1.0, 1.3, 0.2, 4)]
    assert read(name, _ctx(its, [Req(1.0, 0.2)])) is None


@pytest.mark.parametrize("name", NEW)
def test_readers_find_nothing_in_a_program_without_spans(name):
    """A program that records no spans and stamps no prefill wait (the
    benchmark's metrics run against the commit before them too)."""
    ctx = _ctx(requests=[Req(12.0)], spans=False)
    assert read(name, ctx) is None


def test_run_cell_reports_them_with_a_trace(monkeypatch):
    cell = spec.Cell(
        name="tiny.chat", chips=1,
        config=json.loads((DATA / "tiny.json").read_text()),
        traffic=json.loads((DATA / "tiny-chat.json").read_text()),
        limits=json.loads((DATA / "tiny-limits.json").read_text()),
        end_to_end=[],
        per_layer=[{"name": n, "unit": "u"} for n in NEW])
    # the CPU is not in the peaks table; these readers take no peak
    monkeypatch.setattr(spec, "peak", lambda kind: {})
    out = run.run_cell(cell, 2**31 + 11, 2.0, True, jax.devices(),
                       log=lambda *a: None)
    out.pop("_lines")
    assert set(out["metrics"]) == set(NEW)
    for name in NEW:
        assert math.isfinite(out["metrics"][name]["value"]), name
    assert out["metrics"]["host_ms_per_iter"]["value"] > 0
    assert 1 <= out["metrics"]["decode_rows_mean"]["value"] <= \
        cell.config["engine"]["num_slots"]
