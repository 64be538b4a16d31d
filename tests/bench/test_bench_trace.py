"""The trace reducer: busy union, time by name, idle gaps and labels."""

import pytest

from _paths import DATA
from bench import trace as tr

# two programs on chip 0; host spans around them (times in ns)
EVENTS = tr.Events(
    ops=[("fusion.1", 0, 40, 0), ("paged_decode_attention", 30, 60, 0),
         ("fusion.2", 100, 130, 0), ("uncertainty_head", 125, 150, 0),
         ("fusion.3", 300, 320, 0)],
    modules=[("jit_scan_decode(1)", 0, 150, 0),
             ("jit__lambda(2)", 300, 320, 0)],
    host=[("engine.run", 0, 400, -1), ("harvest", 160, 290, -1)])


def test_union_merges_overlaps():
    assert tr.union([(5, 9), (0, 4), (3, 6), (20, 30)]) == [(0, 9),
                                                             (20, 30)]


def test_busy_is_the_union_of_operations():
    # [0, 60] + [100, 150] + [300, 320]
    assert tr.busy_ns(EVENTS, 0) == 60 + 50 + 20


def test_time_and_count_by_name():
    assert tr.time_by_name(EVENTS.ops)["paged_decode_attention"] == 30
    assert tr.count_by_name(EVENTS.modules) == {"jit_scan_decode(1)": 1,
                                                "jit__lambda(2)": 1}


def test_gaps_and_their_labels():
    gaps = tr.gaps(EVENTS, 0, 0, 400)
    assert gaps == [(60, 100), (150, 300), (320, 400)]
    assert [tr.label(EVENTS, (s + e) / 2) for s, e in gaps] == \
        ["engine.run", "engine.run / harvest", "engine.run"]
    assert tr.label(EVENTS, 500) == "unattributed"


def test_programs_by_name_and_by_kernel():
    kern = tr.module_kernels(EVENTS)
    assert kern["jit_scan_decode(1)"] == {
        "fusion.1", "paged_decode_attention", "fusion.2", "uncertainty_head"}
    red = {"modules": {"jit_scan_decode(1)": 1.5e-7, "jit__lambda(2)": 2e-8},
           "module_counts": {"jit_scan_decode(1)": 1, "jit__lambda(2)": 1},
           "module_kernels": {k: sorted(v) for k, v in kern.items()},
           "ops": {"uncertainty_head": 2.5e-8, "fusion.1": 4e-8},
           "op_counts": {"uncertainty_head": 1, "fusion.1": 1}}
    assert tr.programs(red, named="scan_decode") == (1.5e-7, 1)
    assert tr.programs(red, running="paged_decode_attention") == (1.5e-7, 1)
    assert tr.programs(red, running="paged_prefill_attention") == (0.0, 0)
    assert tr.operations(red, "uncertainty_head") == (2.5e-8, 1)


# a trace recorded on one TPU v5e chip: three steps, each the paged decode
# kernel at 4 slots then a 512x512 matmul, inside host spans 'bench.small'
# and 'step' (data/small.xplane.pb, 46 KB)
SMALL = str(DATA / "small.xplane.pb")


def test_recorded_trace_busy_union_and_kernel_time():
    ev = tr.load(SMALL)
    assert (len(ev.ops), len(ev.modules)) == (51, 6)
    # the union of all 51 operation intervals, in ns
    assert tr.busy_ns(ev, 0) == 62_201
    # the kernel's three calls: 16,272 + 15,800 + 15,828 ns
    assert tr.self_time_by_name(ev.ops)["paged_decode_attention_kernel.1"] \
        == 47_900
    mods = tr.time_by_name(ev.modules)
    assert mods["jit_paged_decode_attention_kernel(13906058580793008420)"] \
        == 17_397 + 16_926 + 16_954


def test_recorded_trace_reduces_to_idle_gaps_between_steps():
    red = tr.reduce(SMALL)
    assert red["busy_s"] == pytest.approx(62_201e-9)
    assert red["window_s"] == pytest.approx((57_133_090 - 43_237_850) * 1e-9)
    name, secs = red["breakdown"]["device_ops"][0]
    assert name == "paged_decode_attention_kernel.1"
    assert secs == pytest.approx(47_900e-9)
    gaps = red["breakdown"]["idle_gaps"]
    # the longest gaps lie between the steps, inside the harness's span
    assert gaps[0][0].startswith("bench.small") and gaps[0][1] > 3e-3
    assert tr.operations(red, "paged_decode_attention") == \
        (pytest.approx(47_900e-9), 3)
    assert tr.programs(red, named="paged_decode_attention_kernel")[1] == 3


def test_self_time_subtracts_nested_operations():
    nested = [("while.1", 0, 100, 0), ("fusion.1", 10, 30, 0),
              ("custom.1", 40, 90, 0), ("copy.1", 120, 130, 0)]
    assert tr.self_time_by_name(nested) == {
        "while.1": 30, "fusion.1": 20, "custom.1": 50, "copy.1": 10}
