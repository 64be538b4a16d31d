"""The window's harvests and cuts, the readers that take them, and the
estimators' guarantees that ``correct`` holds the triplet to."""

import math
import types

import numpy as np
import pytest

from _paths import ROOT  # noqa: F401  (puts the repository on sys.path)
from bench import check, spec
from bench.serve import Window


def _req(rid, t_submit):
    return types.SimpleNamespace(rid=rid, t_submit=t_submit)


def test_cut_after_the_close_and_after_the_grace():
    win = Window(10.0, {0}, grace=5.0)
    win.token(0, True, 100.0)                  # opens the window
    early, late = _req(0, 99.0), _req(1, 111.0)
    assert not win.cut(early, 105.0)           # inside: never cut
    win.token(0, False, 110.5)                 # closes it at 110
    assert win.t_close == 110.0
    assert win.cut(late, 111.5)                # joined after the close
    assert not win.cut(early, 114.0)           # still in its grace
    assert win.cut(early, 115.0) and win.cut_late == {0}


def test_harvests_split_where_a_slot_comes_round_again():
    win = Window(100.0, {9}, chunk=4)
    t = 0.0
    # harvest 1: requests 1, 2, 3 (3 finishes after two tokens);
    # harvest 2, right after it: 1 and 2 again; harvest 3: 1 alone,
    # twice in a row (the second begins after four appends of it)
    for rid, n in ((1, 4), (2, 4), (3, 2), (1, 4), (2, 4), (1, 4), (1, 4)):
        for _ in range(n):
            t += 1e-5
            win.token(rid, False, t)
    assert [h[1] for h in win.harvests] == [10, 8, 4, 4]
    # a pause of the device starts one too
    win.token(2, False, t + 0.5)
    assert len(win.harvests) == 5
    assert win.decode_rows(0.0, t) == (26, 4)


def _ctx(tokens, harvests):
    return {"harvests": lambda t0, t1: (tokens, harvests),
            "window": (0.0, 1.0), "traced": (0.0, 1.0),
            "engine": {"decode_chunk": 8, "prefill_chunk": 128,
                       "num_slots": 24}}


def test_decode_batch_mean_reads_the_window_harvests():
    read = spec.metric_reader("decode_batch_mean")
    assert read(_ctx(8 * 17 * 10, 10)) == pytest.approx(17.0)
    assert read(_ctx(0, 0)) is None


def test_prefill_per_ktok_counts_the_tokens_chunks_computed():
    read = spec.metric_reader("prefill_ms_per_ktok")
    ctx = _ctx(0, 0)
    ctx["trace"] = {"modules": {"jit_prefill(1)": 0.2},
                    "module_counts": {"jit_prefill(1)": 10},
                    "module_kernels": {"jit_prefill(1)":
                                       ["paged_prefill_attention.3"]}}
    # 2,000 prompt tokens, 500 of them from the prefix cache, in 15
    # chunks: 100 tokens a chunk, 20 ms a chunk
    ctx["results"] = {"prefill_chunks": 15, "prefix_cache": {
        "prompt_tokens": 2000, "prompt_tokens_saved": 500}}
    assert read(ctx) == pytest.approx(200.0)
    ctx["results"]["prefill_chunks"] = 0
    assert read(ctx) is None


def test_head_roofline_counts_the_rows_served():
    read = spec.metric_reader("head_roofline")
    config = {"num_hidden_layers": 1, "hidden_size": 1536,
              "num_attention_heads": 12, "num_key_value_heads": 2,
              "head_dim": 128, "intermediate_size": 8960,
              "vocab_size": 151936, "bayesian_head": {"mc_samples": 10}}
    peak = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}
    ctx = _ctx(6 * 8 * 5, 5)                   # 6 rows a step
    ctx.update(config=config, peak=peak, trace={
        "ops": {"uncertainty_head_sampled.1": 40 * 4.6e-3},
        "op_counts": {"uncertainty_head_sampled.1": 80},
        "modules": {"jit_scan_decode(1)": 1.0},
        "module_counts": {"jit_scan_decode(1)": 5},
        "module_kernels": {}})
    bytes_ = 2 * 1536 * 151936 * 4 + 6 * 1536 * 2 + 6 * 5 * 4
    assert read(ctx) == pytest.approx(100 * bytes_ / 819e9 / 4.6e-3)


def test_triplet_guarantees():
    V = 1000
    h = np.array([2.0, 2.0, 2.0, 2.0, 8.0, np.nan, 2.0])
    se = np.array([1.5, 1.5, 1.5, 2.5, 7.0, 1.0, 1.0])
    mi = np.array([0.5, 0.6, -0.1, 0.0, 1.0, 0.5, 1.0])
    assert math.log(V) < 8.0
    assert check.triplet_bad(h, se, mi, V).tolist() == [
        False,          # sound
        True,           # MI is not H - SE
        True,           # MI below 0
        False,          # SE above H: MI clamps to 0
        True,           # H above log V
        True,           # not finite
        False]          # sound


def test_numbers_keep_the_estimators_sign():
    per = {k: np.array([0.1, 0.3]) for k in check.READINGS}
    per.update(H=np.array([0.002, -0.004]), SE=np.array([0.001, 0.001]),
               MI=np.array([0.0, -0.002]), bad=np.array([False, True]))
    out = check.numbers(per)
    assert out["gap_mean"] == pytest.approx(0.2)
    assert out["gap_max"] == pytest.approx(0.3)
    assert out["H_bias"] == pytest.approx(-0.001)
    assert out["MI_bias"] == pytest.approx(-0.001)
    assert out["triplet_bad"] == 1


def test_head_control_rounds_the_head_alone():
    import jax.numpy as jnp
    ref = spec.reference_module("qwen2")
    x = jnp.asarray([1.0 + 2.0 ** -12, 3.0])
    assert (ref._round(x, "head_control", "body") == x).all()
    assert ref._round(x, "head_control", "head")[0] == 1.0
    assert ref._round(x, "control", "body")[0] == 1.0
    assert (ref._round(x, "float32", "head") == x).all()
