"""The engine loop's span recorder (``ServeStats.span`` / ``iteration``).

  * every iteration records its phases, each inside ``serve.iteration``;
  * an iteration's device waits never exceed it, its ``rows`` are the
    slots its scan decoded, and the record deque keeps a fixed bound;
  * ``Request.prefill_wait_s`` is 0 on a full prefix hit and positive for
    a prompt whose first chunk waits behind another's;
  * a ``jax.profiler`` trace of a run holds the ``serve.*`` host events,
    and the benchmark's trace reducer labels an idle time by them;
  * the recorder changes nothing served: tokens and H, SE, MI, p_max are
    bitwise those of a run whose recorder is a no-op;
  * the lowered ``scan_decode`` carries the model's named scopes.
"""

import dataclasses
import re
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import family_setup
from repro.core.entropy import KernelEntropy
from repro.launch.engine import Request, ServeEngine
from repro.launch.engine import stats as stats_mod

ROOT = Path(__file__).resolve().parents[1]

PHASES_EVERY_ITERATION = ("serve.admit", "serve.grant")
PHASES_OF_A_SCAN = ("serve.decode_dispatch", "serve.decode_wait",
                    "serve.decode_fetch", "serve.harvest")


def _prompt(cfg, n, seed):
    rng = np.random.default_rng(seed)
    return rng.integers(1, cfg.vocab_size - 1, size=n).astype(np.int32)


def _requests(cfg, *, n=6, gen=6):
    """Two 26-token prompts, each sent three times: the first two
    admissions miss the prefix cache and walk their prompts in chunks
    (the second behind the first), the later ones arrive once those
    finished and hit in full."""
    a, b = _prompt(cfg, 26, 1), _prompt(cfg, 26, 2)
    return [Request(rid=i, prompt=(a if i % 2 == 0 else b).copy(),
                    max_new_tokens=gen, arrival_step=0 if i < 2 else 40)
            for i in range(n)]


def _engine(cfg, params, **kw):
    return ServeEngine(params, cfg, num_slots=2, max_len=40, chunk=4,
                       kv_layout="paged", kv_block=8, kv_blocks=24,
                       prefix_cache=True, decode_attn="kernel",
                       prefill_mode="chunked", prefill_chunk=8, **kw)


@pytest.fixture(scope="module")
def dense():
    cfg, params, _ = family_setup("dense")
    return cfg, params


@pytest.fixture(scope="module")
def recorded(dense):
    """One run with every closed span logged as (name, t0, t1)."""
    cfg, params = dense
    closed = []
    close = stats_mod.ServeStats._close

    def spy(self, sp):
        closed.append((sp.name, sp.t0, sp.t0 + sp.s))
        close(self, sp)
    mp = pytest.MonkeyPatch()
    mp.setattr(stats_mod.ServeStats, "_close", spy)
    seen_active = []
    eng = _engine(cfg, params)
    scan = eng._scan

    def scan_spy(params, tok, cache, step0, active, flags):
        seen_active.append(int(np.asarray(active).sum()))
        return scan(params, tok, cache, step0, active, flags)
    eng._scan = scan_spy
    try:
        res = eng.run(_requests(cfg))
    finally:
        mp.undo()
    return res, closed, seen_active


def test_every_iteration_records_its_phases_inside_it(recorded):
    res, closed, _ = recorded
    iters = [(t0, t1) for name, t0, t1 in closed
             if name == "serve.iteration"]
    records = res["spans"]["iterations"]
    assert len(iters) == len(records) > 0
    assert [(r.t0, r.t1) for r in records] == iters
    inside = {i: set() for i in range(len(iters))}
    for name, t0, t1 in closed:
        if name == "serve.iteration":
            continue
        assert name.startswith("serve.")
        owner = [i for i, (a, b) in enumerate(iters) if a <= t0 and t1 <= b]
        assert len(owner) == 1, (name, t0, t1)
        inside[owner[0]].add(name)
    for i, rec in enumerate(records):
        assert set(PHASES_EVERY_ITERATION) <= inside[i]
        if rec.rows:
            assert set(PHASES_OF_A_SCAN) <= inside[i]
        else:
            assert not set(PHASES_OF_A_SCAN) & inside[i]
    phases = res["spans"]["phases"]
    assert phases["serve.iteration"]["count"] == len(records)
    for name in PHASES_OF_A_SCAN:
        assert phases[name]["count"] == res["chunks_run"]
    assert phases["serve.prefill_chunk"]["count"] == res["prefill_chunks"]
    for tot in phases.values():
        assert 0 < tot["max_s"] <= tot["s"]


def test_waits_never_exceed_the_iteration(recorded):
    res, closed, _ = recorded
    for rec in res["spans"]["iterations"]:
        assert 0.0 <= rec.wait_s <= rec.t1 - rec.t0
    waits = sum(r.wait_s for r in res["spans"]["iterations"])
    assert waits == pytest.approx(sum(
        t1 - t0 for name, t0, t1 in closed if name in stats_mod.WAITS))


def test_decode_rows_are_the_slots_decoding(recorded):
    res, _, seen_active = recorded
    rows = [r.rows for r in res["spans"]["iterations"] if r.rows]
    assert rows == seen_active
    assert max(rows) == 2


def test_results_keys_derive_from_the_recorder(recorded):
    res, _, _ = recorded
    phases = res["spans"]["phases"]
    decode = sum(phases[n]["s"] for n in stats_mod.DECODE if n in phases)
    assert res["decode_s"] == pytest.approx(decode)
    assert res["decode_tok_per_s"] == pytest.approx(
        res["gen_tokens"] / decode)
    ends = [r.t1 for r in res["spans"]["iterations"] if r.rows]
    assert res["decode_interarrival_p99_s"] == pytest.approx(
        float(np.max(np.diff(ends))))
    assert res["prefill_compile_s"] > 0.0
    assert res["escalation"]["decode_s"] == 0.0


def test_prefill_wait_is_zero_on_a_hit_and_positive_behind_a_job(recorded):
    res, _, _ = recorded
    reqs = res["requests"]
    assert res["prefix_cache"]["hits"] == 4
    # the second prompt's first chunk waited for the first prompt's four
    assert reqs[1].prefill_wait_s > reqs[0].prefill_wait_s >= 0.0
    for r in reqs[2:]:
        assert r.prefill_wait_s == 0.0
    assert all(r._t_admitted is None for r in reqs)


def test_iteration_records_are_bounded(dense, monkeypatch):
    cfg, params = dense
    monkeypatch.setattr(stats_mod, "ITERATIONS_KEPT", 3)
    res = _engine(cfg, params).run(_requests(cfg, n=2))
    spans = res["spans"]
    assert spans["iterations_kept"] == 3
    assert len(spans["iterations"]) == 3
    assert spans["phases"]["serve.iteration"]["count"] > 3
    # the kept records are the newest: the last one holds the last finish
    assert spans["iterations"][-1].t1 >= max(
        r.t_finish for r in res["requests"])


class _NoSpan:
    t0 = s = 0.0

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return None


def _served(res):
    return [(np.asarray(r.tokens),
             *(np.asarray(getattr(r, n), np.float32)
               for n in ("H", "SE", "MI", "p_max")))
            for r in res["requests"]]


@pytest.mark.parametrize("entropy", ["kernel", "operand"])
def test_recorder_changes_nothing_served(dense, monkeypatch, entropy):
    cfg, params = dense
    kw = {}
    if entropy == "kernel":
        cfg = dataclasses.replace(cfg, head_entropy="kernel")
        kw = {"entropy": KernelEntropy(seed=5)}
    with_spans = _served(_engine(cfg, params, **kw).run(_requests(cfg)))
    monkeypatch.setattr(stats_mod.ServeStats, "span",
                        lambda self, name: _NoSpan())
    monkeypatch.setattr(stats_mod.ServeStats, "iteration",
                        lambda self, i: _NoSpan())
    without = _served(_engine(cfg, params, **kw).run(_requests(cfg)))
    assert len(with_spans) == len(without)
    for a, b in zip(with_spans, without):
        for x, y in zip(a, b):
            np.testing.assert_array_equal(x, y)


def test_profiler_trace_holds_the_serve_spans(dense, tmp_path):
    if str(ROOT) not in sys.path:
        sys.path.insert(0, str(ROOT))
    from bench import trace as tr
    cfg, params = dense
    eng = _engine(cfg, params)
    eng.run(_requests(cfg, n=2))                 # compile outside the trace
    # as bench/run.py traces: no Python function events
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        with jax.profiler.TraceAnnotation("bench.test"):
            eng.run(_requests(cfg, n=2))
    finally:
        jax.profiler.stop_trace()
    ev = tr.load(str(tmp_path))
    names = {name for name, *_ in ev.host}
    for name in ("serve.iteration", "serve.admit", "serve.prefill_chunk",
                 "serve.prefill_wait", "serve.grant", *PHASES_OF_A_SCAN):
        assert name in names, sorted(n for n in names if "serve" in n)
    harvests = [(s, e) for name, s, e, _ in ev.host
                if name == "serve.harvest"]
    assert harvests
    s, e = harvests[0]
    assert tr.label(ev, (s + e) / 2) == "bench.test / serve.harvest"


def test_scan_decode_hlo_carries_the_named_scopes(dense):
    cfg, params = dense
    cfg = dataclasses.replace(cfg, head_entropy="kernel")
    eng = ServeEngine(params, cfg, num_slots=2, max_len=32, chunk=2,
                      kv_layout="paged", kv_block=8, decode_attn="kernel",
                      prefill_mode="chunked", prefill_chunk=8,
                      entropy=KernelEntropy(seed=3))
    runner = eng.runner
    flags = {k: jnp.zeros((2,), jnp.int32)
             for k in ("epistemic", "aleatoric")}
    hlo = eng._scan.lower(eng.params, jnp.zeros((2,), jnp.int32),
                          runner.make_cache(2), jnp.asarray(0, jnp.int32),
                          jnp.zeros((2,), bool), flags).as_text(
        dialect="hlo", debug_info=True)
    op_names = set(re.findall(r'op_name="([^"]+)"', hlo))
    for scope in ("decode_step/decode_body", "attention/kv_write", "mlp/"):
        assert any(scope in n for n in op_names), scope
    # the head's sigma is made once by the runner and its operands are
    # never padded: no step recomputes softplus(rho) or copies mu/sigma
    for gone in ("bayes_head/sigma", "vocab_pad"):
        assert not any(gone in n for n in op_names), gone
    assert not any("bayes_head" in n and n.endswith("/log1p")
                   for n in op_names)
