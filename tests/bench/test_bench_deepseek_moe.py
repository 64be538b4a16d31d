"""deepseek-moe-16b-l14 in the harness, and its expert-layer metrics.

The configuration loads as the benchmark names it; its family's
parameter tree is the program's at the published widths (shapes only,
nothing allocated); its FLOPs and the ``moe_roofline`` formula are
pinned by hand-computed numbers.  At ``data/tiny-moe.json``'s size the
program's logits after a paged chunked prefill and decode steps (the
paged decode kernel and the grouped expert matmuls in interpret mode)
agree with the reference's full forward, and the cell runs end to end.
"""

import dataclasses
import json
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _paths import DATA
from bench import run, serve, spec, weights

CELL = "deepseek-moe-16b-l14.chat"
TINY = json.loads((DATA / "tiny-moe.json").read_text())
PEAK = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}


def _family():
    return spec.family_module("deepseek_moe")


def test_cell_loads_with_its_metrics():
    cell = spec.load_cell(CELL)
    assert cell.chips == 1 and cell.config["reference"] == "deepseek_moe"
    assert cell.traffic["arrivals"]["rate_per_step"] == 0.048
    names = {m["name"] for m in cell.per_layer}
    assert {"moe_roofline", "expert_rows_mean", "decode_step_ms"} <= names
    cfg = _family().arch_config(cell.config)
    assert (cfg.num_layers, cfg.first_k_dense, cfg.num_experts,
            cfg.experts_held, cfg.expert_offset, cfg.top_k,
            cfg.num_shared_experts, cfg.moe_d_ff, cfg.d_ff) == \
        (14, 1, 64, 8, 0, 6, 2, 1408, 10944)
    assert (cfg.norm_topk, cfg.norm_eps, cfg.num_kv_heads,
            cfg.vocab_size) == (False, 1e-6, 16, 12800)


def test_program_params_are_the_programs_tree_at_full_width():
    """The family's tree for the seed's weights has the shapes and types
    of the program's own ``init_params`` (traced, not allocated)."""
    from repro.models import registry as M
    config = spec.load_cell(CELL).config
    family = _family()
    cfg = family.arch_config(config)
    got = jax.eval_shape(lambda: family.program_params(
        weights.generate(config, 3), cfg))
    want = jax.eval_shape(lambda: M.init_params(jax.random.key(0), cfg))
    assert jax.tree.structure(got) == jax.tree.structure(want)
    assert all((a.shape, a.dtype) == (b.shape, b.dtype) for a, b in
               zip(jax.tree.leaves(got), jax.tree.leaves(want)))
    # 14 layers' attention 234,881,024; the dense MLP 67,239,936; 13
    # routers 1,703,936; 13 x 8 held experts 899,678,208; 13 x 2 shared
    # 224,919,552; the embedding 26,214,400 and the head's mu and rho
    # 52,428,800; 29 norms 59,392
    assert sum(a.size for a in jax.tree.leaves(got)) == 1_507_125_248


def test_body_flops_per_token_by_hand():
    """14 x 4 d^2 attention projections, 3 d 10944 in the dense layer,
    and 13 x (d 64 router + (2 shared + 6 x 8 / 64 routed) x 3 d 1408),
    times 2: 2 x (234,881,024 + 67,239,936 + 310,968,320)."""
    config = spec.load_cell(CELL).config
    assert _family().body_flops_per_token(config) == 1_226_178_560


def _ctx(records, kernel_s, traced=(10.0, 20.0), window=(10.0, 61.0)):
    return {"config": spec.load_cell(CELL).config, "peak": PEAK,
            "traced": traced, "window": window,
            "trace": {"ops": {"moe_gmm.2": kernel_s / 2,
                              "moe_gmm.3": kernel_s / 2, "fusion.1": 1.0},
                      "op_counts": {"moe_gmm.2": 1, "moe_gmm.3": 1,
                                    "fusion.1": 1}},
            "results": {"counters": {"records": records}}}


def _rec(t, kind, steps, rows, hit):
    return SimpleNamespace(t=t, kind=kind, steps=steps, values={
        "moe.rows": rows, "moe.experts_hit": hit})


def test_moe_roofline_by_hand():
    """rows 10 and 3 experts hit (inside the traced stretch; one record
    outside it): 6 x 2048 x 1408 x 10 = 173,015,040 FLOPs, 3 x 2048 x
    1408 x 2 x 3 + 2 x 2048 x 2 x 10 = 51,986,432 bytes; bytes bound at
    819e9 B/s: 63.4755 us, over 100 us of kernel time: 63.4755%."""
    assert _family().expert_work(spec.load_cell(CELL).config, 10, 3) == \
        (173_015_040.0, 51_986_432.0)
    read = spec.metric_reader("moe_roofline")
    records = [_rec(11.0, "decode", 8, 6, 2), _rec(12.0, "prefill", 1, 4, 1),
               _rec(25.0, "decode", 8, 100, 8)]
    got = read(_ctx(records, 100e-6))
    assert got == pytest.approx(100 * 51_986_432 / 819e9 / 100e-6)
    assert read(_ctx([], 100e-6)) is None
    assert read(dict(_ctx(records, 0.0), results={})) is None


def test_expert_rows_mean_by_hand():
    """Decode records in the window only: (120 + 96) rows over 16 steps
    and 13 MoE layers."""
    read = spec.metric_reader("expert_rows_mean")
    records = [_rec(11.0, "decode", 8, 120, 50), _rec(30.0, "decode", 8, 96,
                                                      40),
               _rec(12.0, "prefill", 1, 500, 60),
               _rec(70.0, "decode", 8, 1000, 60)]
    assert read(_ctx(records, 1.0)) == pytest.approx(216 / (16 * 13))
    assert read(dict(_ctx(records, 1.0), results={})) is None


def test_engine_logits_match_the_reference(monkeypatch):
    """A paged, chunked prefill of the prompt and then decode steps,
    with the paged decode kernel and the grouped expert matmuls in
    interpret mode: at every decoded
    position the program's mean logits (hidden @ mu) agree with the
    reference's full forward over the whole sequence.  Both run in
    float32; the tolerance is float32 rounding through three layers and
    a 64-wide contraction into the head, summed in other orders (flash
    attention's online softmax against a plain one, per-expert products
    against grouped ones)."""
    from repro.models import moe
    from repro.models import registry as M
    monkeypatch.setattr(moe, "_use_kernel", lambda: True)
    family = _family()
    cfg = dataclasses.replace(family.arch_config(TINY), decode_attn="kernel")
    w = weights.generate(TINY, 11)
    params = family.program_params(w, cfg)
    ref = spec.reference_module("deepseek_moe")
    P, n, chunk, kvb = 40, 6, 16, 16
    seq = np.asarray(jax.random.randint(jax.random.key(5), (P + n,), 0,
                                        TINY["vocab_size"]), np.int32)
    # what the engine's model sees: the prompt, its last token again
    # (the first decode input), then the decoded tokens
    fed = np.concatenate([seq[:P], seq[P - 1:P - 1 + n]])
    x_ref = ref.hidden_states(w, jnp.asarray(fed), TINY, "float32")
    want = np.asarray(x_ref @ w["head_mu"])[P:]

    cache = M.make_cache(cfg, 2, 64, layout="paged", kv_block=kvb)
    cache = dict(cache, block_table=cache["block_table"].at[1].set(
        jnp.arange(4, dtype=jnp.int32)))
    span = -(-P // kvb) * kvb
    for off in range(0, P, chunk):
        toks = np.zeros((chunk,), np.int32)
        real = min(chunk, P - off)
        toks[:real] = seq[off:off + real]
        cache, counters = M.take_counters(M.prefill_chunk(
            params, cfg, jnp.asarray(toks)[None], cache, jnp.int32(1),
            jnp.int32(off), jnp.int32(off + real), span))
        assert int(counters["moe.rows"]) > 0
    got = []
    for i in range(n):
        tok = jnp.asarray([0, fed[P + i]], jnp.int32)
        hidden, cache = M.decode_hidden(params, cfg, tok, cache)
        got.append(np.asarray(hidden[1] @ params["head"]["q"].mu))
    np.testing.assert_allclose(np.stack(got), want, rtol=2e-4, atol=2e-4)


def test_tiny_cell_runs_end_to_end_and_counts():
    """The tiny configuration served by the engine for a few seconds:
    correct against the reference, with the expert counters kept."""
    cell = spec.Cell(
        name="tiny-moe.chat", chips=1, config=TINY,
        traffic=json.loads((DATA / "tiny-chat.json").read_text()),
        limits=json.loads((DATA / "tiny-limits.json").read_text()),
        end_to_end=[{"name": n, "unit": "u"} for n in (
            "tok_s", "ttft_p95_ms", "tpot_p95_ms", "setup_s")],
        per_layer=[])
    seen = {}
    build = serve.build_engine

    def keep(*a, **k):
        eng = build(*a, **k)
        run_ = eng.run

        def run_and_keep(reqs):
            out = run_(reqs)
            seen["counters"] = out["counters"]
            return out
        eng.run = run_and_keep
        return eng

    serve.build_engine = keep
    try:
        out = run.run_cell(cell, 2147483653, 2.0, False, jax.devices(),
                           log=lambda m: None)
    finally:
        serve.build_engine = build
    assert out["correct"], out["_lines"]
    totals = seen["counters"]["totals"]
    assert totals["moe.rows"] > 0 and totals["moe.experts_hit"] > 0
    assert {r.kind for r in seen["counters"]["records"]} == {"decode",
                                                              "prefill"}
