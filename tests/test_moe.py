"""The dropless expert layer: shares, batch independence, the published
equations, the decode counters, and prefix-cache hits on ``moe``.

Each layer test runs on both paths the program picks from: plain
``jnp`` (the CPU, a mesh) and the grouped-matmul kernels
(``kernels/moe_gmm.py``), here in interpret mode.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import MoEConfig
from repro.configs.registry import get_config, reduced
from repro.launch.serve import ServeEngine
from repro.models import layers as L
from repro.models import moe
from repro.models import registry as M

from conftest import family_setup
from conftest import make_request as _req

# a float32 layer at 64 experts top-6 with 2 shared ones: the
# published routing, at a width the CPU runs in a moment
LAYER = MoEConfig(name="moe-layer", family="moe", num_layers=1,
                  d_model=64, num_heads=4, num_kv_heads=4, d_ff=96,
                  vocab_size=64, num_experts=64, top_k=6,
                  num_shared_experts=2, moe_d_ff=32, norm_topk=False,
                  param_dtype="float32")
# float32 sums of the same products in another order (per expert, or
# per share, then added): rounding, not a difference of semantics
SUM_TOL = dict(rtol=1e-5, atol=1e-6)


@pytest.fixture(params=["jnp", "kernel"])
def path(request, monkeypatch):
    """Run the routed experts on the named path."""
    monkeypatch.setattr(moe, "_use_kernel",
                        lambda: request.param == "kernel")
    return request.param


def _layer(cfg, key=0):
    return jax.vmap(lambda k: moe.init_moe_layer(k, cfg))(
        jax.random.split(jax.random.key(key), 1))


def _tokens(n, d=64, key=1):
    return jax.random.normal(jax.random.key(key), (1, n, d), jnp.float32)


def test_eight_shares_add_up_to_the_uncut_layer(path):
    """Each of 8 chips holds 8 of the 64 experts; with the shared experts
    counted once, their parts add up to the layer that holds all 64."""
    whole = _layer(LAYER)
    x = _tokens(24)
    y_all, counts_all, _ = moe.moe_ffn(whole, 0, LAYER, x)
    shared = L.apply_mlp(jax.tree.map(lambda a: a[0], whole["shared"]),
                         LAYER, x.reshape(24, 64)).reshape(x.shape)
    total, rows = shared, 0
    for s in range(8):
        cfg = dataclasses.replace(LAYER, experts_held=8, expert_offset=8 * s)
        part = dict(whole, experts_ep=jax.tree.map(
            lambda a: a[:, 8 * s:8 * (s + 1)], whole["experts_ep"]))
        y, counts, _ = moe.moe_ffn(part, 0, cfg, x)
        total = total + (y - shared)
        rows += int(counts[0])
    np.testing.assert_allclose(np.asarray(total), np.asarray(y_all),
                               **SUM_TOL)
    assert rows == int(counts_all[0]) == 24 * 6


def test_a_token_is_independent_of_its_batch_mates(path):
    """Dropless: no capacity, so a token's output is what it is alone,
    whatever else shares its batch and in whatever order."""
    cfg = dataclasses.replace(LAYER, experts_held=8, expert_offset=16)
    p = _layer(cfg)
    x = _tokens(32)
    y, _, _ = moe.moe_ffn(p, 0, cfg, x)
    perm = np.random.default_rng(0).permutation(32)
    y_perm, _, _ = moe.moe_ffn(p, 0, cfg, x[:, perm])
    np.testing.assert_allclose(np.asarray(y_perm), np.asarray(y[:, perm]),
                               **SUM_TOL)
    for t in (0, 13, 31):
        alone, _, _ = moe.moe_ffn(p, 0, cfg, x[:, t:t + 1])
        np.testing.assert_allclose(np.asarray(alone[:, 0]),
                                   np.asarray(y[:, t]), **SUM_TOL)


@pytest.mark.parametrize("norm_topk", [False, True])
def test_gates_follow_the_published_equations(path, norm_topk):
    """g = softmax(h W_router) over every expert; the top-k weights are
    renormalised only under ``norm_topk``; the layer is the shared
    experts plus each held pick's SwiGLU weighted by its gate."""
    cfg = dataclasses.replace(LAYER, norm_topk=norm_topk, experts_held=64)
    p = _layer(cfg)
    x = _tokens(5)
    h = np.asarray(x[0], np.float64)
    logits = h @ np.asarray(p["router"]["w"][0], np.float64)
    g = np.exp(logits - logits.max(-1, keepdims=True))
    g /= g.sum(-1, keepdims=True)
    _, topv, topi = moe.route(p["router"]["w"][0], cfg, x[0])
    want = np.sort(g, -1)[:, ::-1][:, :6]
    if norm_topk:
        want = want / want.sum(-1, keepdims=True)
    np.testing.assert_allclose(np.asarray(topv), want, rtol=1e-5)

    def swiglu(h, w13, w2):
        f = w2.shape[0]
        gu = h @ w13
        return (gu[:, :f] / (1 + np.exp(-gu[:, :f])) * gu[:, f:]) @ w2

    ep = jax.tree.map(lambda a: np.asarray(a[0], np.float64),
                      p["experts_ep"])
    sh = jax.tree.map(lambda a: np.asarray(a[0], np.float64), p["shared"])
    want_y = swiglu(h, np.concatenate([sh["w1"], sh["w3"]], -1), sh["w2"])
    for t in range(5):
        for k in range(6):
            e = int(topi[t, k])
            want_y[t] += float(topv[t, k]) * swiglu(
                h[t:t + 1], ep["w13"][e], ep["w2"][e])[0]
    y, _, _ = moe.moe_ffn(p, 0, cfg, x)
    np.testing.assert_allclose(np.asarray(y[0]), want_y, rtol=1e-4,
                               atol=1e-5)


def test_first_k_dense_layers_run_the_dense_mlp():
    """Layer 0 of a first_k_dense=1 stack is x += Attn; x += SwiGLU_dff,
    and layer 1 the MoE block: the forward equals those layers composed
    by hand."""
    cfg = dataclasses.replace(reduced(get_config("deepseek_moe_16b")),
                              remat=False)
    assert cfg.first_k_dense == 1 and cfg.num_layers == 2
    params = M.init_params(jax.random.key(0), cfg)
    toks = jax.random.randint(jax.random.key(1), (1, 10), 0, cfg.vocab_size)
    got, _, _ = moe.forward(params, cfg, toks)
    x = L.apply_embed(params["embed"], toks)
    pos = jnp.arange(10)[None]
    eps = cfg.norm_eps
    for i in range(2):
        bp = jax.tree.map(lambda a: a[i], params["blocks"])
        h, _ = L.apply_attention(bp["attn"], cfg,
                                 L.rms_norm(x, bp["ln1"], eps),
                                 positions=pos)
        x = x + h
        h = L.rms_norm(x, bp["ln2"], eps)
        if i == 0:
            x = x + L.apply_mlp(jax.tree.map(lambda a: a[0],
                                             params["dense"]), cfg, h)
        else:
            x = x + moe.moe_ffn(params["moe"], 0, cfg, h)[0]
    want = L.rms_norm(x, params["final_norm"], eps)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


def test_decode_counts_the_real_rows_only(path):
    """``moe.rows`` counts the held picks of the slots the scan marks
    decoding; the others route nowhere and count nothing."""
    cfg, params, prompts = family_setup("moe")
    _, cache = M.prefill(params, cfg, jnp.asarray(prompts[:3]), 16)
    tok = jnp.asarray(prompts[:3, -1])
    rows = jnp.asarray([True, False, True])
    out, _ = M.decode_step(params, cfg, tok, cache, jax.random.key(0),
                           rows=rows)
    out_all, _ = M.decode_step(params, cfg, tok, cache, jax.random.key(0))
    got = {k: int(v) for k, v in out["counters"].items()}
    every = {k: int(v) for k, v in out_all["counters"].items()}
    assert 0 < got["moe.rows"] < every["moe.rows"]
    assert every["moe.rows"] == 3 * cfg.top_k * cfg.moe_layers  # all held
    assert 0 < got["moe.experts_hit"] <= every["moe.experts_hit"]
    # the real rows decode the same token either way
    for k in ("next_token", "H"):
        np.testing.assert_array_equal(np.asarray(out[k])[[0, 2]],
                                      np.asarray(out_all[k])[[0, 2]])


@pytest.mark.parametrize("prefill", ["batch", "chunked"])
def test_prefix_cache_hit_decodes_bitwise_as_cold(prefill):
    """A prefix-cache hit on ``moe`` (copy-on-write divergence inside a
    block) serves the very token and uncertainty streams of a cold
    prefill: the dropless layer gives a suffix token what the whole
    prompt gives it."""
    cfg, params, _ = family_setup("moe")
    assert M.supports_prefix_cache(cfg)
    shared = np.asarray(jax.random.randint(jax.random.key(0), (20,), 0,
                                           cfg.vocab_size), np.int32)
    tails = np.asarray(jax.random.randint(jax.random.key(1), (4, 6), 0,
                                          cfg.vocab_size), np.int32)

    def requests():
        return [_req(i, np.concatenate([shared, tails[i]]), 6)
                for i in range(4)]

    kw = dict(num_slots=2, max_len=32, chunk=4, kv_layout="paged",
              kv_block=8, prefill_mode=prefill, prefill_chunk=8)
    cold = ServeEngine(params, cfg, **kw).run(requests())
    warm = ServeEngine(params, cfg, **kw, prefix_cache=True).run(requests())
    assert warm["prefix_cache"]["hits"] > 0
    for a, b in zip(cold["requests"], warm["requests"]):
        assert a.tokens == b.tokens, a.rid
        for k in ("H", "SE", "MI"):
            np.testing.assert_array_equal(np.asarray(getattr(a, k)),
                                          np.asarray(getattr(b, k)))
