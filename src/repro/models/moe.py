"""Mixture-of-Experts transformer (grok-1-314b, deepseek-moe-16b).

Layer i < ``cfg.first_k_dense`` is a dense block (``d_ff`` SwiGLU); the
others are MoE blocks:

    h = RMSNorm(x)
    x += sum_shared FFN_s(h) + sum_{e in top_k(g), e held} g_e * FFN_e(h)

with ``g = softmax(h @ W_router)`` over all ``num_experts`` in float32,
the top-k gates renormalised to sum 1 only where ``cfg.norm_topk``.

Dropless: a token's output is a function of that token alone.  There
is no capacity and no cross-token queue, so a chunk, a batch, a
prefix-cache suffix or a decode step all give a token the same experts.

A chip may hold a share of the routed experts (``MoEConfig.experts_held``
from ``expert_offset``): the router still scores every expert, a token
computes the part of its picks that this chip holds, and the shared
experts whole.  Routed experts run as grouped matmuls
(``kernels/moe_gmm.py``) on a TPU outside a mesh, and as the same math
in plain ``jnp`` elsewhere (the CPU, a serving or training mesh).

Parameters: ``blocks`` holds every layer's norms and attention (L
stacked), ``dense`` the leading dense layers' MLPs, ``moe`` the MoE
layers' router (Lm, d, E) float32, routed experts ``experts_ep`` or
``experts_tp`` (``w13`` (Lm, held, d, 2 ff) gate then up, ``w2``) and
the shared experts as one MLP of ``num_shared_experts * moe_d_ff``
columns (their outputs sum, so they concatenate).  One scan walks all L
layers; the feed-forward of layer i picks its kind with ``lax.cond``,
so the KV cache keeps the one (L, ...) layout every family shares.

Counters: the decode body and each prefill chunk return, under the
cache key ``registry.COUNTERS``, ``moe.rows`` ((token, k) assignments
routed to held experts) and ``moe.experts_hit`` (held experts that got
a token), summed over the MoE layers.  Named scopes ``moe/router`` and
``moe/experts``.

Expert parallelism under a training mesh: the expert stacks shard over
the ``model`` axis when ``expert_sharding == 'ep'``, or keep their ff
dim tensor-parallel under ``'tp'`` (grok-1's 8 experts < 16-way axis).
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.configs.base import MoEConfig
from repro.models import layers as L
from repro.models import transformer as T
from repro.models import uncertain_head as U
from repro.sharding.partition import constrain, constrain_seq

COUNTERS = "counters"          # = registry.COUNTERS


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------

def _experts_name(cfg: MoEConfig) -> str:
    return "experts_ep" if cfg.expert_sharding == "ep" else "experts_tp"


def _eff(cfg: MoEConfig) -> int:
    return cfg.moe_d_ff or cfg.d_ff


def init_attn_block(key, cfg: MoEConfig):
    return {"ln1": jnp.ones((cfg.d_model,), L.dtype_of(cfg)),
            "attn": L.init_attention(key, cfg),
            "ln2": jnp.ones((cfg.d_model,), L.dtype_of(cfg))}


def init_moe_layer(key, cfg: MoEConfig):
    dt = L.dtype_of(cfg)
    d, eff, held = cfg.d_model, _eff(cfg), cfg.held
    k1, k2, k3, k4 = jax.random.split(key, 4)
    p = {"router": {"w": jax.random.normal(
            k1, (d, cfg.num_experts), jnp.float32) * 0.02},
         _experts_name(cfg): {
             "w13": L.he_init(k2, (held, d, 2 * eff), d, dt),
             "w2": L.he_init(k3, (held, eff, d), eff, dt)}}
    if cfg.num_shared_experts:
        p["shared"] = L.init_mlp(k4, cfg,
                                 d_ff=cfg.num_shared_experts * eff)
    return p


def init_params(key, cfg: MoEConfig):
    ke, kb, kd, km, kh = jax.random.split(key, 5)
    F = cfg.first_k_dense
    p = {"embed": L.init_embed(ke, cfg),
         "blocks": jax.vmap(lambda k: init_attn_block(k, cfg))(
             jax.random.split(kb, cfg.num_layers)),
         "moe": jax.vmap(lambda k: init_moe_layer(k, cfg))(
             jax.random.split(km, cfg.moe_layers)),
         "final_norm": jnp.ones((cfg.d_model,), L.dtype_of(cfg)),
         "head": L.init_head(kh, cfg)}
    if F:
        p["dense"] = jax.vmap(lambda k: L.init_mlp(k, cfg))(
            jax.random.split(kd, F))
    return p


# ---------------------------------------------------------------------------
# the expert layer
# ---------------------------------------------------------------------------

def _use_kernel() -> bool:
    """Grouped-matmul kernels on a TPU outside any mesh; plain jnp on
    the CPU and under a serving or training mesh (Pallas kernels are
    not partitioned)."""
    from repro.kernels.ops import _on_tpu
    from repro.sharding.partition import get_mesh, get_serve_mesh
    return _on_tpu() and get_mesh() is None and get_serve_mesh() is None


def route(router_w, cfg: MoEConfig, h):
    """(gates (T, E), top-k weights (T, K), top-k ids (T, K)) of the
    normed hidden states h (T, d), all float32."""
    gates = jax.nn.softmax(jnp.dot(h.astype(jnp.float32), router_w,
                                   precision=jax.lax.Precision.HIGHEST),
                           axis=-1)
    topv, topi = jax.lax.top_k(gates, cfg.top_k)
    if cfg.norm_topk:
        topv = topv / jnp.maximum(topv.sum(-1, keepdims=True), 1e-9)
    return gates, topv, topi


def moe_ffn(mp, j, cfg: MoEConfig, x: jax.Array,
            rows: Optional[jax.Array] = None):
    """MoE layer ``j`` (of the stacks in ``mp``) over x (B, S, d), normed.

    ``rows`` (B, S) bool, optional: tokens that route to no expert (idle
    decode slots, a chunk's pad), so that they cost no expert work and
    no count.  Returns (y (B, S, d), counts (2,) int32 = [assignments to
    held experts, held experts hit], the Switch load-balance loss)."""
    from repro.kernels.moe_gmm import grouped_ffn_ref, moe_gmm
    B, S, d = x.shape
    E, held = cfg.num_experts, cfg.held
    xt = x.reshape(B * S, d)
    at = lambda a: jax.lax.dynamic_index_in_dim(a, j, 0,  # noqa: E731
                                                keepdims=False)
    with jax.named_scope("moe/router"):
        gates, topv, topi = route(at(mp["router"]["w"]), cfg, xt)
        local = topi - cfg.expert_offset
        mine = (local >= 0) & (local < held)
        if rows is not None:
            mine &= rows.reshape(B * S, 1)
        experts = jnp.where(mine, local, held).astype(jnp.int32)
        weights = jnp.where(mine, topv, 0.0)
        hits = jnp.zeros((held + 1,), jnp.int32).at[experts].add(1)
        counts = jnp.stack([mine.sum(dtype=jnp.int32),
                            (hits[:held] > 0).sum(dtype=jnp.int32)])
        # Switch load-balance loss, E * sum_e f_e p_e (training aux)
        f = jax.nn.one_hot(topi, E, dtype=jnp.float32).sum(1).mean(0)
        aux = E * jnp.sum(f * gates.mean(0))
    with jax.named_scope("moe/experts"):
        ep = mp[_experts_name(cfg)]
        args = (xt, experts, weights, ep["w13"], ep["w2"], j)
        if _use_kernel():
            from repro.kernels.ops import _on_tpu
            y = moe_gmm(*args, act=cfg.mlp_activation,
                        interpret=not _on_tpu())
        else:
            y = grouped_ffn_ref(*args, act=cfg.mlp_activation)
        if "shared" in mp:
            y = y + L.apply_mlp(jax.tree.map(at, mp["shared"]), cfg,
                                xt).astype(jnp.float32)
    return y.astype(x.dtype).reshape(B, S, d), counts, aux


def _ffn(params, cfg: MoEConfig, i, x, rows):
    """Layer i's feed-forward over normed x: the dense MLP for
    i < first_k_dense, else the MoE layer i - first_k_dense."""
    F = cfg.first_k_dense

    def routed(x):
        return moe_ffn(params["moe"], jnp.maximum(i - F, 0), cfg, x, rows)

    if not F:
        return routed(x)

    def dense(x):
        mp = jax.tree.map(lambda a: jax.lax.dynamic_index_in_dim(
            a, jnp.minimum(i, F - 1), 0, keepdims=False), params["dense"])
        with jax.named_scope("mlp"):
            y = L.apply_mlp(mp, cfg, x)
        return y, jnp.zeros((2,), jnp.int32), jnp.zeros((), jnp.float32)

    return jax.lax.cond(i < F, dense, routed, x)


def _walk(params, cfg: MoEConfig, x, attend, xs, rows=None,
          seq_parallel=False, remat=False):
    """Every layer over x: ``attend(block_params, normed_x, xs_i) ->
    (out, ys_i)`` gives the attention, then the layer's feed-forward.
    Returns (x, stacked ys, counts summed over layers, mean aux)."""
    eps = cfg.norm_eps

    def layer(x, inp):
        i, bp, xi = inp
        h, yi = attend(bp, L.rms_norm(x, bp["ln1"], eps), xi)
        x = constrain_seq(x + constrain_seq(h, seq_parallel), seq_parallel)
        y, counts, aux = _ffn(params, cfg, i, L.rms_norm(x, bp["ln2"], eps),
                              rows)
        x = constrain_seq(x + constrain_seq(y, seq_parallel), seq_parallel)
        return x, (yi, counts, aux)

    if remat:
        layer = jax.checkpoint(layer, prevent_cse=False)
    idx = jnp.arange(cfg.num_layers, dtype=jnp.int32)
    x, (ys, counts, aux) = jax.lax.scan(layer, x,
                                        (idx, params["blocks"], xs))
    return x, ys, counts.sum(0), aux.sum() / max(cfg.moe_layers, 1)


def _counters(counts) -> dict:
    return {"moe.rows": counts[0], "moe.experts_hit": counts[1]}


# ---------------------------------------------------------------------------
# training forward
# ---------------------------------------------------------------------------

def forward(params, cfg: MoEConfig, tokens: jax.Array,
            return_kv: bool = False):
    """tokens (B, S) -> (hidden (B, S, d), aux, per-layer (k, v) or None)."""
    x = L.apply_embed(params["embed"], tokens)
    x = constrain(x, "batch", None, None)
    x = constrain_seq(x, cfg.seq_parallel)
    positions = jnp.arange(tokens.shape[1])[None, :]

    def attend(bp, h, _):
        h, kv = L.apply_attention(bp["attn"], cfg, h, positions=positions,
                                  causal=True)
        return h, (kv if return_kv else None)

    x, kvs, _, aux = _walk(params, cfg, x, attend, None,
                           seq_parallel=cfg.seq_parallel,
                           remat=cfg.remat and not return_kv)
    x = L.rms_norm(x, params["final_norm"], cfg.norm_eps)
    return x, aux, kvs


def nll_loss(params, cfg: MoEConfig, batch: dict, key: jax.Array,
             aux_weight: float = 0.01):
    hidden, aux, _ = forward(params, cfg, batch["tokens"])
    head = params["head"]
    if "q" in head:
        eps = jax.random.normal(key, head["q"].mu.shape, jnp.float32)
        w = head["q"].sample_with_eps(eps)
        logits = jnp.dot(hidden, w.astype(hidden.dtype),
                         preferred_element_type=jnp.float32)
    else:
        logits = L.head_logits_mean(head, hidden, cfg)
    if cfg.logits_softcap:
        logits = cfg.logits_softcap * jnp.tanh(logits / cfg.logits_softcap)
    logits = constrain(logits, "batch", None, "model")
    labels = batch["labels"]
    valid = labels >= 0
    lab = jnp.where(valid, labels, 0)
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    tok_nll = -jnp.take_along_axis(logp, lab[..., None], axis=-1)[..., 0]
    nll = jnp.where(valid, tok_nll, 0.0).sum() / jnp.maximum(valid.sum(), 1)
    acc = ((logits.argmax(-1) == labels) & valid).sum() / \
        jnp.maximum(valid.sum(), 1)
    return nll + aux_weight * aux, {"accuracy": acc, "aux_loss": aux}


# ---------------------------------------------------------------------------
# serving
# ---------------------------------------------------------------------------

make_cache = T.make_cache  # same KV cache layout


def prefill(params, cfg: MoEConfig, tokens: jax.Array, max_len: int):
    """Run the full prompt, build the KV cache: (hidden_last, cache)."""
    x, _, (k, v) = forward(params, cfg, tokens, return_kv=True)
    S = tokens.shape[1]
    pad = ((0, 0), (0, 0), (0, max_len - S), (0, 0), (0, 0))
    return x[:, -1], {"k": jnp.pad(k, pad), "v": jnp.pad(v, pad),
                      "len": jnp.full((tokens.shape[0],), S, jnp.int32)}


def prefill_suffix(params, cfg: MoEConfig, tokens: jax.Array,
                   prefix_kv: dict, prefix_len: int):
    """Prefill ONLY the uncached suffix of a prefix-cache hit (see
    ``transformer.prefill_suffix``): the attention path is the cold
    prefill's, and the dropless expert layer gives each suffix token
    what the cold prompt gives it."""
    prefix_len = int(prefix_len)
    x = L.apply_embed(params["embed"], tokens)
    S = tokens.shape[1]
    positions = prefix_len + jnp.arange(S)[None, :]
    strips = {n: prefix_kv[n][:, :, :prefix_len] for n in ("k", "v")}

    def attend(bp, h, pkv):
        return L.apply_attention_suffix(
            bp["attn"], cfg, h, prefix_kv=(pkv["k"], pkv["v"]),
            prefix_len=prefix_len, positions=positions)

    x, (k, v), _, _ = _walk(params, cfg, x, attend, strips)
    x = L.rms_norm(x, params["final_norm"], cfg.norm_eps)
    lens = jnp.full((tokens.shape[0],), prefix_len + S, jnp.int32)
    return x[:, -1], {"k": k, "v": v, "len": lens}


def prefill_chunk(params, cfg: MoEConfig, tokens: jax.Array, cache: dict,
                  slot: jax.Array, offset: jax.Array, new_len: jax.Array,
                  span: int):
    """One chunk of an incremental prompt prefill (see
    ``transformer.prefill_chunk``).  The pad past ``new_len`` routes to
    no expert.  The cache comes back with the chunk's counters under
    ``COUNTERS``.  Named scope ``prefill_chunk``."""
    with jax.named_scope("prefill_chunk"):
        row = jax.lax.dynamic_slice_in_dim(cache["block_table"], slot, 1, 0)
        x = L.apply_embed(params["embed"], tokens)
        real = offset + jnp.arange(tokens.shape[1])[None, :] < new_len

        def attend(bp, h, kv):
            return L.apply_attention_chunk(
                bp["attn"], cfg, h, kv_pools=kv, block_row=row,
                offset=offset, span=span)

        _, (kps, vps), counts, _ = _walk(params, cfg, x, attend,
                                         (cache["k"], cache["v"]), real)
        return dict(cache, k=kps, v=vps,
                    len=cache["len"].at[slot].set(new_len),
                    **{COUNTERS: _counters(counts)})


def decode_hidden(params, cfg: MoEConfig, token: jax.Array, cache: dict,
                  rows: Optional[jax.Array] = None):
    """The KV-writing decode body (see ``transformer.decode_hidden``).

    ``rows`` (B,) bool, optional: the slots whose tokens are real; the
    others route to no expert.  The new cache carries the step's
    counters under ``COUNTERS``.  Named scope ``decode_body`` (blocks:
    ``attention``, ``mlp`` for a dense layer, ``moe/router`` and
    ``moe/experts``)."""
    with jax.named_scope("decode_body"):
        x = L.apply_embed(params["embed"], token[:, None])
        cache_len = cache["len"]
        pos = jnp.reshape(cache_len, (-1, 1))
        block_table = cache.get("block_table")     # paged layout marker

        def attend(bp, h, kv):
            with jax.named_scope("attention"):
                h, new_kv = L.apply_attention(
                    bp["attn"], cfg, h, positions=pos,
                    kv_cache=(kv["k"], kv["v"]), cache_len=cache_len,
                    block_table=block_table)
            return h, {"k": new_kv[0], "v": new_kv[1]}

        x, new_kvs, counts, _ = _walk(
            params, cfg, x, attend, {"k": cache["k"], "v": cache["v"]},
            None if rows is None else rows[:, None])
        x = L.rms_norm(x, params["final_norm"], cfg.norm_eps)
        return x[:, 0], {"k": new_kvs["k"], "v": new_kvs["v"],
                         "len": cache_len + 1, COUNTERS: _counters(counts)}


def decode_step(params, cfg: MoEConfig, token: jax.Array, cache: dict,
                key: jax.Array, rows: Optional[jax.Array] = None):
    hidden, new_cache = decode_hidden(params, cfg, token, cache, rows)
    return U.head_outputs(params, cfg, hidden, cache["len"], key), \
        new_cache
