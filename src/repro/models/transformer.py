"""Dense decoder-only transformer (qwen2*, codeqwen, nemotron, phi-3-vision).

Covers the ``dense`` and ``vlm`` families.  VLM/audio frontends are stubs
per the assignment: ``prefix_embeds`` (precomputed patch/frame embeddings)
overwrite the leading positions of the token embedding sequence.

Layers are stacked on a leading L axis and consumed with ``jax.lax.scan``
(+ optional per-layer remat) so the HLO stays O(1) in depth — essential
for the 64-layer dry-runs to compile quickly and for XLA's scheduler to
pipeline the FSDP all-gathers (weights of layer i+1 prefetch during i).

The output head follows the paper: a Gaussian-variational projection
(``bayesian_head=True``) trained with SVI (one weight-space draw per step)
and sampled N times at serving to produce the (H, SE, MI) uncertainty
triplet per generated token.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.models import layers as L
from repro.models import uncertain_head as U
from repro.sharding.partition import constrain, constrain_seq


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------

def init_block(key, cfg: ArchConfig):
    k1, k2 = jax.random.split(key)
    return {
        "ln1": jnp.ones((cfg.d_model,), L.dtype_of(cfg)),
        "attn": L.init_attention(k1, cfg),
        "ln2": jnp.ones((cfg.d_model,), L.dtype_of(cfg)),
        "mlp": L.init_mlp(k2, cfg),
    }


def init_params(key, cfg: ArchConfig):
    ke, kb, kh = jax.random.split(key, 3)
    block_keys = jax.random.split(kb, cfg.num_layers)
    blocks = jax.vmap(lambda k: init_block(k, cfg))(block_keys)
    return {
        "embed": L.init_embed(ke, cfg),
        "blocks": blocks,                      # stacked (L, ...)
        "final_norm": jnp.ones((cfg.d_model,), L.dtype_of(cfg)),
        "head": L.init_head(kh, cfg),
    }


# ---------------------------------------------------------------------------
# forward (training / prefill)
# ---------------------------------------------------------------------------

def _block_fwd(bp, cfg: ArchConfig, x, positions):
    # sequence-parallel residual stream: x lives S-sharded over 'model';
    # rms_norm is position-local so it runs sharded; the attention/MLP
    # inputs gather S implicitly (GSPMD AG) and their row-parallel
    # outputs reduce-scatter back into the sharded stream.
    h, kv = L.apply_attention(bp["attn"], cfg, L.rms_norm(x, bp["ln1"]),
                              positions=positions, causal=True)
    x = x + constrain_seq(h, cfg.seq_parallel)
    x = constrain_seq(x, cfg.seq_parallel)
    x = x + constrain_seq(L.apply_mlp(bp["mlp"], cfg,
                                      L.rms_norm(x, bp["ln2"])),
                          cfg.seq_parallel)
    x = constrain_seq(x, cfg.seq_parallel)
    return x, kv


def forward(params, cfg: ArchConfig, tokens: jax.Array,
            prefix_embeds: Optional[jax.Array] = None,
            return_kv: bool = False):
    """tokens: (B, S) -> hidden (B, S, d); optionally per-layer (k, v)."""
    x = L.apply_embed(params["embed"], tokens)
    if prefix_embeds is not None:
        P = prefix_embeds.shape[1]
        x = jnp.concatenate(
            [prefix_embeds.astype(x.dtype), x[:, P:]], axis=1)
    x = constrain(x, "batch", None, None)
    x = constrain_seq(x, cfg.seq_parallel)
    positions = jnp.arange(tokens.shape[1])[None, :]

    def scan_step(x, bp):
        if cfg.remat:
            y, kv = jax.checkpoint(
                lambda b, xx: _block_fwd(b, cfg, xx, positions),
                prevent_cse=False)(bp, x)
        else:
            y, kv = _block_fwd(bp, cfg, x, positions)
        return y, (kv if return_kv else None)

    g = cfg.remat_group
    if (cfg.scan_layers and cfg.remat and g and not return_kv
            and cfg.num_layers % g == 0):
        # hierarchical remat: checkpoint every g layers — the saved
        # residual stack shrinks L -> L/g slabs (grok: 64 -> 8), trading
        # one extra inner recompute during bwd (EXPERIMENTS.md §Perf).
        grouped = jax.tree.map(
            lambda a: a.reshape(cfg.num_layers // g, g, *a.shape[1:]),
            params["blocks"])

        def outer_step(x, bps):
            def inner(xx, bp):
                y, _ = _block_fwd(bp, cfg, xx, positions)
                return y, None

            y, _ = jax.checkpoint(
                lambda b, xx: jax.lax.scan(inner, xx, b),
                prevent_cse=False)(bps, x)
            return y, None

        x, kvs = jax.lax.scan(outer_step, x, grouped)
    elif cfg.scan_layers:
        x, kvs = jax.lax.scan(scan_step, x, params["blocks"])
    else:
        kvs = []
        blocks = [jax.tree.map(lambda a, i=i: a[i], params["blocks"])
                  for i in range(cfg.num_layers)]
        for bp in blocks:
            x, kv = scan_step(x, bp)
            kvs.append(kv)
        if return_kv:
            kvs = jax.tree.map(lambda *xs: jnp.stack(xs), *kvs)
    x = L.rms_norm(x, params["final_norm"], cfg.norm_eps)
    return (x, kvs) if return_kv else (x, None)


# ---------------------------------------------------------------------------
# losses
# ---------------------------------------------------------------------------

def nll_loss(params, cfg: ArchConfig, batch: dict, key: jax.Array):
    """Mean next-token NLL with one weight-space draw of the Bayesian head.

    batch: {tokens (B,S), labels (B,S)} (labels already shifted; -100 pad).
    """
    hidden, _ = forward(params, cfg, batch["tokens"],
                        prefix_embeds=batch.get("prefix_embeds"))
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
    tok_nll = jnp.where(valid, tok_nll, 0.0)
    nll = tok_nll.sum() / jnp.maximum(valid.sum(), 1)
    acc = ((logits.argmax(-1) == labels) & valid).sum() / \
        jnp.maximum(valid.sum(), 1)
    return nll, {"accuracy": acc}


# ---------------------------------------------------------------------------
# serving: prefill + MC-sampled uncertain decode
# ---------------------------------------------------------------------------

def make_cache(cfg: ArchConfig, batch: int, max_len: int,
               dtype=None, layout: str = "dense", kv_block: int = 16,
               num_blocks: int = 0):
    """Slot-indexed KV cache: ``len`` is per-slot (batch,) so decode slots
    admitted at different times sit at independent depths.

    ``layout='dense'`` (the reference layout) gives each slot one
    contiguous ``max_len`` strip.  ``layout='paged'`` replaces the strips
    with a global pool of ``num_blocks`` blocks of ``kv_block`` tokens
    each plus a (batch, MB) ``block_table`` mapping logical block j of a
    slot to its physical block (-1 = unmapped); the host-side
    ``launch.serve.BlockAllocator`` owns the pool."""
    dt = dtype or L.dtype_of(cfg)
    if layout == "paged":
        nb = num_blocks or batch * L.paged_table_width(max_len, kv_block)
        shape = (cfg.num_layers, nb, cfg.num_kv_heads, kv_block,
                 cfg.head_dim)
        return {"k": jnp.zeros(shape, dt), "v": jnp.zeros(shape, dt),
                "len": jnp.zeros((batch,), jnp.int32),
                "block_table": L.init_block_table(batch, max_len,
                                                  kv_block)}
    shape = (cfg.num_layers, batch, max_len, cfg.num_kv_heads, cfg.head_dim)
    return {"k": jnp.zeros(shape, dt), "v": jnp.zeros(shape, dt),
            "len": jnp.zeros((batch,), jnp.int32)}


def prefill(params, cfg: ArchConfig, tokens: jax.Array, max_len: int,
            prefix_embeds: Optional[jax.Array] = None):
    """Run the full prompt, build the KV cache, return (hidden_last, cache)."""
    hidden, kvs = forward(params, cfg, tokens, prefix_embeds=prefix_embeds,
                          return_kv=True)
    S = tokens.shape[1]
    k, v = kvs  # (L, B, S, Hkv, hd) each (scan stacks the per-layer kv)
    pad = max_len - S
    k = jnp.pad(k, ((0, 0), (0, 0), (0, pad), (0, 0), (0, 0)))
    v = jnp.pad(v, ((0, 0), (0, 0), (0, pad), (0, 0), (0, 0)))
    cache = {"k": k, "v": v,
             "len": jnp.full((tokens.shape[0],), S, jnp.int32)}
    return hidden[:, -1], cache


def prefill_suffix(params, cfg: ArchConfig, tokens: jax.Array,
                   prefix_kv: dict, prefix_len: int):
    """Prefill ONLY the uncached suffix of a prefix-cache hit.

    tokens: (B, S) the suffix token IDs (absolute positions
    ``prefix_len + [0, S)``); ``prefix_kv``: {"k", "v"} logical strips
    (L, B, W, Hkv, hd) gathered from the block pool with
    ``W >= prefix_len``; ``prefix_len``: STATIC Python int (one compile
    per (hit, suffix) length pair — equal attention reduction extents
    are what make this path bit-exact, see
    ``layers.apply_attention_suffix``).

    Returns (hidden_last, sub) where sub holds the SUFFIX-ONLY K/V
    strips (L, B, S, Hkv, hd) — the caller scatters them at logical
    offset ``prefix_len`` (``write_slot(..., offset=prefix_len)``) —
    and the slot's full depth ``len = prefix_len + S``.  Suffix rows
    are bit-exact vs a cold prefill of the whole prompt (same
    flash-attention path; tested in tests/test_prefix_cache.py).
    """
    prefix_len = int(prefix_len)
    x = L.apply_embed(params["embed"], tokens)
    x = constrain(x, "batch", None, None)
    x = constrain_seq(x, cfg.seq_parallel)
    S = tokens.shape[1]
    positions = prefix_len + jnp.arange(S)[None, :]
    strips = {n: prefix_kv[n][:, :, :prefix_len] for n in ("k", "v")}

    def scan_step(x, bpkv):
        bp, pkv = bpkv
        h, kv = L.apply_attention_suffix(
            bp["attn"], cfg, L.rms_norm(x, bp["ln1"]),
            prefix_kv=(pkv["k"], pkv["v"]), prefix_len=prefix_len,
            positions=positions)
        x = x + constrain_seq(h, cfg.seq_parallel)
        x = constrain_seq(x, cfg.seq_parallel)
        x = x + constrain_seq(L.apply_mlp(bp["mlp"], cfg,
                                          L.rms_norm(x, bp["ln2"])),
                              cfg.seq_parallel)
        x = constrain_seq(x, cfg.seq_parallel)
        return x, kv

    x, kvs = jax.lax.scan(scan_step, x, (params["blocks"], strips))
    x = L.rms_norm(x, params["final_norm"], cfg.norm_eps)
    k, v = kvs
    lens = jnp.full((tokens.shape[0],), prefix_len + S, jnp.int32)
    return x[:, -1], {"k": k, "v": v, "len": lens}


def prefill_chunk(params, cfg: ArchConfig, tokens: jax.Array, cache: dict,
                  slot: jax.Array, offset: jax.Array, new_len: jax.Array,
                  span: int):
    """One chunk of an incremental (Sarathi-style) prompt prefill.

    tokens: (1, S) chunk token IDs for absolute positions
    ``offset + [0, S)`` of the slot's prompt (final chunks of
    padding-safe families carry junk pads past the true prompt end —
    causally masked, then overwritten by decode writes).  ``span``:
    STATIC attention extent = the prompt's bucketed width W.  Writes the
    chunk's per-layer K/V into the slot's pool blocks and pins the
    slot's ``len`` to ``new_len`` (the true prefilled depth — this also
    heals the +1/step drift that interleaved decode scans inflict on a
    mid-prefill slot's len).  Hidden outputs are discarded: the engine
    re-feeds the prompt's last token at activation, same as batch
    prefill.  Bit-exact vs ``prefill`` on the same bucketed prompt
    (tests/test_chunked_prefill.py).  Named scope ``prefill_chunk``."""
    with jax.named_scope("prefill_chunk"):
        return _prefill_chunk(params, cfg, tokens, cache, slot, offset,
                              new_len, span)


def _prefill_chunk(params, cfg, tokens, cache, slot, offset, new_len,
                   span):
    row = jax.lax.dynamic_slice_in_dim(cache["block_table"], slot, 1, 0)
    x = L.apply_embed(params["embed"], tokens)

    def scan_step(x, bpkv):
        bp, kp, vp = bpkv
        h, (kp, vp) = L.apply_attention_chunk(
            bp["attn"], cfg, L.rms_norm(x, bp["ln1"]),
            kv_pools=(kp, vp), block_row=row, offset=offset, span=span)
        x = x + h
        x = x + L.apply_mlp(bp["mlp"], cfg, L.rms_norm(x, bp["ln2"]))
        return x, (kp, vp)

    _, (kps, vps) = jax.lax.scan(
        scan_step, x, (params["blocks"], cache["k"], cache["v"]))
    return dict(cache, k=kps, v=vps,
                len=cache["len"].at[slot].set(new_len))


def _decode_block(bp, cfg, x, kv, cache_len, block_table=None):
    """One layer of single-token decode; kv: dict k/v (B, S, Hkv, hd)
    strips, or (NB, Hkv, BS, hd) block pools when ``block_table`` is set
    (read via gather or the block-sparse kernel per ``cfg.decode_attn``).

    cache_len () or (B,): per-slot depths give per-slot RoPE positions.
    """
    pos = jnp.reshape(cache_len, (-1, 1))
    with jax.named_scope("attention"):
        h, new_kv = L.apply_attention(
            bp["attn"], cfg, L.rms_norm(x, bp["ln1"]), positions=pos,
            kv_cache=(kv["k"], kv["v"]), cache_len=cache_len,
            block_table=block_table)
    x = x + h
    with jax.named_scope("mlp"):
        x = x + L.apply_mlp(bp["mlp"], cfg, L.rms_norm(x, bp["ln2"]))
    return x, {"k": new_kv[0], "v": new_kv[1]}


def decode_hidden(params, cfg: ArchConfig, token: jax.Array, cache: dict):
    """The KV-writing decode body: embed -> blocks -> final norm.

    token: (B,) last sampled token.  Writes the step's K/V at each
    slot's PRE-step depth and returns ``(hidden, new_cache)`` with
    ``len`` advanced by one; the uncertain head over ``hidden`` is the
    shared ``uncertain_head.head_outputs`` (fed the pre-step depths).
    Named scope ``decode_body`` (blocks: ``attention``, its
    ``kv_write``, ``mlp``).
    """
    with jax.named_scope("decode_body"):
        return _decode_hidden(params, cfg, token, cache)


def _decode_hidden(params, cfg: ArchConfig, token: jax.Array, cache: dict):
    x = L.apply_embed(params["embed"], token[:, None])
    x = constrain(x, "batch", None, None)
    cache_len = cache["len"]
    block_table = cache.get("block_table")     # paged layout marker

    def scan_step(x, bpkv):
        bp, kv = bpkv
        x, new_kv = _decode_block(bp, cfg, x, kv, cache_len, block_table)
        return x, new_kv

    x, new_kvs = jax.lax.scan(
        scan_step, x, (params["blocks"], {"k": cache["k"], "v": cache["v"]}))
    x = L.rms_norm(x, params["final_norm"], cfg.norm_eps)
    return x[:, 0], {"k": new_kvs["k"], "v": new_kvs["v"],
                     "len": cache_len + 1}


def decode_step(params, cfg: ArchConfig, token: jax.Array, cache: dict,
                key: jax.Array):
    """One uncertain decode step.

    token: (B,) last sampled token.  Returns (outputs, new_cache) where
    outputs = {next_token, H, SE, MI, p_max} per sequence — the paper's
    uncertainty triplet computed from cfg.mc_samples LRT head draws
    (fused in kernels/uncertainty_head on TPU; jnp math in
    ``uncertain_head`` lowers everywhere and is what the dry-run
    compiles).
    """
    hidden, new_cache = decode_hidden(params, cfg, token, cache)
    return U.head_outputs(params, cfg, hidden, cache["len"], key), \
        new_cache
