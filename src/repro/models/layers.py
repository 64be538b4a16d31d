"""Shared model layers: norms, RoPE, flash-style attention, MLPs, heads.

Pure-functional: ``init_*`` builds param pytrees (plain dicts), ``apply``
functions are jit/scan/remat friendly.  All matmuls keep a bf16 storage /
f32 accumulation policy via ``preferred_element_type``.
"""

from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.core.bayesian import GaussianVariational
# the readable-depth clamp both paged-attention paths apply
from repro.kernels.paged_attention import mapped_span
# serving-TP seam: no-op unless launch.engine.runner set a serve mesh —
# each call site sits DIRECTLY on a sharded producer (column-parallel
# matmul outputs; the kv-head-sharded attention read before wo), so the
# forced all-gather (pure data movement) replicates the operand before
# any elementwise tail or contraction can absorb the shard; that
# producer-adjacent placement is what keeps sharded decode bitwise
# equal to the unsharded reference (see partition.gather_rep)
from repro.sharding.partition import gather_rep


def dtype_of(cfg: ArchConfig):
    return jnp.dtype(cfg.param_dtype)


# --------------------------------------------------------------------------
# primitives
# --------------------------------------------------------------------------

def rms_norm(x: jax.Array, w: jax.Array, eps: float = 1e-5) -> jax.Array:
    x32 = x.astype(jnp.float32)
    var = jnp.mean(x32 * x32, axis=-1, keepdims=True)
    return (x32 * jax.lax.rsqrt(var + eps)).astype(x.dtype) * w


def rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """x: (..., S, H, D); positions: (..., S) int32."""
    d = x.shape[-1]
    half = d // 2
    freqs = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    ang = positions[..., None].astype(jnp.float32) * freqs  # (..., S, half)
    cos = jnp.cos(ang)[..., None, :]
    sin = jnp.sin(ang)[..., None, :]
    x1, x2 = x[..., :half], x[..., half:]
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


def _mm(x, w):
    # output dtype == activation dtype: the MXU still accumulates f32
    # internally, but the PARTIAL-SUM output of sharded contractions is
    # bf16, so GSPMD's row-parallel all-reduces move bf16 not f32
    # (2x collective bytes; Megatron's 'bf16 reduce' — §Perf/grok it.5).
    return jnp.dot(x, w, preferred_element_type=x.dtype)


def he_init(key, shape, fan_in, dtype):
    return (jax.random.normal(key, shape, jnp.float32)
            / jnp.sqrt(float(max(fan_in, 1)))).astype(dtype)


# --------------------------------------------------------------------------
# flash-style chunked attention (pure jnp online softmax)
# --------------------------------------------------------------------------

def _attn_chunk_spec(nq: int, B: int, H: int):
    """Sharding for the (nq, B, qc, H, D) q-chunk stack.

    Heads shard over 'model' when they divide; otherwise the q-CHUNK axis
    takes the model axis (sequence-parallel attention).  GQA archs whose
    head counts don't divide the 16-way model axis (qwen2-7b: 28H) force
    GSPMD into per-tile score all-reduces under head sharding — the
    chunk-parallel layout keeps every score tile device-local
    (EXPERIMENTS.md §Perf/qwen2_7b-prefill).
    """
    from repro.sharding.partition import get_mesh
    mesh = get_mesh()
    if mesh is None or "model" not in mesh.axis_names:
        return None
    msize = mesh.shape["model"]
    if H % msize == 0:
        return (None, "batch", None, "model", None)
    if nq % msize == 0:
        return ("model", "batch", None, None, None)
    return (None, "batch", None, None, None)


def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = True, q_chunk: int = 512,
                    kv_chunk: int = 1024,
                    q_offset: int = 0) -> jax.Array:
    """Memory-bounded attention (flash-style online softmax).

    q: (B, Sq, H, D); k/v: (B, Sk, Hkv, D) with H % Hkv == 0 (GQA).
    Online-softmax over kv chunks (sequential scan), VMAPPED over q
    chunks — q chunks are independent, so the chunk axis is shardable
    (sequence parallelism) and XLA may batch it.  Peak score buffer is
    (chunks_local, B, H, q_chunk, kv_chunk).

    The whole body runs under ``jax.named_scope('fused_attention')``: on
    TPU this region maps to the Pallas kernel
    ``kernels/flash_attention.py`` (same tiling, VMEM-resident score
    tiles); the roofline accounting uses the scope to model the fused
    kernel's HBM traffic (launch.hlo_cost skip_byte_scopes).

    ``q_offset``: absolute position of q[0] (prefill continuation).
    """
    from repro.sharding.partition import constrain
    B, Sq, H, D = q.shape
    _, Sk, Hkv, _ = k.shape
    rep = H // Hkv
    scale = 1.0 / jnp.sqrt(jnp.float32(D))
    qc = min(q_chunk, Sq)
    kc = min(kv_chunk, Sk)
    # pad to chunk multiples
    pq = (-Sq) % qc
    pk = (-Sk) % kc
    qp = jnp.pad(q, ((0, 0), (0, pq), (0, 0), (0, 0)))
    kp = jnp.pad(k, ((0, 0), (0, pk), (0, 0), (0, 0)))
    vp = jnp.pad(v, ((0, 0), (0, pk), (0, 0), (0, 0)))
    nq, nk = qp.shape[1] // qc, kp.shape[1] // kc
    qb = qp.reshape(B, nq, qc, H, D).transpose(1, 0, 2, 3, 4)
    kb = kp.reshape(B, nk, kc, Hkv, D).transpose(1, 0, 2, 3, 4)
    vb = vp.reshape(B, nk, kc, Hkv, D).transpose(1, 0, 2, 3, 4)
    spec = _attn_chunk_spec(nq, B, H)
    if spec is not None:
        qb = constrain(qb, *spec)

    with jax.named_scope("fused_attention"):
        def q_step(qi, blk):                               # (), (B,qc,H,D)
            qpos = q_offset + qi * qc + jnp.arange(qc)

            def kv_step(carry, kj_blks):
                m, l, acc = carry
                kj, kblk, vblk = kj_blks
                kk = jnp.repeat(kblk, rep, axis=2)         # (B,kc,H,D)
                vv = jnp.repeat(vblk, rep, axis=2)
                s = jnp.einsum("bqhd,bkhd->bhqk", blk, kk,
                               preferred_element_type=jnp.float32) * scale
                kp_abs = kj * kc + jnp.arange(kc)
                mask = kp_abs < Sk
                if causal:
                    mask = mask[None, :] & \
                        (kp_abs[None, :] <= qpos[:, None])
                else:
                    mask = jnp.broadcast_to(mask[None, :], (qc, kc))
                s = jnp.where(mask[None, None], s, -jnp.inf)
                m2 = jnp.maximum(m, s.max(axis=-1))
                # guard rows with no valid keys yet
                m2s = jnp.where(jnp.isinf(m2), 0.0, m2)
                p = jnp.exp(s - m2s[..., None])
                p = jnp.where(mask[None, None], p, 0.0)
                corr = jnp.where(jnp.isinf(m), 0.0, jnp.exp(m - m2s))
                l2 = l * corr + p.sum(axis=-1)
                # p in V's dtype, as in decode_attention
                acc2 = acc * corr[..., None] + jnp.einsum(
                    "bhqk,bkhd->bhqd", p.astype(vv.dtype), vv,
                    preferred_element_type=jnp.float32)
                return (m2, l2, acc2), None

            m0 = jnp.full((B, H, qc), -jnp.inf, jnp.float32)
            l0 = jnp.zeros((B, H, qc), jnp.float32)
            a0 = jnp.zeros((B, H, qc, D), jnp.float32)
            (m, l, acc), _ = jax.lax.scan(
                kv_step, (m0, l0, a0), (jnp.arange(nk), kb, vb))
            out = acc / jnp.maximum(l, 1e-20)[..., None]
            return out.transpose(0, 2, 1, 3).astype(q.dtype)

        outs = jax.vmap(q_step)(jnp.arange(nq), qb)        # (nq,B,qc,H,D)

    out = outs.transpose(1, 0, 2, 3, 4).reshape(B, nq * qc, H, D)
    return out[:, :Sq]


def decode_attention(q: jax.Array, k_cache: jax.Array, v_cache: jax.Array,
                     cache_len: jax.Array) -> jax.Array:
    """Single-token attention against a (B, S, Hkv, D) cache.

    q: (B, 1, H, D); cache_len: () or (B,) number of valid cache slots.
    GQA via grouped dots — NOT jnp.repeat, which would materialize the
    KV cache rep x (H/Hkv-fold HBM read amplification at decode).

    MHA (rep == 1) pads the replica axis to two rows (one zero row,
    discarded after): XLA lowers a 1-row contraction through a
    matrix-vector emitter whose f32 association differs from the >= 2
    row gemm, and the block-sparse decode kernel — which reduces per
    (slot, kv-head) tile and is bit-exact against this function — can
    only reproduce the gemm form.  Padding keeps BOTH paths on one
    canonical association for every head layout; rep >= 2 bits are
    untouched (tests/test_paged_attention.py).
    """
    B, S, Hkv, D = k_cache.shape
    H = q.shape[2]
    rep = H // Hkv
    # serve-TP: q arrives head-sharded (columns of wq).  rep is a FREE
    # dim of the grouped dot below, so a shard would shrink the local
    # row count and flip XLA between the gemm and matrix-vector
    # emitters — the exact association split this function's rep==1
    # padding exists to prevent.  All-gather q (pure data movement);
    # the kv-head axis g is a BATCH dim of the dot, so a kv-head-
    # sharded cache keeps the per-row reduction shape and stays exact.
    q = gather_rep(q)
    qg = q.reshape(B, Hkv, rep, D)
    if rep == 1:
        qg = jnp.concatenate([qg, jnp.zeros_like(qg)], axis=2)
    # each (slot, kv-head) batch of both dots is the paged kernel's own
    # 2-D product, (R, D) x (S, D)^T then (R, S) x (S, D), with K and V
    # kv-head major: jnp.einsum over the (B, S, Hkv, D) strips lets
    # XLA:CPU contract transposed operands instead (an (S, R) or (D, R)
    # output), whose f32 sums associate differently from the kernel's
    dims = lambda c: (((3,), (c,)), ((0, 1), (0, 1)))  # noqa: E731
    s = jax.lax.dot_general(qg, jnp.swapaxes(k_cache, 1, 2), dims(3),
                            preferred_element_type=jnp.float32) / jnp.sqrt(
                                jnp.float32(D))                 # (B,g,R,S)
    pos = jnp.arange(S)
    mask = pos[None, :] < jnp.reshape(cache_len, (-1, 1))
    s = jnp.where(mask[:, None, None, :], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    # probabilities meet V in V's dtype: a bf16 x bf16 MXU product with
    # f32 accumulation, which the paged kernels reproduce exactly (an
    # f32 x bf16 dot would leave the rounding of p to the backend's
    # default matmul precision)
    out = jax.lax.dot_general(p.astype(v_cache.dtype),
                              jnp.swapaxes(v_cache, 1, 2), dims(2),
                              preferred_element_type=jnp.float32)
    if rep == 1:
        out = out[:, :, :1]
    return out.reshape(B, 1, H, D).astype(q.dtype)


# --------------------------------------------------------------------------
# paged KV cache: (slot, logical_pos) -> (block, offset) indirection
# --------------------------------------------------------------------------

def paged_table_width(max_len: int, kv_block: int) -> int:
    """Block-table width MB: blocks needed to span max_len tokens."""
    return -(-max_len // kv_block)


def init_block_table(batch: int, max_len: int, kv_block: int) -> jax.Array:
    """Fresh all-unmapped (-1) per-slot block table."""
    return jnp.full((batch, paged_table_width(max_len, kv_block)), -1,
                    jnp.int32)


def paged_scatter(pool: jax.Array, block_table: jax.Array,
                  lens: jax.Array, new: jax.Array) -> jax.Array:
    """Scatter per-slot KV entries into a global block pool.

    pool: (NB, Hkv, BS, D) physical blocks of BS tokens each, kv-head
    major so that one head's block is a contiguous (BS, D) tile (the
    tile the paged Pallas kernels fetch; Mosaic's tiling rule wants the
    last two block dims divisible by (8, 128) or whole);
    block_table: (B, MB) int32, logical block j of slot b lives in
    physical block ``block_table[b, j]`` (-1 = unmapped);
    lens: (B,) current logical depth per slot; new: (B, S, Hkv, D)
    entries for logical positions ``lens[b] + [0, S)``.

    Writes to unmapped (-1) or out-of-table logical positions are
    DROPPED — the paged analog of the dense layout's out-of-bounds
    scatter drop, and what makes post-eviction junk steps harmless (an
    evicted slot's table row is all -1).
    """
    BS = pool.shape[2]
    MB = block_table.shape[1]
    B, S = new.shape[:2]
    idx = lens[:, None] + jnp.arange(S)[None, :]            # (B, S) logical
    tbl = idx // BS
    rows = jnp.arange(B)[:, None]
    phys = jnp.where(tbl < MB,
                     block_table[rows, jnp.minimum(tbl, MB - 1)], -1)
    # sentinel must be OOB-positive: jnp wraps negative indices
    # numpy-style BEFORE the mode="drop" check, so -1 would silently hit
    # the last physical block instead of dropping
    phys = jnp.where(phys < 0, pool.shape[0], phys)
    # the two (B, S) index arrays sit around the head slice, so numpy
    # indexing puts their broadcast dims first: (B, S, Hkv, D) == new
    return pool.at[phys, :, idx % BS].set(new, mode="drop")


def copy_block(pool: jax.Array, src: jax.Array, dst: jax.Array) -> jax.Array:
    """Copy-on-write: duplicate physical block ``src`` into ``dst``.

    pool: (NB, Hkv, BS, D).  The serving engine calls this (vmapped over
    the layer axis of every paged KV leaf) when a slot is about to
    scatter into a block it shares with the prefix cache: the slot's
    table entry is swapped to ``dst`` host-side and the divergent write
    lands in the copy, leaving the cached original untouched.
    """
    return pool.at[dst].set(pool[src])


def paged_gather(pool: jax.Array, block_table: jax.Array) -> jax.Array:
    """Gather each slot's logical KV strip from the block pool.

    pool: (NB, Hkv, BS, D); block_table: (B, MB).  Returns (B, MB*BS,
    Hkv, D) — the dense logical view attention reads.  The gather is
    block-granular (one index per block, not per token: logical position
    j lives at (table[j // BS], :, j % BS), so whole blocks move
    contiguously).  Unmapped entries gather block 0 — which may be a
    prefix-cache-OWNED block holding another request's tokens — so
    callers must mask by ``mapped_span``, not raw ``cache_len``: a
    slot whose depth outruns its mapped prefix (an evicted slot's junk
    steps) would otherwise feed cached bytes into its softmax
    (tests/test_paged_attention.py::TestUnmappedMasking).
    """
    g = pool[jnp.maximum(block_table, 0)]          # (B, MB, Hkv, BS, D)
    g = jnp.swapaxes(g, 2, 3)                      # (B, MB, BS, Hkv, D)
    return g.reshape(g.shape[0], -1, *g.shape[3:])


# --------------------------------------------------------------------------
# attention block (GQA, optional QKV bias, RoPE)
# --------------------------------------------------------------------------

def init_attention(key, cfg: ArchConfig, d_model: Optional[int] = None):
    d = d_model or cfg.d_model
    hd, H, Hkv = cfg.head_dim, cfg.num_heads, cfg.num_kv_heads
    ks = jax.random.split(key, 4)
    dt = dtype_of(cfg)
    p = {
        "wq": he_init(ks[0], (d, H * hd), d, dt),
        "wk": he_init(ks[1], (d, Hkv * hd), d, dt),
        "wv": he_init(ks[2], (d, Hkv * hd), d, dt),
        "wo": he_init(ks[3], (H * hd, d), H * hd, dt),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((H * hd,), dt)
        p["bk"] = jnp.zeros((Hkv * hd,), dt)
        p["bv"] = jnp.zeros((Hkv * hd,), dt)
    return p


def apply_attention(p, cfg: ArchConfig, x: jax.Array, *,
                    positions: jax.Array, causal: bool = True,
                    kv_cache: Optional[tuple] = None,
                    cache_len: Optional[jax.Array] = None,
                    block_table: Optional[jax.Array] = None,
                    cross_kv: Optional[tuple] = None):
    """Returns (out, new_kv) where new_kv is the updated (k, v) cache slot
    content for decode, or the computed (k, v) for prefill, or None.

    ``block_table`` selects the paged-KV layout: ``kv_cache`` is then a
    pair of global block POOLS (NB, Hkv, BS, D) instead of per-slot
    strips (B, S, Hkv, D), and reads/writes go through the
    (slot, logical_pos) -> (block, offset) indirection of
    ``paged_scatter`` / ``paged_gather``.  Bit-exact against the dense
    layout when the logical span MB*BS equals the dense max_len: masked
    positions differ only in garbage that ``decode_attention`` replaces
    with -inf before the softmax either way (positions past the mapped
    prefix included — ``mapped_span`` clamps the readable depth).

    ``cfg.decode_attn`` picks the paged decode read path:
    ``'gather'`` (the bit-exact reference) materializes the full
    logical strip; ``'kernel'`` runs the block-sparse Pallas kernel
    (``kernels/paged_attention.py``) that reads only mapped, in-depth
    blocks straight from the pool — same bits, HBM reads scaling with
    ``cache_len`` (tests/test_paged_attention.py)."""
    B, S, d = x.shape
    H, Hkv, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    q = _mm(x, p["wq"])
    if "bq" in p:
        q = q + p["bq"]
    q = q.reshape(B, S, H, hd)

    if cross_kv is not None:
        k, v = cross_kv
        out = flash_attention(q, k, v, causal=False,
                              q_chunk=cfg.attn_q_chunk,
                              kv_chunk=cfg.attn_kv_chunk)
        new_kv = None
    else:
        k = _mm(x, p["wk"])
        v = _mm(x, p["wv"])
        if "bk" in p:
            k, v = k + p["bk"], v + p["bv"]
        # serve-TP: wk/wv column shards land on hd after the reshape —
        # the CONTRACTED dim of the score dot — and a q-side all-gather
        # leaves that shard as the dot's only sharding, so GSPMD would
        # partial-sum over D shards and all-reduce (a re-associated
        # float reduction).  Gather adjacent to the projection instead:
        # pure data movement, and every attention operand downstream is
        # replicated (or kv-head/batch-sharded via the cache, which
        # never re-associates a contraction).
        k = gather_rep(k).reshape(B, S, Hkv, hd)
        v = gather_rep(v).reshape(B, S, Hkv, hd)
        q = rope(q, positions, cfg.rope_theta)
        k = rope(k, positions, cfg.rope_theta)
        if kv_cache is not None:
            kc, vc = kv_cache
            # cache_len: () -> every row appends at the same depth;
            # (B,) -> slot-indexed cache, each row writes its own offset
            # (continuous batching: slots admitted at different times sit
            # at different depths).  The scatter handles any S (chunked
            # appends included); rows already at capacity land out of
            # bounds and are dropped.
            lens = jnp.broadcast_to(jnp.reshape(cache_len, (-1,)), (B,))
            if block_table is not None:
                with jax.named_scope("kv_write"):
                    kc = paged_scatter(kc, block_table, lens, k)
                    vc = paged_scatter(vc, block_table, lens, v)
                if cfg.decode_attn == "kernel" and S == 1:
                    # block-sparse Pallas kernel: reads only mapped,
                    # in-depth blocks from the pool — HBM traffic
                    # scales with cache_len, not the MB*BS span; the
                    # gather path below stays the bit-exact reference
                    from repro.kernels.ops import paged_decode_attention
                    out = paged_decode_attention(q, kc, vc, block_table,
                                                 lens + S)
                else:
                    # readable depth clamped to the mapped prefix so an
                    # unmapped entry's block-0 gather fallback never
                    # reaches the softmax (block 0 may be owned by the
                    # prefix cache)
                    eff = mapped_span(block_table, kc.shape[2], lens + S)
                    out = decode_attention(q,
                                           paged_gather(kc, block_table),
                                           paged_gather(vc, block_table),
                                           eff)
            else:
                rows = jnp.arange(B)[:, None]
                idx = lens[:, None] + jnp.arange(S)[None, :]
                with jax.named_scope("kv_write"):
                    kc = kc.at[rows, idx].set(k, mode="drop")
                    vc = vc.at[rows, idx].set(v, mode="drop")
                out = decode_attention(q, kc, vc, lens + S)
            new_kv = (kc, vc)
        else:
            out = flash_attention(q, k, v, causal=causal,
                                  q_chunk=cfg.attn_q_chunk,
                                  kv_chunk=cfg.attn_kv_chunk,
                                  q_offset=0)
            new_kv = (k, v)
    out = out.reshape(B, S, H * hd)
    return _mm(gather_rep(out), p["wo"]), new_kv


def apply_attention_suffix(p, cfg: ArchConfig, x: jax.Array, *,
                           prefix_kv: tuple, prefix_len: int,
                           positions: jax.Array):
    """Prefill continuation: attention for the UNCACHED suffix of a
    prompt whose first ``prefix_len`` positions already live in the KV
    cache (prefix-cache hit).

    x: (B, S, d) suffix hidden states for absolute positions
    ``prefix_len + [0, S)``; ``prefix_kv``: (k, v) logical strips
    (B, prefix_len, Hkv, D) — exactly the cached span, sliced by the
    caller; ``positions``: (B or 1, S) absolute RoPE positions
    (``prefix_len + arange(S)``).  ``prefix_len`` must be a STATIC
    Python int (one compile per hit length), not a traced value.

    Returns (out, (k_suffix, v_suffix)) — the suffix K/V the caller
    scatters into the pool at logical offset ``prefix_len``.

    BIT-EXACTNESS: this runs the same ``flash_attention`` code path as
    the cold full-prompt prefill, attending over exactly
    ``prefix_len + S`` keys — cached prefix concatenated with the
    suffix K/V, i.e. the identical operand values at the identical
    indices AND the identical reduction extent as the cold path's
    suffix rows.  Equal reduction lengths matter: XLA's lane/remainder
    handling associates a k-axis sum differently for different key
    counts, so attending over a longer padded-and-masked strip would
    drift in the last ulp even though masked positions contribute
    exact zeros.  Queries are row-independent, so the q-chunk geometry
    differing from the cold path is irrelevant.  Tested bitwise in
    tests/test_prefix_cache.py.
    """
    B, S, d = x.shape
    H, Hkv, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    q = _mm(x, p["wq"])
    if "bq" in p:
        q = q + p["bq"]
    q = q.reshape(B, S, H, hd)
    k = _mm(x, p["wk"])
    v = _mm(x, p["wv"])
    if "bk" in p:
        k, v = k + p["bk"], v + p["bv"]
    # serve-TP: gather next to the projection (see apply_attention)
    k = gather_rep(k).reshape(B, S, Hkv, hd)
    v = gather_rep(v).reshape(B, S, Hkv, hd)
    q = rope(q, positions, cfg.rope_theta)
    k = rope(k, positions, cfg.rope_theta)
    kc, vc = prefix_kv
    ks = jnp.concatenate([kc.astype(k.dtype), k], axis=1)
    vs = jnp.concatenate([vc.astype(v.dtype), v], axis=1)
    out = flash_attention(q, ks, vs, causal=True,
                          q_chunk=cfg.attn_q_chunk,
                          kv_chunk=cfg.attn_kv_chunk,
                          q_offset=prefix_len)
    out = out.reshape(B, S, H * hd)
    return _mm(gather_rep(out), p["wo"]), (k, v)


def apply_attention_chunk(p, cfg: ArchConfig, x: jax.Array, *,
                          kv_pools: tuple, block_row: jax.Array,
                          offset: jax.Array, span: int):
    """Chunked-prefill attention for ONE slot against its paged KV pool.

    x: (1, S, d) hidden states of a prompt chunk occupying absolute
    positions ``offset + [0, S)``; ``kv_pools``: (k, v) block pools
    (NB, Hkv, BS, D); ``block_row``: (1, MB) the slot's table row;
    ``offset``: TRACED int32 scalar (chunk progress is data, not shape);
    ``span``: STATIC token extent of the whole prompt's attention
    reduction — the bucketed width W for padding-safe families, the
    exact prompt length for exact-extent ones.

    The chunk's K/V are scattered into the pool FIRST, then the strip is
    read back over ``span`` tokens and attended with the same
    ``flash_attention`` (or the multi-query block-sparse kernel when
    ``cfg.decode_attn == 'kernel'``) the batch prefill uses.

    BIT-EXACTNESS vs batch prefill: every chunk reduces over the SAME
    static extent ``span`` that the batch path uses for the whole
    prompt, with not-yet-written positions causally masked — masked
    positions contribute exact zeros regardless of the junk they hold,
    and equal reduction extents keep XLA's k-axis sum association
    identical (see ``apply_attention_suffix``).  Q rows are independent,
    so splitting them across chunks is free.  Tested bitwise in
    tests/test_chunked_prefill.py.
    """
    B, S, d = x.shape
    H, Hkv, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    q = _mm(x, p["wq"])
    if "bq" in p:
        q = q + p["bq"]
    q = q.reshape(B, S, H, hd)
    k = _mm(x, p["wk"])
    v = _mm(x, p["wv"])
    if "bk" in p:
        k, v = k + p["bk"], v + p["bv"]
    # serve-TP: gather next to the projection (see apply_attention)
    k = gather_rep(k).reshape(B, S, Hkv, hd)
    v = gather_rep(v).reshape(B, S, Hkv, hd)
    positions = offset + jnp.arange(S)[None, :]
    q = rope(q, positions, cfg.rope_theta)
    k = rope(k, positions, cfg.rope_theta)
    kc, vc = kv_pools
    lens = jnp.broadcast_to(jnp.reshape(offset, (-1,)), (B,))
    kc = paged_scatter(kc, block_row, lens, k)
    vc = paged_scatter(vc, block_row, lens, v)
    BS = kc.shape[2]
    nb = -(-span // BS)
    if cfg.decode_attn == "kernel":
        from repro.kernels.ops import paged_prefill_attention
        out = paged_prefill_attention(q, kc, vc, block_row[:, :nb],
                                      offset, span=span,
                                      kv_chunk=cfg.attn_kv_chunk)
    else:
        ks = paged_gather(kc, block_row[:, :nb])[:, :span]
        vs = paged_gather(vc, block_row[:, :nb])[:, :span]
        out = flash_attention(q, ks, vs, causal=True,
                              q_chunk=cfg.attn_q_chunk,
                              kv_chunk=cfg.attn_kv_chunk,
                              q_offset=offset)
    out = out.reshape(B, S, H * hd)
    return _mm(gather_rep(out), p["wo"]), (kc, vc)


def make_cross_kv(p, cfg: ArchConfig, enc_out: jax.Array):
    """Precompute cross-attention K/V from encoder output (no RoPE)."""
    B, S, _ = enc_out.shape
    Hkv, hd = cfg.num_kv_heads, cfg.head_dim
    # serve-TP: gather next to the projection (see apply_attention)
    k = gather_rep(_mm(enc_out, p["wk"])).reshape(B, S, Hkv, hd)
    v = gather_rep(_mm(enc_out, p["wv"])).reshape(B, S, Hkv, hd)
    return k, v


# --------------------------------------------------------------------------
# MLP (gated silu/gelu or nemotron squared-ReLU)
# --------------------------------------------------------------------------

def init_mlp(key, cfg: ArchConfig, d_model: Optional[int] = None,
             d_ff: Optional[int] = None):
    d = d_model or cfg.d_model
    ff = d_ff or cfg.d_ff
    dt = dtype_of(cfg)
    ks = jax.random.split(key, 3)
    if cfg.mlp_activation == "relu2":
        return {"w1": he_init(ks[0], (d, ff), d, dt),
                "w2": he_init(ks[1], (ff, d), ff, dt)}
    return {"w1": he_init(ks[0], (d, ff), d, dt),       # gate
            "w3": he_init(ks[1], (d, ff), d, dt),       # up
            "w2": he_init(ks[2], (ff, d), ff, dt)}      # down


def apply_mlp(p, cfg: ArchConfig, x: jax.Array) -> jax.Array:
    # serve-TP: gather DIRECTLY on each column-sharded matmul output,
    # before the activation.  A gather placed later — on act(g)*u just
    # ahead of the down-projection — leaves dot(all-gather(h), w2) in
    # the module, which XLA rewrites into per-shard partial dots plus
    # an all-reduce over the ff contraction: a re-associated float sum
    # that breaks bitwise parity with the unsharded engine.  With the
    # gather adjacent to the producer the down-projection sees a plain
    # replicated operand and stays a single local gemm.
    if cfg.mlp_activation == "relu2":
        h = gather_rep(_mm(x, p["w1"]))
        h = jnp.square(jax.nn.relu(h))
        return _mm(h, p["w2"])
    g = gather_rep(_mm(x, p["w1"]))
    u = gather_rep(_mm(x, p["w3"]))
    act = jax.nn.silu if cfg.mlp_activation == "silu" else jax.nn.gelu
    return _mm(act(g) * u, p["w2"])


# --------------------------------------------------------------------------
# embeddings + (Bayesian) output head
# --------------------------------------------------------------------------

def init_embed(key, cfg: ArchConfig):
    dt = dtype_of(cfg)
    return {"table": he_init(key, (cfg.vocab_size, cfg.d_model),
                             cfg.d_model, dt)}


def apply_embed(p, tokens: jax.Array) -> jax.Array:
    from repro.sharding.partition import constrain
    x = jnp.take(p["table"], tokens, axis=0)
    return constrain(x, "batch", None, None)


def init_head(key, cfg: ArchConfig):
    """Deterministic or Gaussian-variational output projection."""
    if cfg.bayesian_head:
        return {"q": GaussianVariational.init(
            key, (cfg.d_model, cfg.vocab_size), fan_in=cfg.d_model,
            init_sigma=cfg.head_init_sigma, dtype=jnp.float32)}
    return {"w": he_init(key, (cfg.d_model, cfg.vocab_size), cfg.d_model,
                         dtype_of(cfg))}


def head_logits_mean(p, x: jax.Array, cfg: ArchConfig) -> jax.Array:
    """Mean logits (training fwd uses MC draws via head_logits_sampled)."""
    w = p["q"].mu if "q" in p else p["w"]
    # vocab columns are exact per-shard; gather DIRECTLY on the dot
    # output.  A gather placed after the softcap would let GSPMD sink
    # the elementwise ops across the all-gather, parking the gather
    # next to the softmax/entropy V-reductions in
    # uncertainty_from_logits — which XLA then splits into per-shard
    # partial sums, a re-associated reduction that drifts the
    # uncertainty floats off the unsharded reference.
    logits = gather_rep(jnp.dot(x, w.astype(x.dtype),
                                preferred_element_type=jnp.float32))
    if cfg.logits_softcap:
        c = cfg.logits_softcap
        logits = c * jnp.tanh(logits / c)
    return logits


def decode_head_noise(key: jax.Array, cache_len: jax.Array,
                      num_samples: int, vocab: int) -> jax.Array:
    """Per-(slot, depth) operand noise for the Bayesian decode head.

    Returns an (S, B, V) f32 xi tensor where column b is drawn from
    ``fold_in(fold_in(key, b), cache_len[b])`` — slot index and the
    slot's own token depth, NOT the engine's global step.  A slot's
    noise stream is therefore a function of its position alone: two
    schedules that reach the same (slot, depth) through different
    global interleavings (batch vs chunked prefill, a slot paused on a
    block-grant shortfall, different ``--chunk`` sizes) draw identical
    variates, which is what keeps the engine's decode streams bit-exact
    across scheduling policies (tests/test_serve.py,
    tests/test_chunked_prefill.py).
    """
    depths = jnp.broadcast_to(jnp.reshape(cache_len, (-1,)).astype(
        jnp.int32), (cache_len.shape[0] if cache_len.ndim else 1,))
    slots = jnp.arange(depths.shape[0], dtype=jnp.int32)

    def one(slot, depth):
        kb = jax.random.fold_in(jax.random.fold_in(key, slot), depth)
        return jax.random.normal(kb, (num_samples, vocab), jnp.float32)

    return jax.vmap(one, in_axes=(0, 0), out_axes=1)(slots, depths)


def head_logits_sampled(p, x: jax.Array, cfg: ArchConfig,
                        xi: jax.Array) -> jax.Array:
    """One LRT draw of the Bayesian head: x (..., d), xi (..., V).

    This is the jnp form of kernels/lrt_matmul (kernel used on TPU).
    """
    if "q" not in p:
        return head_logits_mean(p, x, cfg)
    q = p["q"]
    x32 = x.astype(jnp.float32)
    # serve-TP: gather each vocab-sharded dot output before the LRT
    # combine (see head_logits_mean for why the gather must sit on the
    # producer, not after the elementwise tail)
    mean = gather_rep(x32 @ q.mu)
    var = gather_rep((x32 * x32) @ (q.sigma ** 2))
    logits = mean + jnp.sqrt(jnp.maximum(var, 0.0)) * xi
    if cfg.logits_softcap:
        c = cfg.logits_softcap
        logits = c * jnp.tanh(logits / c)
    return logits
