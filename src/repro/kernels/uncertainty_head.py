"""Pallas TPU kernels: fused Bayesian LM head + uncertainty readout.

The serving hot spot of a Bayesian LM: for every token, draw S Monte-Carlo
samples of the output head, softmax each over the vocabulary, and reduce to
the paper's uncertainty triplet (H total / SE aleatoric / MI epistemic,
Eqs. 1-2).  Done naively this is S full-vocab softmaxes plus S sampled
(K, V) weight tensors in HBM.

Fusion strategy (two passes over the vocab tiles):

  pass 1 ``_head_stats_kernel``:
    logits_s = x @ mu + sqrt((x*x) @ sigma^2) * xi_s       (LRT sampling,
    mu/sigma read ONCE for all S samples — the photonic 'weights stay in
    the analog domain' property), written to a scratch logits buffer, with
    ONLINE (max, sumexp, sum l*exp) accumulators per (sample, row) carried
    across vocab tiles — the flash-softmax trick extended with the
    first-moment accumulator A = sum(e^{l-mx} * l), which closes SE:
        SE_s = mx + log Z - A / Z.

  pass 2 ``_head_entropy_kernel``:
    re-reads the logits tiles with the pass-1 normalizers to accumulate the
    mean predictive p_bar tile by tile:  H = -sum p_bar log p_bar, plus the
    argmax/confidence of p_bar.  No matmul in this pass — it is purely
    bandwidth-bound over the (S, M, V) logits scratch.

``uncertainty_head_fused_kernel`` is the in-kernel-entropy successor: the
(S, M, V) logits scratch — at V=4096, S=10 *larger than the weight
traffic the kernel was built to avoid* — disappears entirely.  Pass 1
emits only the (3, S, M) online stats; pass 2 *regenerates* each logits
tile (re-doing the two small matmuls and re-seeding the per-core PRNG
with the same (seed, tile id), which replays the same variates) instead of
re-reading it from HBM.  Compute is traded for the dominant HBM term.
With an explicit xi operand the same structure runs in interpret mode as
the validation path (both passes read the same xi tile).

Vocab padding is handled by masking inside the kernel (static closure over
the true V), so any vocabulary size works with 128-aligned tiles.  The fused
kernel pads nothing: its grid runs over ``cdiv(V, bv)`` tiles and the last
one is ragged, its columns past V read as whatever lies there and masked
before any reduction (no per-call copy of mu and sigma).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import rng

_NEG = -1e30


def _head_stats_kernel(x_ref, mu_ref, sg_ref, xi_ref, logits_ref, stats_ref,
                       *, v_actual: int, bv: int):
    j = pl.program_id(1)
    x = x_ref[...].astype(jnp.float32)                       # (bm, K)
    mu = mu_ref[...].astype(jnp.float32)                     # (K, bv)
    sg = sg_ref[...].astype(jnp.float32)
    mean = jnp.dot(x, mu, preferred_element_type=jnp.float32)
    var = jnp.dot(x * x, sg * sg, preferred_element_type=jnp.float32)
    std = jnp.sqrt(jnp.maximum(var, 0.0))
    logits = mean[None] + std[None] * xi_ref[...].astype(jnp.float32)
    # mask padded vocab columns
    col = j * bv + jax.lax.broadcasted_iota(jnp.int32, logits.shape, 2)
    logits = jnp.where(col < v_actual, logits, _NEG)
    logits_ref[...] = logits

    tmax = logits.max(axis=-1)                               # (S, bm)
    ex = jnp.exp(logits - tmax[..., None])
    tz = ex.sum(axis=-1)
    ta = (ex * logits).sum(axis=-1)

    @pl.when(j == 0)
    def _init():
        stats_ref[0] = tmax
        stats_ref[1] = tz
        stats_ref[2] = ta

    @pl.when(j > 0)
    def _merge():
        mx, z, a = stats_ref[0], stats_ref[1], stats_ref[2]
        mx2 = jnp.maximum(mx, tmax)
        c1 = jnp.exp(mx - mx2)
        c2 = jnp.exp(tmax - mx2)
        stats_ref[0] = mx2
        stats_ref[1] = z * c1 + tz * c2
        stats_ref[2] = a * c1 + ta * c2


def _head_entropy_kernel(logits_ref, stats_ref, h_ref, best_ref, *,
                         v_actual: int, bv: int, num_samples: int):
    j = pl.program_id(1)
    logits = logits_ref[...]                                 # (S, bm, bv)
    mx = stats_ref[0][..., None]                             # (S, bm, 1)
    z = stats_ref[1][..., None]
    pbar = (jnp.exp(logits - mx) / z).mean(axis=0)           # (bm, bv)
    contrib = pbar * jnp.log(pbar + 1e-12)
    col = j * bv + jax.lax.broadcasted_iota(jnp.int32, pbar.shape, 1)
    contrib = jnp.where(col < v_actual, contrib, 0.0)
    tile_h = contrib.sum(axis=-1)                            # (bm,)
    pbar_m = jnp.where(col < v_actual, pbar, -1.0)
    tile_best = pbar_m.max(axis=-1)
    tile_idx = (j * bv + jnp.argmax(pbar_m, axis=-1)).astype(jnp.float32)

    @pl.when(j == 0)
    def _init():
        h_ref[0] = -tile_h
        best_ref[0] = tile_best
        best_ref[1] = tile_idx

    @pl.when(j > 0)
    def _merge():
        h_ref[0] = h_ref[0] - tile_h
        better = tile_best > best_ref[0]
        best_ref[0] = jnp.where(better, tile_best, best_ref[0])
        best_ref[1] = jnp.where(better, tile_idx, best_ref[1])


def uncertainty_head_kernel(x: jax.Array, mu: jax.Array, sigma: jax.Array,
                            xi: jax.Array, *, bm: int = 128, bv: int = 512,
                            interpret: bool = False) -> dict[str, jax.Array]:
    """x: (M, K); mu/sigma: (K, V); xi: (S, M, V) -> uncertainty dict.

    Shapes must satisfy M % bm == 0; V is padded internally to bv-multiple
    (mask-correct).  K is unblocked (the head's K is d_model, fits VMEM).
    """
    m, k = x.shape
    _, v = mu.shape
    s = xi.shape[0]
    bm = min(bm, m)
    assert m % bm == 0, (m, bm)
    v_pad = (-v) % bv
    if v_pad:
        mu = jnp.pad(mu, ((0, 0), (0, v_pad)))
        sigma = jnp.pad(sigma, ((0, 0), (0, v_pad)))
        xi = jnp.pad(xi, ((0, 0), (0, 0), (0, v_pad)))
    vp = v + v_pad
    grid = (m // bm, vp // bv)

    logits, stats = pl.pallas_call(
        functools.partial(_head_stats_kernel, v_actual=v, bv=bv),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, k), lambda i, j: (i, 0)),
            pl.BlockSpec((k, bv), lambda i, j: (0, j)),
            pl.BlockSpec((k, bv), lambda i, j: (0, j)),
            pl.BlockSpec((s, bm, bv), lambda i, j: (0, i, j)),
        ],
        out_specs=[
            pl.BlockSpec((s, bm, bv), lambda i, j: (0, i, j)),
            pl.BlockSpec((3, s, bm), lambda i, j: (0, 0, i)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((s, m, vp), jnp.float32),
            jax.ShapeDtypeStruct((3, s, m), jnp.float32),
        ],
        interpret=interpret,
    )(x, mu, sigma, xi)

    h, best = pl.pallas_call(
        functools.partial(_head_entropy_kernel, v_actual=v, bv=bv,
                          num_samples=s),
        grid=grid,
        in_specs=[
            pl.BlockSpec((s, bm, bv), lambda i, j: (0, i, j)),
            pl.BlockSpec((3, s, bm), lambda i, j: (0, 0, i)),
        ],
        out_specs=[
            pl.BlockSpec((1, bm), lambda i, j: (0, i)),
            pl.BlockSpec((2, bm), lambda i, j: (0, i)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((1, m), jnp.float32),
            jax.ShapeDtypeStruct((2, m), jnp.float32),
        ],
        interpret=interpret,
    )(logits, stats)

    mx, z, a = stats[0], stats[1], stats[2]
    se = (mx + jnp.log(z) - a / z).mean(axis=0)              # (M,)
    h = h[0]
    return {"H": h, "SE": se, "MI": jnp.maximum(h - se, 0.0),
            "pred": best[1].astype(jnp.int32), "p_max": best[0]}


# ---------------------------------------------------------------------------
# fused in-kernel-entropy variant: no (S, M, V) logits scratch in HBM
# ---------------------------------------------------------------------------

def _sampled_logits_tile(x_ref, mu_ref, sg_ref, xi, j, *, v_actual: int,
                         bv: int):
    """(S, bm, bv) LRT logits of one vocab tile, padded columns masked."""
    x = x_ref[...].astype(jnp.float32)                       # (bm, K)
    mu = mu_ref[...].astype(jnp.float32)                     # (K, bv)
    sg = sg_ref[...].astype(jnp.float32)
    mean = jnp.dot(x, mu, preferred_element_type=jnp.float32)
    var = jnp.dot(x * x, sg * sg, preferred_element_type=jnp.float32)
    std = jnp.sqrt(jnp.maximum(var, 0.0))
    logits = mean[None] + std[None] * xi
    col = j * bv + jax.lax.broadcasted_iota(jnp.int32, logits.shape, 2)
    return jnp.where(col < v_actual, logits, _NEG)


def _tile_xi(seed_ref, refs_xi, shape, in_kernel_rng: bool):
    """The (S, bm, bv) standard variates of the current tile.

    In-kernel path: re-seeding with the same (seed, tile id of (i, j))
    replays the same bits in pass 1 and pass 2 — the property that makes
    the logits scratch avoidable.  Both passes run the same grid, so the
    ids agree.  Operand path: both passes read the same tile.
    """
    if in_kernel_rng:
        pltpu.prng_seed(seed_ref[0, 0], rng.tile_id(0, 1))
        return rng.normal_draw(shape)
    return refs_xi[...].astype(jnp.float32)


def _head_stats_fused_kernel(*refs, v_actual: int, bv: int,
                             num_samples: int, in_kernel_rng: bool):
    if in_kernel_rng:
        seed_ref, x_ref, mu_ref, sg_ref, stats_ref = refs
        xi_ref = None
    else:
        seed_ref, x_ref, mu_ref, sg_ref, xi_ref, stats_ref = refs
    j = pl.program_id(1)
    bm = x_ref.shape[0]
    xi = _tile_xi(seed_ref, xi_ref, (num_samples, bm, bv), in_kernel_rng)
    logits = _sampled_logits_tile(x_ref, mu_ref, sg_ref, xi, j,
                                  v_actual=v_actual, bv=bv)

    tmax = logits.max(axis=-1)                               # (S, bm)
    ex = jnp.exp(logits - tmax[..., None])
    tz = ex.sum(axis=-1)
    ta = (ex * logits).sum(axis=-1)

    @pl.when(j == 0)
    def _init():
        stats_ref[0] = tmax
        stats_ref[1] = tz
        stats_ref[2] = ta

    @pl.when(j > 0)
    def _merge():
        mx, z, a = stats_ref[0], stats_ref[1], stats_ref[2]
        mx2 = jnp.maximum(mx, tmax)
        c1 = jnp.exp(mx - mx2)
        c2 = jnp.exp(tmax - mx2)
        stats_ref[0] = mx2
        stats_ref[1] = z * c1 + tz * c2
        stats_ref[2] = a * c1 + ta * c2


def _head_entropy_fused_kernel(*refs, v_actual: int, bv: int,
                               num_samples: int, in_kernel_rng: bool):
    if in_kernel_rng:
        seed_ref, x_ref, mu_ref, sg_ref, stats_ref, h_ref, best_ref = refs
        xi_ref = None
    else:
        (seed_ref, x_ref, mu_ref, sg_ref, xi_ref, stats_ref, h_ref,
         best_ref) = refs
    j = pl.program_id(1)
    bm = x_ref.shape[0]
    xi = _tile_xi(seed_ref, xi_ref, (num_samples, bm, bv), in_kernel_rng)
    logits = _sampled_logits_tile(x_ref, mu_ref, sg_ref, xi, j,
                                  v_actual=v_actual, bv=bv)
    mx = stats_ref[0][..., None]                             # (S, bm, 1)
    z = stats_ref[1][..., None]
    pbar = (jnp.exp(logits - mx) / z).mean(axis=0)           # (bm, bv)
    contrib = pbar * jnp.log(pbar + 1e-12)
    col = j * bv + jax.lax.broadcasted_iota(jnp.int32, pbar.shape, 1)
    contrib = jnp.where(col < v_actual, contrib, 0.0)
    tile_h = contrib.sum(axis=-1)                            # (bm,)
    pbar_m = jnp.where(col < v_actual, pbar, -1.0)
    tile_best = pbar_m.max(axis=-1)
    tile_idx = (j * bv + jnp.argmax(pbar_m, axis=-1)).astype(jnp.float32)

    @pl.when(j == 0)
    def _init():
        h_ref[0] = -tile_h
        best_ref[0] = tile_best
        best_ref[1] = tile_idx

    @pl.when(j > 0)
    def _merge():
        h_ref[0] = h_ref[0] - tile_h
        better = tile_best > best_ref[0]
        best_ref[0] = jnp.where(better, tile_best, best_ref[0])
        best_ref[1] = jnp.where(better, tile_idx, best_ref[1])


def uncertainty_head_fused_kernel(x: jax.Array, mu: jax.Array,
                                  sigma: jax.Array, seed, *,
                                  num_samples: int,
                                  xi: jax.Array | None = None,
                                  bm: int = 128, bv: int = 512,
                                  interpret: bool = False
                                  ) -> dict[str, jax.Array]:
    """x: (M, K); mu/sigma: (K, V) -> uncertainty dict, no logits scratch.

    xi=None selects the in-kernel PRNG fast path (TPU only); an explicit
    xi (S, M, V) selects the validation path (runs in interpret mode).
    Pass 2 regenerates the logits tiles (two small matmuls + the replayed
    variates) instead of re-reading an (S, M, V) HBM buffer.  V need not
    divide into ``bv``: the last vocab tile is ragged and masked, so mu
    and sigma are read where they lie, never padded.
    """
    m, k = x.shape
    _, v = mu.shape
    s = num_samples
    bm = min(bm, m)
    assert m % bm == 0, (m, bm)
    grid = (m // bm, pl.cdiv(v, bv))
    in_kernel_rng = xi is None
    seed_arr = jnp.asarray(seed, jnp.int32).reshape(1, 1)

    in_specs = [
        pl.BlockSpec((1, 1), lambda i, j: (0, 0)),
        pl.BlockSpec((bm, k), lambda i, j: (i, 0)),
        pl.BlockSpec((k, bv), lambda i, j: (0, j)),
        pl.BlockSpec((k, bv), lambda i, j: (0, j)),
    ]
    operands = [seed_arr, x, mu, sigma]
    if not in_kernel_rng:
        assert xi.shape == (s, m, v), (xi.shape, (s, m, v))
        in_specs.append(pl.BlockSpec((s, bm, bv), lambda i, j: (0, i, j)))
        operands.append(xi)

    stats = pl.pallas_call(
        functools.partial(_head_stats_fused_kernel, v_actual=v, bv=bv,
                          num_samples=s, in_kernel_rng=in_kernel_rng),
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((3, s, bm), lambda i, j: (0, 0, i)),
        out_shape=jax.ShapeDtypeStruct((3, s, m), jnp.float32),
        interpret=interpret,
    )(*operands)

    h, best = pl.pallas_call(
        functools.partial(_head_entropy_fused_kernel, v_actual=v, bv=bv,
                          num_samples=s, in_kernel_rng=in_kernel_rng),
        grid=grid,
        in_specs=in_specs + [
            pl.BlockSpec((3, s, bm), lambda i, j: (0, 0, i)),
        ],
        out_specs=[
            pl.BlockSpec((1, bm), lambda i, j: (0, i)),
            pl.BlockSpec((2, bm), lambda i, j: (0, i)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((1, m), jnp.float32),
            jax.ShapeDtypeStruct((2, m), jnp.float32),
        ],
        interpret=interpret,
    )(*operands, stats)

    mx, z, a = stats[0], stats[1], stats[2]
    se = (mx + jnp.log(z) - a / z).mean(axis=0)              # (M,)
    h = h[0]
    return {"H": h, "SE": se, "MI": jnp.maximum(h - se, 0.0),
            "pred": best[1].astype(jnp.int32), "p_max": best[0]}
