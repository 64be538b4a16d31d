"""Public jit'd wrappers around the Pallas kernels.

Selection logic: on TPU backends the Pallas path runs compiled; elsewhere
(this CPU container) `interpret=True` executes the kernel body in Python
for correctness, and callers who need speed on CPU (tests over big sweeps,
examples) can force the pure-jnp oracle with ``impl='ref'``.

The ``*_sampled`` wrappers are the seed-driven fast path: on TPU the
kernels generate their entropy in-register (``in_kernel_rng=True``, zero
HBM entropy bytes); in interpret mode the same fused kernels run with an
explicit operand derived host-side from the same seed (the validation
path); ``impl='ref'`` routes to the seeded jnp oracle.  ``entropy_bytes``
reports the HBM randomness traffic of each configuration so benchmarks
measure the win instead of asserting it.

All wrappers handle padding to kernel tile multiples and strip it off, so
arbitrary problem shapes are accepted.
"""

from __future__ import annotations

import functools
from typing import Literal

import jax
import jax.numpy as jnp

from repro.kernels import ref
from repro.kernels.bayes_matmul import (
    bayes_matmul_fused_kernel, bayes_matmul_kernel, lrt_matmul_fused_kernel,
    lrt_matmul_kernel)
from repro.kernels.flash_attention import flash_attention_kernel
from repro.kernels.paged_attention import (paged_decode_attention_kernel,
                                           paged_prefill_attention_kernel)
from repro.kernels.photonic_conv import (
    photonic_conv_fused_kernel, photonic_conv_kernel)
from repro.kernels.uncertainty_head import (
    uncertainty_head_fused_kernel, uncertainty_head_kernel)

Impl = Literal["auto", "pallas", "ref"]


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def _resolve(impl: Impl) -> tuple[bool, bool]:
    """-> (use_pallas, interpret)"""
    if impl == "ref":
        return False, False
    if impl == "pallas":
        return True, not _on_tpu()
    return (True, False) if _on_tpu() else (False, False)


def _pad_to(x: jax.Array, axis: int, mult: int) -> jax.Array:
    pad = (-x.shape[axis]) % mult
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


@functools.partial(jax.jit, static_argnames=("impl", "bm", "bn", "bk"))
def bayes_matmul(x, mu, sigma, eps, impl: Impl = "auto",
                 bm: int = 128, bn: int = 128, bk: int = 512):
    """Sampled-weight GEMM y = x @ (mu + sigma*eps); any (M, K, N)."""
    use_pallas, interp = _resolve(impl)
    if not use_pallas:
        return ref.bayes_matmul(x, mu, sigma, eps)
    m, k = x.shape
    _, n = mu.shape
    xp = _pad_to(_pad_to(x, 0, bm), 1, bk)
    mup = _pad_to(_pad_to(mu, 0, bk), 1, bn)
    sgp = _pad_to(_pad_to(sigma, 0, bk), 1, bn)
    epp = _pad_to(_pad_to(eps, 0, bk), 1, bn)
    y = bayes_matmul_kernel(xp, mup, sgp, epp, bm=bm, bn=bn, bk=bk,
                            interpret=interp)
    return y[:m, :n]


@functools.partial(jax.jit, static_argnames=("impl", "bm", "bn", "bk"))
def lrt_matmul(x, mu, sigma, xi, impl: Impl = "auto",
               bm: int = 128, bn: int = 128, bk: int = 512):
    """Local-reparameterization GEMM; xi is output-space (M, N) noise."""
    use_pallas, interp = _resolve(impl)
    if not use_pallas:
        return ref.lrt_matmul(x, mu, sigma, xi)
    m, k = x.shape
    _, n = mu.shape
    xp = _pad_to(_pad_to(x, 0, bm), 1, bk)
    mup = _pad_to(_pad_to(mu, 0, bk), 1, bn)
    sgp = _pad_to(_pad_to(sigma, 0, bk), 1, bn)
    xip = _pad_to(_pad_to(xi, 0, bm), 1, bn)
    y = lrt_matmul_kernel(xp, mup, sgp, xip, bm=bm, bn=bn, bk=bk,
                          interpret=interp)
    return y[:m, :n]


@functools.partial(jax.jit, static_argnames=("impl", "bb", "dac_bits",
                                             "adc_bits"))
def photonic_conv(x, mu, sigma, eps, impl: Impl = "auto", bb: int = 8,
                  dac_bits: int = 8, adc_bits: int = 8):
    """Machine primitive: (B, T) x 9-channel probabilistic kernel."""
    use_pallas, interp = _resolve(impl)
    if not use_pallas:
        return ref.photonic_conv(x, mu, sigma, eps, dac_bits=dac_bits,
                                 adc_bits=adc_bits)
    b = x.shape[0]
    xp = _pad_to(x, 0, bb)
    epp = _pad_to(eps, 0, bb)
    y = photonic_conv_kernel(xp, mu, sigma, epp, bb=bb, dac_bits=dac_bits,
                             adc_bits=adc_bits, interpret=interp)
    return y[:b]


@functools.partial(jax.jit, static_argnames=("impl", "bm", "bv"))
def uncertainty_head(x, mu, sigma, xi, impl: Impl = "auto",
                     bm: int = 128, bv: int = 512):
    """Fused Bayesian head + (H, SE, MI, pred, p_max) per row."""
    use_pallas, interp = _resolve(impl)
    if not use_pallas:
        return ref.uncertainty_head(x, mu, sigma, xi)
    m = x.shape[0]
    xp = _pad_to(x, 0, bm)
    xip = _pad_to(xi, 1, bm)
    out = uncertainty_head_kernel(xp, mu, sigma, xip, bm=bm, bv=bv,
                                  interpret=interp)
    return {k: v[:m] for k, v in out.items()}


@functools.partial(jax.jit, static_argnames=("impl", "causal", "q_offset",
                                              "bq", "bk"))
def flash_attention(q, k, v, impl: Impl = "auto", causal: bool = True,
                    q_offset: int = 0, bq: int = 128, bk: int = 256):
    """Fused flash attention; q (B,S,H,D), k/v (B,S,Hkv,D) -> (B,S,H,D).

    On TPU this is the production path of the models' attention scope
    ('fused_attention'); elsewhere the jnp online-softmax reference.
    """
    use_pallas, interp = _resolve(impl)
    if not use_pallas:
        from repro.models.layers import flash_attention as ref_attn
        return ref_attn(q, k, v, causal=causal, q_offset=q_offset,
                        q_chunk=bq, kv_chunk=bk)
    out = flash_attention_kernel(
        q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
        v.transpose(0, 2, 1, 3), causal=causal, q_offset=q_offset,
        bq=bq, bk=bk, interpret=interp)
    return out.transpose(0, 2, 1, 3).astype(q.dtype)


@functools.partial(jax.jit, static_argnames=("impl",))
def paged_decode_attention(q, k_pool, v_pool, block_table, cache_len,
                           impl: Impl = "auto"):
    """Block-sparse decode attention over the paged KV pool.

    q (B, 1, H, D); k/v pools (NB, Hkv, BS, D); block_table (B, MB);
    cache_len () or (B,).  Unlike the other wrappers, ``impl='auto'``
    still runs the KERNEL off-TPU (interpret mode — the CI validation
    path): the jnp reference of this op is the gather path
    (``layers.paged_gather`` + ``layers.decode_attention``), and the
    serving engine selects between the two one level up
    (``--decode-attn``), so falling back here would silently benchmark
    the wrong HBM traffic.  ``impl='ref'`` routes to that gather
    composition for tests.
    """
    if impl == "ref":
        from repro.models.layers import (decode_attention, mapped_span,
                                         paged_gather)
        eff = mapped_span(block_table, k_pool.shape[2], cache_len)
        return decode_attention(q, paged_gather(k_pool, block_table),
                                paged_gather(v_pool, block_table), eff)
    return paged_decode_attention_kernel(q, k_pool, v_pool, block_table,
                                         cache_len,
                                         interpret=not _on_tpu())


@functools.partial(jax.jit, static_argnames=("span", "kv_chunk", "impl"))
def paged_prefill_attention(q, k_pool, v_pool, block_row, offset,
                            span: int, kv_chunk: int = 1024,
                            impl: Impl = "auto"):
    """Multi-query block-sparse attention for one slot's prompt chunk.

    q (1, S, H, D) at absolute positions ``offset + [0, S)``; k/v pools
    (NB, Hkv, BS, D); ``block_row`` (1, NBLK) the slot's leading mapped
    table entries covering ``span`` tokens.  Same ``impl`` policy as
    :func:`paged_decode_attention` — 'auto' still runs the kernel
    off-TPU (interpret), 'ref' routes to the gather composition
    (``layers.paged_gather`` + causal ``layers.flash_attention`` with
    ``q_offset``), which the kernel matches bitwise.
    """
    if impl == "ref":
        from repro.models.layers import flash_attention, paged_gather
        ks = paged_gather(k_pool, block_row)[:, :span]
        vs = paged_gather(v_pool, block_row)[:, :span]
        return flash_attention(q, ks, vs, causal=True, kv_chunk=kv_chunk,
                               q_offset=offset)
    return paged_prefill_attention_kernel(q, k_pool, v_pool, block_row,
                                          offset, span=span,
                                          kv_chunk=kv_chunk,
                                          interpret=not _on_tpu())


# ---------------------------------------------------------------------------
# seed-driven fast path: entropy generated in-kernel on TPU
# ---------------------------------------------------------------------------

def entropy_bytes(kind: str, *, num_samples: int, m: int = 0, k: int = 0,
                  n: int = 0, b: int = 0, t_out: int = 0, c: int = 9,
                  in_kernel: bool = False) -> int:
    """Bytes of randomness crossing HBM per prediction.

    kind: 'weight_space' (S*K*N operand), 'lrt' (S*M*N), 'head' (S*M*V ==
    lrt at the vocab), 'conv' (S*B*To*C — one fresh per-symbol draw per
    MC shot).  The in-kernel path is 0 by construction: the variates are
    born and die in registers.
    """
    if in_kernel:
        return 0
    counts = {
        "weight_space": num_samples * k * n,
        "lrt": num_samples * m * n,
        "head": num_samples * m * n,
        "conv": num_samples * b * t_out * c,
    }
    return counts[kind] * 4


@functools.partial(jax.jit, static_argnames=("num_samples", "impl", "bm",
                                             "bn", "bk"))
def bayes_matmul_sampled(x, mu, sigma, seed, num_samples: int = 10,
                         impl: Impl = "auto", bm: int = 128, bn: int = 128,
                         bk: int = 512):
    """S seeded weight-space MC samples of y = x @ (mu + sigma*eps).

    Returns (S, M, N).  On TPU the eps tensor never exists: the kernel
    draws it in-register from (seed, grid coords), and each mu/sigma tile
    is read once for all S samples.
    """
    use_pallas, interp = _resolve(impl)
    if not use_pallas:
        return ref.bayes_matmul_sampled(x, mu, sigma, seed, num_samples)
    m, k = x.shape
    _, n = mu.shape
    xp = _pad_to(_pad_to(x, 0, bm), 1, bk)
    mup = _pad_to(_pad_to(mu, 0, bk), 1, bn)
    sgp = _pad_to(_pad_to(sigma, 0, bk), 1, bn)
    eps = None
    if interp:  # validation path: host-derived operand, same seed
        eps = ref.sampled_normal(seed, (num_samples, *mup.shape))
    y = bayes_matmul_fused_kernel(xp, mup, sgp, seed,
                                  num_samples=num_samples, eps=eps,
                                  bm=bm, bn=bn, bk=bk, interpret=interp)
    return y[:, :m, :n]


@functools.partial(jax.jit, static_argnames=("num_samples", "impl", "bm",
                                             "bn", "bk"))
def lrt_matmul_sampled(x, mu, sigma, seed, num_samples: int = 10,
                       impl: Impl = "auto", bm: int = 128, bn: int = 128,
                       bk: int = 512):
    """S seeded LRT MC samples: (S, M, N), one mean/var GEMM for all S."""
    use_pallas, interp = _resolve(impl)
    if not use_pallas:
        return ref.lrt_matmul_sampled(x, mu, sigma, seed, num_samples)
    m, k = x.shape
    _, n = mu.shape
    xp = _pad_to(_pad_to(x, 0, bm), 1, bk)
    mup = _pad_to(_pad_to(mu, 0, bk), 1, bn)
    sgp = _pad_to(_pad_to(sigma, 0, bk), 1, bn)
    xi = None
    if interp:
        xi = ref.sampled_normal(
            seed, (num_samples, xp.shape[0], mup.shape[1]))
    y = lrt_matmul_fused_kernel(xp, mup, sgp, seed,
                                num_samples=num_samples, xi=xi,
                                bm=bm, bn=bn, bk=bk, interpret=interp)
    return y[:, :m, :n]


@functools.partial(jax.jit, static_argnames=("impl", "bb", "dac_bits",
                                             "adc_bits"))
def photonic_conv_sampled(x, mu, sigma, seed, impl: Impl = "auto",
                          bb: int = 8, dac_bits: int = 8, adc_bits: int = 8):
    """Seeded machine primitive: per-symbol draws born in-kernel on TPU."""
    use_pallas, interp = _resolve(impl)
    if not use_pallas:
        return ref.photonic_conv_sampled(x, mu, sigma, seed,
                                         dac_bits=dac_bits,
                                         adc_bits=adc_bits)
    b, t = x.shape
    c = mu.shape[-1]
    xp = _pad_to(x, 0, bb)
    eps = None
    if interp:
        eps = ref.sampled_normal(seed, (xp.shape[0], t - c + 1, c))
    y = photonic_conv_fused_kernel(xp, mu, sigma, seed, eps=eps, bb=bb,
                                   dac_bits=dac_bits, adc_bits=adc_bits,
                                   interpret=interp)
    return y[:b]


# scoped VMEM the head's double-buffered (K, bv) float32 mu and sigma
# blocks may take: what K=1536 at bv=512 takes (2 x 2 x 1536 x 512 x 4
# bytes), the most that leaves room for the sample tiles in 16 MiB
HEAD_WEIGHT_VMEM = 12 << 20


def head_tiles(m: int, k: int) -> tuple[int, int]:
    """(bm, bv) row and vocab tiles of the fused head for ``m`` rows of
    width ``k``.

    One row tile covers a decode batch of up to 128 rows, rounded up to
    the 8-row sublane: its (3, S, bm) stats block then spans the whole
    padded row axis, which Mosaic's tiling rule admits at any multiple
    of 8.  Larger batches tile rows by 128 (a lane multiple).  The
    vocab tile is the widest of 512, 256, 128 whose double-buffered mu
    and sigma blocks fit ``HEAD_WEIGHT_VMEM``: 512 at K=1536, 256 at
    K=2048, 128 at K=3584.  Above 64 rows it halves (not below 128) to
    stay inside the 16 MiB of scoped VMEM at S=10: the (S, bm, bv) f32
    sample tiles grow with bm (bm=128 at K=1536, bv=512 asks for 22.45
    MiB on v5e).  At K=1536 these are the tiles of every earlier
    release.
    """
    bm = min(-(-m // 8) * 8, 128)
    bv = 512
    while bv > 128 and 2 * 2 * k * bv * 4 > HEAD_WEIGHT_VMEM:
        bv //= 2
    return bm, (bv if bm <= 64 else max(bv // 2, 128))


@functools.partial(jax.jit, static_argnames=("num_samples", "impl", "bm",
                                             "bv"))
def uncertainty_head_sampled(x, mu, sigma, seed, num_samples: int = 10,
                             impl: Impl = "auto", bm: int | None = None,
                             bv: int | None = None):
    """Seeded fused Bayesian head: no xi operand, no logits scratch.

    Pass 2 regenerates the sample logits from the replayed in-kernel
    stream instead of re-reading an (S, M, V) HBM buffer.  ``bm``/``bv``
    default to ``head_tiles`` of the row count and width.
    """
    use_pallas, interp = _resolve(impl)
    if not use_pallas:
        return ref.uncertainty_head_sampled(x, mu, sigma, seed, num_samples)
    m = x.shape[0]
    auto_bm, auto_bv = head_tiles(m, x.shape[1])
    bm, bv = bm or auto_bm, bv or auto_bv
    xp = _pad_to(x, 0, bm)
    xi = None
    if interp:
        xi = ref.sampled_normal(
            seed, (num_samples, xp.shape[0], mu.shape[-1]))
    out = uncertainty_head_fused_kernel(xp, mu, sigma, seed,
                                        num_samples=num_samples, xi=xi,
                                        bm=bm, bv=bv, interpret=interp)
    return {k: v[:m] for k, v in out.items()}


def bayes_conv2d_im2col_sampled(x, mu, sigma, seed, num_samples: int = 10,
                                impl: Impl = "auto"):
    """S seeded MC samples of the 3x3 probabilistic conv (im2col GEMM).

    x: (B, C_in, H, W); mu/sigma: (C_out, C_in, 3, 3)
    -> (S, B, C_out, H, W).  The im2col GEMM routes through the fused
    S-sample kernel: one weight load per prediction.
    """
    b, cin, h, w = x.shape
    cout = mu.shape[0]
    xp = jnp.pad(x, ((0, 0), (0, 0), (1, 1), (1, 1)))
    patches = jax.lax.conv_general_dilated_patches(
        xp, (3, 3), (1, 1), "VALID",
        dimension_numbers=("NCHW", "OIHW", "NHWC"))
    pk = patches.reshape(b * h * w, cin * 9)
    mu2 = mu.reshape(cout, cin * 9).T
    sg2 = sigma.reshape(cout, cin * 9).T
    y = bayes_matmul_sampled(pk, mu2, sg2, seed, num_samples=num_samples,
                             impl=impl)
    return y.reshape(num_samples, b, h, w, cout).transpose(0, 1, 4, 2, 3)


def bayes_conv2d_im2col(x, mu, sigma, eps, impl: Impl = "auto"):
    """3x3 probabilistic conv as a sampled GEMM (im2col).

    The TPU-native form of the machine's convolution: the 9 spectral
    channels become the 9 im2col columns feeding the MXU.
    x: (B, C_in, H, W); mu/sigma/eps: (C_out, C_in, 3, 3) -> (B, C_out, H, W).
    """
    b, cin, h, w = x.shape
    cout = mu.shape[0]
    xp = jnp.pad(x, ((0, 0), (0, 0), (1, 1), (1, 1)))
    # patches: (B, H, W, C_in*9)
    patches = jax.lax.conv_general_dilated_patches(
        xp, (3, 3), (1, 1), "VALID",
        dimension_numbers=("NCHW", "OIHW", "NHWC"))
    pk = patches.reshape(b * h * w, cin * 9)
    mu2 = mu.reshape(cout, cin * 9).T
    sg2 = sigma.reshape(cout, cin * 9).T
    ep2 = eps.reshape(cout, cin * 9).T
    y = bayes_matmul(pk, mu2, sg2, ep2, impl=impl)
    return y.reshape(b, h, w, cout).transpose(0, 3, 1, 2)
