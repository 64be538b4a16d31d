"""The serving kernels compile for a TPU v5e at the served widths.

qwen2-1.5B's throughout; the head at K=2048 (deepseek-moe-16b) and
K=3584 (qwen2-7B) too, and deepseek-moe-16b's grouped expert matmuls
and MHA paged decode.

The TPU compiler ships with jaxlib and compiles for a chip that is
described, not attached (``jax.experimental.topologies``): Mosaic's
tiling rule, its cast and PRNG-seed support, and the 16 MiB scoped-VMEM
limit all bite here, where interpret mode accepts anything.  Each test
lowers one kernel on one chip of a described ``v5e:2x2`` host and
asserts that the compiled module holds the kernel (``tpu_custom_call``).

Nothing runs: a compile that passes is not a chip run.  The topology is
described inside a module fixture (never at import), so only the xdist
worker given this file loads the TPU library.
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs.registry import get_config
from repro.kernels.bayes_matmul import (bayes_matmul_fused_kernel,
                                        lrt_matmul_fused_kernel)
from repro.kernels.moe_gmm import moe_gmm
from repro.kernels.ops import head_tiles
from repro.kernels.paged_attention import (paged_decode_attention_kernel,
                                           paged_prefill_attention_kernel)
from repro.kernels.uncertainty_head import uncertainty_head_fused_kernel

CFG = get_config("qwen2_1_5b")
MOE = get_config("deepseek_moe_16b")
KV_BLOCK = 16          # the serving CLI's --kv-block default
SLOTS = 24             # the benchmark's decode batch


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means no TPU lib
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a described chip's executables cannot be read back from the
    # persistent cache: keep these compiles out of it
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)


def _compiled_text(one_chip, fn, *shapes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip)
            for s, d in shapes]
    return jax.jit(fn).lower(*args).compile().as_text()


@pytest.mark.parametrize("head_dtype", [jnp.bfloat16, jnp.float32])
def test_uncertainty_head_in_kernel_rng(one_chip, head_dtype):
    """The served decode head: M=8 rows (a 4-slot batch padded to the
    sublane), the full vocab, S=10 draws generated in-kernel.  V=151,936
    is not a multiple of the 512-wide vocab tile: Mosaic takes the
    ragged last tile, and mu and sigma (float32 as served) are read in
    place, with no pad in the program."""
    m, k, v = 8, CFG.d_model, CFG.vocab_size
    bm, bv = head_tiles(m, k)
    assert bm == 8 and v == 151_936 and v % bv
    hlo = _compiled_text(
        one_chip,
        lambda x, mu, sg, seed: uncertainty_head_fused_kernel(
            x, mu, sg, seed, num_samples=CFG.mc_samples, bm=bm, bv=bv),
        ((m, k), jnp.bfloat16), ((k, v), head_dtype),
        ((k, v), head_dtype), ((), jnp.int32))
    assert "tpu_custom_call" in hlo
    assert " pad(" not in hlo


def test_paged_decode_attention(one_chip):
    b, hkv, d, h = 4, CFG.num_kv_heads, CFG.head_dim, CFG.num_heads
    mb = 11                                 # 168-token table span
    nb = b * mb
    hlo = _compiled_text(
        one_chip, paged_decode_attention_kernel,
        ((b, 1, h, d), jnp.bfloat16), ((nb, hkv, KV_BLOCK, d), jnp.bfloat16),
        ((nb, hkv, KV_BLOCK, d), jnp.bfloat16), ((b, mb), jnp.int32),
        ((b,), jnp.int32))
    assert "tpu_custom_call" in hlo


def test_paged_prefill_attention(one_chip):
    s, span, hkv, d, h = 32, 128, CFG.num_kv_heads, CFG.head_dim, \
        CFG.num_heads
    nb = 44
    hlo = _compiled_text(
        one_chip,
        lambda q, kp, vp, row, off: paged_prefill_attention_kernel(
            q, kp, vp, row, off, span=span, kv_chunk=CFG.attn_kv_chunk),
        ((1, s, h, d), jnp.bfloat16), ((nb, hkv, KV_BLOCK, d), jnp.bfloat16),
        ((nb, hkv, KV_BLOCK, d), jnp.bfloat16),
        ((1, span // KV_BLOCK), jnp.int32), ((), jnp.int32))
    assert "tpu_custom_call" in hlo


@pytest.mark.parametrize("kernel", [lrt_matmul_fused_kernel,
                                    bayes_matmul_fused_kernel])
def test_fused_sampling_gemms_in_kernel_rng(one_chip, kernel):
    """S=10 sampled GEMMs over a d_model x d_ff weight, variates drawn
    in-kernel (no eps/xi operand)."""
    m, k, n = 128, CFG.d_model, CFG.d_ff
    hlo = _compiled_text(
        one_chip,
        lambda x, mu, sg, seed: kernel(x, mu, sg, seed,
                                       num_samples=CFG.mc_samples),
        ((m, k), jnp.bfloat16), ((k, n), jnp.bfloat16),
        ((k, n), jnp.bfloat16), ((), jnp.int32))
    assert "tpu_custom_call" in hlo


def test_head_tiles_at_1536_are_the_earlier_tiles():
    """K=1536 (qwen2-1.5B) keeps the tiles every earlier release used,
    at every row count a decode batch can have: its programs do not
    change."""
    for m in range(1, 129):
        bm = min(-(-m // 8) * 8, 128)
        assert head_tiles(m, 1536) == (bm, 512 if bm <= 64 else 256), m


@pytest.mark.parametrize("k", [2048, 3584], ids=["K2048", "K3584"])
def test_uncertainty_head_wide(one_chip, k):
    """The head at deepseek-moe-16b's and qwen2-7B's widths over a
    24-slot decode batch: the vocab tile shrinks with K so that the
    double-buffered float32 mu and sigma blocks fit scoped VMEM (at
    bv=512, K=2048 alone asks for all 16 MiB)."""
    m, v = SLOTS, 12_800
    bm, bv = head_tiles(m, k)
    assert bv < 512
    hlo = _compiled_text(
        one_chip,
        lambda x, mu, sg, seed: uncertainty_head_fused_kernel(
            x, mu, sg, seed, num_samples=CFG.mc_samples, bm=bm, bv=bv),
        ((bm, k), jnp.bfloat16), ((k, v), jnp.float32),
        ((k, v), jnp.float32), ((), jnp.int32))
    assert "tpu_custom_call" in hlo


@pytest.mark.parametrize("tokens", [SLOTS, 128], ids=["decode", "chunk"])
def test_moe_grouped_matmul(one_chip, tokens):
    """deepseek-moe-16b's routed experts on one chip's share (8 held of
    64, top-6) over a decode batch and a 128-token prompt chunk: both
    grouped matmuls read the layer's experts out of a 13-layer stack."""
    d, f, held, k = MOE.d_model, MOE.moe_d_ff, 8, MOE.top_k
    hlo = _compiled_text(
        one_chip,
        lambda x, e, g, w13, w2, j: moe_gmm(x, e, g, w13, w2, j),
        ((tokens, d), jnp.bfloat16), ((tokens, k), jnp.int32),
        ((tokens, k), jnp.float32), ((13, held, d, 2 * f), jnp.bfloat16),
        ((13, held, f, d), jnp.bfloat16), ((), jnp.int32))
    assert hlo.count("tpu_custom_call") >= 2


def test_paged_decode_attention_mha(one_chip):
    """One query head per KV head over 16 KV heads (deepseek-moe-16b)."""
    b, hkv, d, h = SLOTS, MOE.num_kv_heads, MOE.head_dim, MOE.num_heads
    mb = 104                                # 1,664-token table span
    nb = b * mb
    hlo = _compiled_text(
        one_chip, paged_decode_attention_kernel,
        ((b, 1, h, d), jnp.bfloat16), ((nb, hkv, KV_BLOCK, d), jnp.bfloat16),
        ((nb, hkv, KV_BLOCK, d), jnp.bfloat16), ((b, mb), jnp.int32),
        ((b,), jnp.int32))
    assert "tpu_custom_call" in hlo
