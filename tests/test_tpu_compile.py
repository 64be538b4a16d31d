"""The serving kernels compile for a TPU v5e at qwen2-1.5B widths.

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
from repro.kernels.ops import head_tiles
from repro.kernels.paged_attention import (paged_decode_attention_kernel,
                                           paged_prefill_attention_kernel)
from repro.kernels.uncertainty_head import uncertainty_head_fused_kernel

CFG = get_config("qwen2_1_5b")
KV_BLOCK = 16          # the serving CLI's --kv-block default


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
    bm, bv = head_tiles(m)
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
