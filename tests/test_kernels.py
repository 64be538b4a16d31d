"""Per-kernel validation: Pallas (interpret mode) vs the pure-jnp oracle.

Shape/dtype sweeps + hypothesis property tests per the deliverables: every
kernel is checked against ref.py over a grid of problem sizes including
non-tile-multiple shapes (the ops.py wrappers pad/strip).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_compat import given, settings, st

from repro.kernels import ops, ref

jax.config.update("jax_enable_x64", False)


def _rand(key, *shape, scale=1.0):
    return scale * jax.random.normal(key, shape, jnp.float32)


# ---------------------------------------------------------------------------
# bayes_matmul
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("m,k,n", [
    (8, 16, 8), (128, 128, 128), (64, 96, 80), (33, 70, 17),
    (256, 512, 128), (1, 9, 7),
])
def test_bayes_matmul_matches_ref(m, k, n):
    ks = jax.random.split(jax.random.key(0), 4)
    x = _rand(ks[0], m, k)
    mu = _rand(ks[1], k, n, scale=0.3)
    sg = jnp.abs(_rand(ks[2], k, n, scale=0.1))
    eps = _rand(ks[3], k, n)
    got = ops.bayes_matmul(x, mu, sg, eps, impl="pallas")
    want = ref.bayes_matmul(x, mu, sg, eps)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-4)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_bayes_matmul_dtypes(dtype):
    ks = jax.random.split(jax.random.key(1), 4)
    x = _rand(ks[0], 32, 64).astype(dtype)
    mu = _rand(ks[1], 64, 48, scale=0.3).astype(dtype)
    sg = jnp.abs(_rand(ks[2], 64, 48, scale=0.1)).astype(dtype)
    eps = _rand(ks[3], 64, 48).astype(dtype)
    got = ops.bayes_matmul(x, mu, sg, eps, impl="pallas")
    want = ref.bayes_matmul(x, mu, sg, eps)
    np.testing.assert_allclose(got, want, rtol=2e-2, atol=2e-2)


def test_bayes_matmul_zero_sigma_is_deterministic():
    """sigma=0 -> exactly the mean GEMM regardless of entropy."""
    ks = jax.random.split(jax.random.key(2), 3)
    x = _rand(ks[0], 16, 32)
    mu = _rand(ks[1], 32, 24)
    z = jnp.zeros((32, 24))
    for eps_scale in (0.0, 1.0, 100.0):
        eps = _rand(ks[2], 32, 24, scale=eps_scale)
        got = ops.bayes_matmul(x, mu, z, eps, impl="pallas")
        np.testing.assert_allclose(got, x @ mu, rtol=1e-5, atol=1e-4)


# ---------------------------------------------------------------------------
# lrt_matmul
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("m,k,n", [
    (8, 16, 8), (128, 256, 128), (40, 50, 60), (1, 128, 11),
])
def test_lrt_matmul_matches_ref(m, k, n):
    ks = jax.random.split(jax.random.key(3), 4)
    x = _rand(ks[0], m, k)
    mu = _rand(ks[1], k, n, scale=0.3)
    sg = jnp.abs(_rand(ks[2], k, n, scale=0.1))
    xi = _rand(ks[3], m, n)
    got = ops.lrt_matmul(x, mu, sg, xi, impl="pallas")
    want = ref.lrt_matmul(x, mu, sg, xi)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-4)


def test_lrt_moments_match_weight_space_sampling():
    """LRT and weight-space sampling share mean and variance (the local
    reparameterization theorem) — the statistical contract that lets the
    LM head replace per-sample weight draws with output-space noise."""
    key = jax.random.key(4)
    ks = jax.random.split(key, 3)
    m, k, n, S = 4, 32, 8, 4000
    x = _rand(ks[0], m, k)
    mu = _rand(ks[1], k, n, scale=0.3)
    sg = jnp.abs(_rand(ks[2], k, n, scale=0.2))

    eps = jax.random.normal(jax.random.key(5), (S, k, n))
    y_ws = jax.vmap(lambda e: ref.bayes_matmul(x, mu, sg, e))(eps)
    xi = jax.random.normal(jax.random.key(6), (S, m, n))
    y_lrt = jax.vmap(lambda z: ref.lrt_matmul(x, mu, sg, z))(xi)

    np.testing.assert_allclose(y_ws.mean(0), y_lrt.mean(0),
                               rtol=0.1, atol=0.15)
    np.testing.assert_allclose(y_ws.std(0), y_lrt.std(0),
                               rtol=0.15, atol=0.05)


# ---------------------------------------------------------------------------
# photonic_conv
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("b,t", [(1, 16), (8, 64), (5, 40), (16, 256)])
def test_photonic_conv_matches_ref(b, t):
    ks = jax.random.split(jax.random.key(7), 3)
    x = jax.random.uniform(ks[0], (b, t), minval=-1, maxval=1)
    mu = jax.random.uniform(ks[1], (9,), minval=-0.8, maxval=0.8)
    sg = jnp.abs(mu) * 0.2
    eps = jax.random.normal(ks[2], (b, t - 8, 9))
    got = ops.photonic_conv(x, mu, sg, eps, impl="pallas")
    want = ref.photonic_conv(x, mu, sg, eps)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


def test_photonic_conv_matches_machine_twin():
    """Kernel == core.photonic.convolve with impairments disabled."""
    from repro.core.photonic import MachineConfig, convolve, ChannelProgram
    from repro.core import entropy as E
    cfg = MachineConfig(detector_noise=0.0, crosstalk=0.0, drift_std=0.0,
                        eom_mod_depth=0.0, gaussian_surrogate=True)
    key = jax.random.key(8)
    x = jax.random.uniform(key, (24,), minval=-1, maxval=1)
    mu = jnp.linspace(-0.5, 0.5, 9)
    bw = jnp.full((9,), 100.0)
    prog = ChannelProgram(power=mu, bandwidth=bw)
    y_machine = convolve(key, x, prog, cfg)
    # reproduce the machine's eps draw through the kernel interface
    m = E.modes_from_bandwidth(bw)
    sigma = jnp.abs(mu) / jnp.sqrt(m)
    eps = jax.random.normal(key, (1, 16, 9))
    y_kernel = ops.photonic_conv(x[None], mu, sigma,
                                 eps, impl="ref")
    assert y_machine.shape == (16,)
    assert y_kernel.shape == (1, 16)


# ---------------------------------------------------------------------------
# uncertainty_head
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("m,k,v,s", [
    (8, 16, 12, 4), (32, 64, 48, 10), (7, 33, 21, 3), (128, 128, 256, 10),
])
def test_uncertainty_head_matches_ref(m, k, v, s):
    ks = jax.random.split(jax.random.key(9), 4)
    x = _rand(ks[0], m, k)
    mu = _rand(ks[1], k, v, scale=0.2)
    sg = jnp.abs(_rand(ks[2], k, v, scale=0.05))
    xi = _rand(ks[3], s, m, v)
    got = ops.uncertainty_head(x, mu, sg, xi, impl="pallas")
    want = ref.uncertainty_head(x, mu, sg, xi)
    for name in ("H", "SE", "MI", "p_max"):
        np.testing.assert_allclose(got[name], want[name], rtol=1e-4,
                                   atol=1e-5, err_msg=name)
    np.testing.assert_array_equal(got["pred"], want["pred"])


def test_uncertainty_head_identities():
    """0 <= MI <= H <= log(V); SE = H - MI."""
    ks = jax.random.split(jax.random.key(10), 4)
    m, k, v, s = 64, 32, 10, 10
    out = ref.uncertainty_head(
        _rand(ks[0], m, k), _rand(ks[1], k, v, scale=0.5),
        jnp.abs(_rand(ks[2], k, v, scale=0.3)), _rand(ks[3], s, m, v))
    h, se, mi = out["H"], out["SE"], out["MI"]
    assert (mi >= -1e-6).all()
    assert (h <= np.log(v) + 1e-5).all()
    assert (mi <= h + 1e-6).all()
    np.testing.assert_allclose(se, h - mi, atol=1e-5)


# ---------------------------------------------------------------------------
# in-kernel entropy path: seeded parity (moments) + determinism
# ---------------------------------------------------------------------------
# The in-kernel PRNG only lowers on real TPUs; in interpret mode the
# *_sampled wrappers run the same fused kernels with an explicit operand
# derived host-side from the same seed (the validation path).  The oracle
# and the kernel draw different bit streams, so parity is statistical —
# mean/std over S samples — exactly the contract the TPU path satisfies.

def _sampled_setup(key, m, k, n):
    ks = jax.random.split(key, 3)
    x = _rand(ks[0], m, k)
    mu = _rand(ks[1], k, n, scale=0.3)
    sg = jnp.abs(_rand(ks[2], k, n, scale=0.1))
    return x, mu, sg


def _assert_sample_moments(got, x, mu, sg, lrt=False):
    """Moments of S MC samples vs the analytic LRT mean/std."""
    x32 = x.astype(jnp.float32)
    mean = x32 @ mu
    std = jnp.sqrt(jnp.maximum((x32 * x32) @ (sg ** 2), 0.0))
    s = got.shape[0]
    # standardized residual of the sample mean is ~N(0,1) per element:
    # its mean |.| is ~0.8 for an unbiased stream; a mean/std bug in the
    # generated variates shifts it by O(sqrt(S)).
    resid = (np.asarray(got.mean(0)) - np.asarray(mean)) \
        / np.maximum(np.asarray(std) / np.sqrt(s), 1e-6)
    assert np.abs(resid).mean() < 1.5, np.abs(resid).mean()
    ratio = np.asarray(got.std(0)) / np.maximum(np.asarray(std), 1e-6)
    assert abs(ratio.mean() - 1.0) < 0.2, ratio.mean()


@pytest.mark.parametrize("fn,oracle", [
    (ops.bayes_matmul_sampled, ref.bayes_matmul_sampled),
    (ops.lrt_matmul_sampled, ref.lrt_matmul_sampled),
])
def test_sampled_matmul_moments_match_oracle(fn, oracle):
    m, k, n, s = 16, 64, 24, 64
    x, mu, sg = _sampled_setup(jax.random.key(30), m, k, n)
    got = fn(x, mu, sg, 123, num_samples=s, impl="pallas")
    want = oracle(x, mu, sg, 123, s)
    assert got.shape == want.shape == (s, m, n)
    _assert_sample_moments(got, x, mu, sg)
    _assert_sample_moments(want, x, mu, sg)
    # the two paths agree on the analytic mean within MC error of each
    np.testing.assert_allclose(got.mean(0), want.mean(0), atol=0.5)


@pytest.mark.parametrize("fn", [ops.bayes_matmul_sampled,
                                ops.lrt_matmul_sampled])
@pytest.mark.parametrize("impl", ["pallas", "ref"])
def test_sampled_matmul_determinism(fn, impl):
    x, mu, sg = _sampled_setup(jax.random.key(31), 8, 32, 16)
    a = fn(x, mu, sg, 7, num_samples=4, impl=impl)
    b = fn(x, mu, sg, 7, num_samples=4, impl=impl)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    c = fn(x, mu, sg, 8, num_samples=4, impl=impl)
    assert not np.allclose(a, c)


def test_fused_kernels_match_ref_with_explicit_entropy():
    """Bit-exact parity of the fused S-sample kernel *structure* against
    the oracle when both consume the same explicit variates (the
    validation path — isolates the fusion from the RNG)."""
    from repro.kernels.bayes_matmul import (bayes_matmul_fused_kernel,
                                            lrt_matmul_fused_kernel)
    m, k, n, s = 16, 32, 24, 5
    x, mu, sg = _sampled_setup(jax.random.key(32), m, k, n)
    eps = jax.random.normal(jax.random.key(33), (s, k, n))
    got = bayes_matmul_fused_kernel(x, mu, sg, 0, num_samples=s, eps=eps,
                                    interpret=True)
    want = jax.vmap(lambda e: ref.bayes_matmul(x, mu, sg, e))(eps)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-4)

    xi = jax.random.normal(jax.random.key(34), (s, m, n))
    got = lrt_matmul_fused_kernel(x, mu, sg, 0, num_samples=s, xi=xi,
                                  interpret=True)
    want = jax.vmap(lambda z: ref.lrt_matmul(x, mu, sg, z))(xi)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-4)


def test_fused_head_matches_ref_with_explicit_entropy():
    """The scratch-free two-pass head (pass 2 regenerates logits instead
    of re-reading the (S, M, V) buffer) is exact vs the oracle when both
    consume the same xi."""
    from repro.kernels.uncertainty_head import uncertainty_head_fused_kernel
    m, k, v, s = 8, 16, 21, 6
    ks = jax.random.split(jax.random.key(35), 4)
    x = _rand(ks[0], m, k)
    mu = _rand(ks[1], k, v, scale=0.2)
    sg = jnp.abs(_rand(ks[2], k, v, scale=0.05))
    xi = _rand(ks[3], s, m, v)
    got = uncertainty_head_fused_kernel(x, mu, sg, 0, num_samples=s, xi=xi,
                                        bm=8, bv=16, interpret=True)
    want = ref.uncertainty_head(x, mu, sg, xi)
    for name in ("H", "SE", "MI", "p_max"):
        np.testing.assert_allclose(got[name], want[name], rtol=1e-4,
                                   atol=1e-5, err_msg=name)
    np.testing.assert_array_equal(got["pred"], want["pred"])


def test_sampled_head_ragged_vocab_tile_matches_ref():
    """V=1000 over 512-wide vocab tiles: the kernel's last tile is
    ragged (nothing padded) and its columns past V are masked, so the
    seeded head matches the seeded oracle on the same variates."""
    m, k, v, s = 8, 64, 1000, 10
    ks = jax.random.split(jax.random.key(38), 3)
    x = _rand(ks[0], m, k)
    mu = _rand(ks[1], k, v, scale=0.2)
    sg = jnp.abs(_rand(ks[2], k, v, scale=0.05))
    got = ops.uncertainty_head_sampled(x, mu, sg, 7, num_samples=s,
                                       impl="pallas", bm=8, bv=512)
    want = ref.uncertainty_head_sampled(x, mu, sg, 7, s)
    for name in ("H", "SE", "MI", "p_max"):
        np.testing.assert_allclose(got[name], want[name], rtol=1e-4,
                                   atol=1e-5, err_msg=name)
    np.testing.assert_array_equal(got["pred"], want["pred"])


def test_sampled_head_moments_and_determinism():
    m, k, v, s = 8, 16, 12, 10
    ks = jax.random.split(jax.random.key(36), 3)
    x = _rand(ks[0], m, k)
    mu = _rand(ks[1], k, v, scale=0.2)
    sg = jnp.abs(_rand(ks[2], k, v, scale=0.05))
    a = ops.uncertainty_head_sampled(x, mu, sg, 5, num_samples=s,
                                     impl="pallas")
    b = ops.uncertainty_head_sampled(x, mu, sg, 5, num_samples=s,
                                     impl="pallas")
    for name in a:
        np.testing.assert_array_equal(np.asarray(a[name]),
                                      np.asarray(b[name]))
    want = ref.uncertainty_head_sampled(x, mu, sg, 5, s)
    # H of the mean predictive is dominated by the mean logits -> the two
    # seed streams must land in the same entropy regime
    np.testing.assert_allclose(a["H"], want["H"], atol=0.35)
    assert (np.asarray(a["MI"]) >= -1e-6).all()
    np.testing.assert_allclose(np.asarray(a["SE"]),
                               np.asarray(a["H"]) - np.asarray(a["MI"]),
                               atol=1e-5)


def test_sampled_conv_moments_and_determinism():
    b, t, c = 8, 64, 9
    ks = jax.random.split(jax.random.key(37), 2)
    x = jax.random.uniform(ks[0], (b, t), minval=-1, maxval=1)
    mu = jax.random.uniform(ks[1], (c,), minval=-0.6, maxval=0.6)
    sg = jnp.abs(mu) * 0.2
    a = ops.photonic_conv_sampled(x, mu, sg, 9, impl="pallas")
    a2 = ops.photonic_conv_sampled(x, mu, sg, 9, impl="pallas")
    np.testing.assert_array_equal(np.asarray(a), np.asarray(a2))
    # different seeds -> different shot noise, same mean conv
    ys = np.stack([np.asarray(
        ops.photonic_conv_sampled(x, mu, sg, s, impl="ref"))
        for s in range(40)])
    want = ref.photonic_conv(x, mu, sg, jnp.zeros((b, t - c + 1, c)))
    np.testing.assert_allclose(ys.mean(0), np.asarray(want), atol=0.15)


def test_im2col_sampled_shape_determinism_and_mean():
    """The seeded 3x3-conv GEMM: (S, B, C_out, H, W) layout is right
    (sample mean converges to the mean-weight conv), and the stream is a
    pure function of the seed."""
    ks = jax.random.split(jax.random.key(40), 3)
    b, cin, cout, h, w, s = 2, 3, 4, 6, 6, 64
    x = _rand(ks[0], b, cin, h, w)
    mu = _rand(ks[1], cout, cin, 3, 3, scale=0.2)
    sg = jnp.abs(_rand(ks[2], cout, cin, 3, 3, scale=0.05))
    y = ops.bayes_conv2d_im2col_sampled(x, mu, sg, 3, num_samples=s,
                                        impl="ref")
    assert y.shape == (s, b, cout, h, w)
    y2 = ops.bayes_conv2d_im2col_sampled(x, mu, sg, 3, num_samples=s,
                                         impl="ref")
    np.testing.assert_array_equal(np.asarray(y), np.asarray(y2))
    mean_conv = ops.bayes_conv2d_im2col(x, mu, sg, jnp.zeros_like(mu),
                                        impl="ref")
    np.testing.assert_allclose(y.mean(0), mean_conv, atol=0.35)


def test_entropy_bytes_accounting():
    """The benchmark's traffic columns: operand path counts the exact
    operand bytes, in-kernel path is 0 by construction."""
    s, m, k, v = 10, 128, 1024, 4096
    assert ops.entropy_bytes("weight_space", num_samples=s, k=k, n=v) \
        == s * k * v * 4
    assert ops.entropy_bytes("head", num_samples=s, m=m, n=v) \
        == s * m * v * 4
    assert ops.entropy_bytes("conv", num_samples=1, b=8, t_out=248) \
        == 8 * 248 * 9 * 4
    for kind in ("weight_space", "lrt", "head", "conv"):
        assert ops.entropy_bytes(kind, num_samples=s, m=m, k=k, n=v, b=8,
                                 t_out=248, in_kernel=True) == 0


# ---------------------------------------------------------------------------
# hypothesis property sweeps
# ---------------------------------------------------------------------------

@settings(max_examples=20, deadline=None)
@given(m=st.integers(1, 40), k=st.integers(1, 64), n=st.integers(1, 40),
       seed=st.integers(0, 2**31 - 1))
def test_prop_bayes_matmul_any_shape(m, k, n, seed):
    ks = jax.random.split(jax.random.key(seed), 4)
    x = _rand(ks[0], m, k)
    mu = _rand(ks[1], k, n, scale=0.3)
    sg = jnp.abs(_rand(ks[2], k, n, scale=0.1))
    eps = _rand(ks[3], k, n)
    got = ops.bayes_matmul(x, mu, sg, eps, impl="pallas")
    want = ref.bayes_matmul(x, mu, sg, eps)
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-3)


@settings(max_examples=20, deadline=None)
@given(m=st.integers(1, 24), v=st.integers(2, 24), s=st.integers(1, 12),
       seed=st.integers(0, 2**31 - 1))
def test_prop_uncertainty_head_invariants(m, v, s, seed):
    ks = jax.random.split(jax.random.key(seed), 4)
    k = 16
    out = ops.uncertainty_head(
        _rand(ks[0], m, k), _rand(ks[1], k, v, scale=0.4),
        jnp.abs(_rand(ks[2], k, v, scale=0.2)), _rand(ks[3], s, m, v),
        impl="pallas")
    assert (out["MI"] >= -1e-6).all()
    assert (out["H"] >= out["MI"] - 1e-5).all()
    assert (out["H"] <= np.log(v) + 1e-4).all()
    assert ((out["pred"] >= 0) & (out["pred"] < v)).all()
    assert (out["p_max"] >= 1.0 / v - 1e-6).all()


# ---------------------------------------------------------------------------
# flash_attention
# ---------------------------------------------------------------------------

def _naive_attention(q, k, v, causal=True):
    B, S, H, D = q.shape
    Hkv = k.shape[2]
    kk = jnp.repeat(k, H // Hkv, axis=2)
    vv = jnp.repeat(v, H // Hkv, axis=2)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, kk) / jnp.sqrt(jnp.float32(D))
    if causal:
        m = jnp.tril(jnp.ones((S, S), bool))
        s = jnp.where(m[None, None], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p, vv)


@pytest.mark.parametrize("b,s,h,hkv,d,causal", [
    (1, 32, 4, 4, 16, True),
    (2, 70, 6, 2, 16, True),      # GQA, non-multiple seq
    (2, 64, 8, 1, 32, False),     # MQA, non-causal
    (1, 128, 2, 2, 64, True),
])
def test_flash_attention_kernel_matches_naive(b, s, h, hkv, d, causal):
    ks = jax.random.split(jax.random.key(20), 3)
    q = _rand(ks[0], b, s, h, d)
    k = _rand(ks[1], b, s, hkv, d)
    v = _rand(ks[2], b, s, hkv, d)
    got = ops.flash_attention(q, k, v, impl="pallas", causal=causal,
                              bq=16, bk=32)
    want = _naive_attention(q, k, v, causal)
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-5)


def test_flash_attention_jnp_scope_matches_kernel():
    """The models' jnp flash path (named_scope 'fused_attention') and the
    Pallas kernel agree — the roofline's scope-skip accounting is backed
    by a real kernel with identical semantics."""
    from repro.models.layers import flash_attention as jnp_flash
    ks = jax.random.split(jax.random.key(21), 3)
    b, s, h, hkv, d = 2, 48, 4, 2, 16
    q = _rand(ks[0], b, s, h, d)
    k = _rand(ks[1], b, s, hkv, d)
    v = _rand(ks[2], b, s, hkv, d)
    a = jnp_flash(q, k, v, causal=True, q_chunk=16, kv_chunk=16)
    bb = ops.flash_attention(q, k, v, impl="pallas", causal=True,
                             bq=16, bk=16)
    np.testing.assert_allclose(a, bb, rtol=1e-4, atol=1e-5)


def test_flash_attention_q_offset_decode_window():
    """Continuation: last token of a prefix equals full-seq attention."""
    ks = jax.random.split(jax.random.key(22), 3)
    b, s, h, d = 1, 40, 2, 16
    q = _rand(ks[0], b, s, h, d)
    k = _rand(ks[1], b, s, h, d)
    v = _rand(ks[2], b, s, h, d)
    full = _naive_attention(q, k, v, causal=True)
    last = ops.flash_attention(q[:, -1:], k, v, impl="pallas",
                               causal=True, q_offset=s - 1, bq=8, bk=16)
    np.testing.assert_allclose(last[:, 0], full[:, -1], rtol=1e-4,
                               atol=1e-5)
