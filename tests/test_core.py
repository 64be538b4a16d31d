"""Core paper machinery: entropy sources, photonic twin, SVI, uncertainty."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_compat import given, settings, st

from repro.core import entropy as E
from repro.core import photonic as PH
from repro.core import svi
from repro.core import uncertainty as U
from repro.core.bayesian import GaussianVariational, mc_forward
from repro.core.surrogate import SurrogateSpec


# ---------------------------------------------------------------------------
# entropy sources
# ---------------------------------------------------------------------------

class TestEntropy:
    def test_prng_standard_moments(self):
        eps = E.PRNGEntropy().sample(jax.random.key(0), (200_000,))
        assert abs(float(eps.mean())) < 0.01
        assert abs(float(eps.std()) - 1.0) < 0.01

    def test_ase_standard_moments_and_skew(self):
        """Gamma(M) standardization keeps mean 0 / std 1 but the chaotic
        light's positive skew 2/sqrt(M) — the physics the Gaussian
        surrogate approximates away."""
        modes = 30.0
        src = E.ASEEntropy(modes=modes)
        eps = np.asarray(src.sample(jax.random.key(1), (400_000,)))
        assert abs(eps.mean()) < 0.01
        assert abs(eps.std() - 1.0) < 0.01
        skew = ((eps - eps.mean()) ** 3).mean() / eps.std() ** 3
        np.testing.assert_allclose(skew, 2 / np.sqrt(modes), rtol=0.15)

    def test_bandwidth_maps_are_inverse(self):
        bw = jnp.linspace(E.BW_MIN_GHZ, E.BW_MAX_GHZ, 7)
        m = E.modes_from_bandwidth(bw)
        rel = 1.0 / jnp.sqrt(m)
        np.testing.assert_allclose(E.bandwidth_for_relstd(rel), bw,
                                   rtol=1e-5)

    def test_relstd_range_matches_paper_68pct(self):
        """25-150 GHz must span a ~sqrt(6)x (≈68% around center) sigma
        tuning range (paper §System Architecture)."""
        lo, hi = E.relstd_range()
        np.testing.assert_allclose(hi / lo, np.sqrt(6.0), rtol=1e-6)

    def test_entropy_stream_draw_and_wraparound(self):
        s = E.EntropyStream.create(jax.random.key(2), 100)
        a, s2 = s.draw((30,))
        b, s3 = s2.draw((30,))
        assert not np.allclose(a, b)
        assert int(s3.cursor) == 60
        c, s4 = s3.draw((60,))      # wraps
        assert int(s4.cursor) == 20
        np.testing.assert_allclose(c[40:], np.asarray(s.buffer[:20]))

    def test_kernel_entropy_moments_skew_and_determinism(self):
        """Contract of the in-kernel TPU PRNG source: standard normal —
        mean 0, std 1, skew 0 (vs ASE's 2/sqrt(M)) — and the stream is a
        pure function of the base seed (same seed -> same bits; the
        property that lets the uncertainty head regenerate its sample
        logits instead of re-reading them from HBM)."""
        src = E.KernelEntropy(seed=42)
        eps = np.asarray(src.sample(None, (400_000,)))
        assert abs(eps.mean()) < 0.01
        assert abs(eps.std() - 1.0) < 0.01
        skew = ((eps - eps.mean()) ** 3).mean() / eps.std() ** 3
        assert abs(skew) < 0.02              # Gaussian: no residual skew
        eps2 = np.asarray(E.KernelEntropy(seed=42).sample(None, (400_000,)))
        np.testing.assert_array_equal(eps, eps2)
        eps3 = np.asarray(E.KernelEntropy(seed=43).sample(None, (1000,)))
        assert not np.allclose(eps[:1000], eps3)

    def test_kernel_entropy_fold_is_stable_and_distinct(self):
        src = E.KernelEntropy(seed=5)
        assert int(src.fold(1, 2)) == int(E.KernelEntropy(seed=5).fold(1, 2))
        assert int(src.fold(1, 2)) != int(src.fold(2, 1))
        assert int(src.fold()) != int(E.KernelEntropy(seed=6).fold())

    def test_entropy_health_flags_dead_source(self):
        rng = np.random.default_rng(0)
        good = E.entropy_health((rng.random(20_000) > 0.5).astype(np.uint8))
        dead = E.entropy_health(np.ones(20_000, np.uint8))
        assert good["monobit_z"] < 4.0
        assert dead["monobit_z"] > 50.0

    def test_gaussian_bits_pass_health(self):
        eps = np.asarray(E.PRNGEntropy().sample(jax.random.key(3), (40_000,)))
        h = E.entropy_health(E.gaussian_to_bits(eps))
        assert h["monobit_z"] < 4.0 and h["runs_z"] < 4.0
        assert h["byte_chi2"] < 400.0     # 255 dof


# ---------------------------------------------------------------------------
# photonic digital twin
# ---------------------------------------------------------------------------

class TestPhotonicMachine:
    def test_quantize_ste_grid_and_gradient(self):
        x = jnp.linspace(-1, 1, 11)
        q = PH.quantize_ste(x, 8, 1.0)
        assert float(jnp.abs(q - x).max()) <= 1.0 / 127 + 1e-6
        g = jax.grad(lambda v: PH.quantize_ste(v, 8, 1.0).sum())(x)
        np.testing.assert_allclose(g, 1.0)   # straight-through

    def test_convolve_mean_tracks_target(self):
        cfg = PH.MachineConfig(detector_noise=0.0, crosstalk=0.0,
                               drift_std=0.0, eom_mod_depth=0.0)
        mu = jnp.linspace(-0.6, 0.6, 9)
        prog = PH.ChannelProgram(power=mu, bandwidth=jnp.full((9,), 150.0))
        x = jax.random.uniform(jax.random.key(0), (64,), minval=-1, maxval=1)
        keys = jax.random.split(jax.random.key(1), 2000)
        ys = jax.vmap(lambda k: PH.convolve(k, x, prog, cfg))(keys)
        C = 9
        idx = jnp.arange(x.shape[-1] - C + 1)[:, None] + jnp.arange(C)
        target = x[idx] @ mu[::-1]
        np.testing.assert_allclose(ys.mean(0), target, atol=0.05)

    def test_calibration_reduces_error(self):
        key = jax.random.key(4)
        mu_t = jnp.array([0.5, -0.3, 0.7, -0.6, 0.2, 0.4, -0.5, 0.3, -0.2])
        sg_t = jnp.abs(mu_t) * 0.15
        _, hist = PH.calibrate(key, mu_t, sg_t, iters=8, n_shots=256)
        assert hist["mu_err"][-1] < hist["mu_err"][0]
        assert hist["mu_err"][-1] < 0.05

    def test_computation_error_in_paper_band(self):
        """Fig. 2c/d: mean err ~0.158, std err ~0.266.  The twin must land
        in the same regime (we assert generous bands, not exact figures).
        The ordering comes from the bandwidth axis being the machine's
        less accurate one: the balanced receiver's mode count puts the
        realizable sigma floor (1/sqrt(M_max)) inside the target range,
        and waveshaper quantization/jitter sit on top of it -- none of
        which the power (mean) axis sees."""
        r = PH.computation_error(jax.random.key(5), n_kernels=6,
                                 n_shots=256, seq_len=48)
        assert r["mean_error"] < 0.35
        assert r["std_error"] < 0.6
        assert r["mean_error"] < r["std_error"]  # paper's ordering

    def test_effective_bandwidth_quantizes_and_jitters(self):
        cfg = PH.MachineConfig(bw_quant_ghz=12.5, bw_jitter_std=0.0)
        bw = jnp.array([26.0, 99.0, 150.0])
        eff = PH.effective_bandwidth(jax.random.key(0), bw, cfg)
        np.testing.assert_allclose(eff, [25.0, 100.0, 150.0])
        cfg = PH.MachineConfig(bw_quant_ghz=0.0, bw_jitter_std=0.1)
        effs = jax.vmap(lambda k: PH.effective_bandwidth(
            k, jnp.full((64,), 100.0), cfg))(
                jax.random.split(jax.random.key(1), 256))
        rel = np.asarray(effs) / 100.0 - 1.0
        assert abs(rel.std() - 0.1) < 0.02      # per-shot filter jitter
        assert (np.asarray(effs) >= E.BW_MIN_GHZ).all()
        assert (np.asarray(effs) <= E.BW_MAX_GHZ).all()

    def test_throughput_constants(self):
        t = PH.conv_throughput_estimate()
        np.testing.assert_allclose(t["conv_per_s"], 26.7e9, rtol=0.01)
        np.testing.assert_allclose(t["interface_tbit_s"], 1.28, rtol=0.01)
        assert t["latency_ps"] == 37.5


# ---------------------------------------------------------------------------
# variational layers + SVI
# ---------------------------------------------------------------------------

class TestSVI:
    def test_kl_closed_form_vs_monte_carlo(self):
        q = GaussianVariational(mu=jnp.array([0.5, -1.0]),
                                rho=jnp.array([0.0, 0.5]))
        kl = float(q.kl_to_prior(1.0))
        # MC estimate of E_q[log q - log p]
        key = jax.random.key(0)
        w = q.sample(key, num=200_000)
        s = q.sigma
        logq = (-0.5 * ((w - q.mu) / s) ** 2 - jnp.log(s)
                - 0.5 * jnp.log(2 * jnp.pi)).sum(-1)
        logp = (-0.5 * w ** 2 - 0.5 * jnp.log(2 * jnp.pi)).sum(-1)
        np.testing.assert_allclose(kl, float((logq - logp).mean()),
                                   rtol=0.02)

    def test_kl_zero_at_prior(self):
        from repro.core.bayesian import inv_softplus
        q = GaussianVariational(mu=jnp.zeros(5),
                                rho=jnp.full((5,), inv_softplus(1.0)))
        assert abs(float(q.kl_to_prior(1.0))) < 1e-5

    def test_reparam_gradients_flow_to_both_moments(self):
        def loss(q):
            w = q.sample_with_eps(jnp.array([0.7]))
            return (w - 2.0).squeeze() ** 2

        q = GaussianVariational(mu=jnp.array([0.0]), rho=jnp.array([0.0]))
        g = jax.grad(loss)(q)
        assert abs(float(g.mu[0])) > 0 and abs(float(g.rho[0])) > 0

    def test_kl_beta_warmup(self):
        cfg = svi.SVIConfig(kl_warmup_steps=100)
        assert float(svi.kl_beta(jnp.asarray(0), cfg)) == 0.0
        assert float(svi.kl_beta(jnp.asarray(50), cfg)) == 0.5
        assert float(svi.kl_beta(jnp.asarray(500), cfg)) == 1.0

    def test_elbo_loss_aggregates(self):
        q = GaussianVariational.init(jax.random.key(0), (4, 3), fan_in=4)
        params = {"q": q, "w": jnp.ones((3,))}

        def nll_fn(p, batch, key):
            return jnp.square(batch["x"] @ p["q"].mu).mean(), {"m": jnp.ones(())}

        cfg = svi.SVIConfig(kl_warmup_steps=1, num_train_examples=10)
        loss, aux = svi.elbo_loss(
            nll_fn, params, {"x": jnp.ones((2, 4))}, jax.random.key(1),
            jnp.asarray(10), cfg)
        expected = aux["nll"] + aux["kl"] / 10
        np.testing.assert_allclose(float(loss), float(expected), rtol=1e-5)

    def test_surrogate_sigma_clamp_is_ste(self):
        spec = SurrogateSpec()
        q = GaussianVariational(mu=jnp.array([0.5]),
                                rho=jnp.array([5.0]))  # huge sigma

        def f(q):
            return spec.apply_weight(q, jnp.array([1.0])).sum()

        g = jax.grad(f)(q)
        # forward is clamped...
        w = spec.apply_weight(q, jnp.array([1.0]))
        lo, hi = E.relstd_range()
        assert float(w[0]) <= float((q.mu + hi * jnp.abs(q.mu))[0]) + 1e-2
        # ...but the sigma gradient still flows (STE)
        assert abs(float(g.rho[0])) > 0

    def test_served_sigma_is_softplus_rho_bitwise(self):
        """The serving runner's prepared head: sigma is softplus(rho),
        bit for bit, made in one program; mu and the other leaves are
        the caller's arrays, and a served tree passes through."""
        from repro.core.bayesian import GaussianServed, softplus
        from repro.launch.engine.runner import served_params
        q = GaussianVariational(
            mu=jax.random.normal(jax.random.key(1), (16, 40)),
            rho=4.0 * jax.random.normal(jax.random.key(2), (16, 40)))
        want = np.asarray(softplus(q.rho))
        assert isinstance(q.served(), GaussianServed)
        np.testing.assert_array_equal(np.asarray(q.served().sigma), want)
        w = jnp.ones((3,))
        tree = served_params({"head": {"q": q}, "w": w})
        got = tree["head"]["q"]
        assert isinstance(got, GaussianServed)
        assert got.mu is q.mu and tree["w"] is w
        np.testing.assert_array_equal(np.asarray(got.sigma), want)
        assert served_params(tree) is tree

    def test_mc_forward_shapes(self):
        out = mc_forward(lambda k: jax.random.normal(k, (3,)),
                         jax.random.key(0), 10)
        assert out.shape == (10, 3)
        assert not np.allclose(out[0], out[1])

    def test_mc_forward_seeded_is_seed_deterministic(self):
        from repro.core.bayesian import mc_forward_seeded
        fn = lambda k: jax.random.normal(k, (3,))
        a = mc_forward_seeded(fn, E.KernelEntropy(seed=9), 6)
        b = mc_forward_seeded(fn, E.KernelEntropy(seed=9), 6)
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        c = mc_forward_seeded(fn, E.KernelEntropy(seed=10), 6)
        assert a.shape == (6, 3) and not np.allclose(a, c)
        assert not np.allclose(a[0], a[1])     # samples independent

    def test_bayes_dense_sampled_moments_and_determinism(self):
        from repro.core.bayesian import bayes_dense_sampled
        q = GaussianVariational.init(jax.random.key(0), (16, 8), fan_in=16,
                                     init_sigma=0.1)
        x = jax.random.normal(jax.random.key(1), (4, 16))
        src = E.KernelEntropy(seed=21)
        y = bayes_dense_sampled(x, q, src, num_samples=256)
        assert y.shape == (256, 4, 8)
        y2 = bayes_dense_sampled(x, q, src, num_samples=256)
        np.testing.assert_array_equal(np.asarray(y), np.asarray(y2))
        np.testing.assert_allclose(np.asarray(y.mean(0)),
                                   np.asarray(x @ q.mu), atol=0.2)


# ---------------------------------------------------------------------------
# uncertainty metrics
# ---------------------------------------------------------------------------

class TestUncertainty:
    def test_decomposition_identity(self):
        probs = jax.nn.softmax(
            jax.random.normal(jax.random.key(0), (10, 50, 7)), -1)
        m = U.predictive_moments(probs)
        np.testing.assert_allclose(m["H"], m["SE"] + m["MI"], atol=1e-5)

    def test_confident_consistent_has_low_everything(self):
        p = jnp.zeros((10, 1, 5)).at[:, :, 2].set(30.0)
        m = U.uncertainty_from_logits(p)
        assert float(m["H"][0]) < 1e-3 and float(m["MI"][0]) < 1e-3

    def test_disagreement_is_epistemic(self):
        """Each sample confident but in different classes -> high MI,
        low SE (paper Fig. 4f / 5c)."""
        logits = jnp.zeros((5, 1, 5))
        for i in range(5):
            logits = logits.at[i, 0, i].set(30.0)
        m = U.uncertainty_from_logits(logits)
        assert float(m["MI"][0]) > 1.0
        assert float(m["SE"][0]) < 1e-3

    def test_ambiguity_is_aleatoric(self):
        """Every sample 50/50 between two classes -> high SE, zero MI
        (paper Fig. 5d)."""
        logits = jnp.zeros((8, 1, 5))
        logits = logits.at[:, 0, 0].set(10.0).at[:, 0, 1].set(10.0)
        m = U.uncertainty_from_logits(logits)
        assert float(m["SE"][0]) > 0.6
        assert float(m["MI"][0]) < 1e-4

    def test_auroc_perfect_and_chance(self):
        pos = jnp.array([0.9, 0.8, 0.95])
        neg = jnp.array([0.1, 0.2, 0.05])
        assert float(U.auroc(pos, neg)) == 1.0
        assert float(U.auroc(neg, pos)) == 0.0
        same = jnp.array([0.5, 0.5])
        assert float(U.auroc(same, same)) == 0.5

    def test_roc_curve_monotone(self):
        key = jax.random.key(1)
        pos = jax.random.normal(key, (500,)) + 1.0
        neg = jax.random.normal(jax.random.key(2), (500,))
        r = U.roc_curve(pos, neg, 64)
        assert (jnp.diff(r["tpr"]) >= -1e-6).all()
        assert (jnp.diff(r["fpr"]) >= -1e-6).all()

    def test_rejection_improves_accuracy(self):
        """Wrong predictions given higher MI -> rejecting high-MI raises
        accepted accuracy (the paper's Fig. 4d mechanism)."""
        n = 400
        labels = jnp.zeros((n,), jnp.int32)
        p_mean = jnp.zeros((n, 2)).at[: n // 2, 0].set(1.0) \
            .at[n // 2:, 1].set(1.0)   # second half wrong
        mi = jnp.concatenate([jnp.full((n // 2,), 0.01),
                              jnp.full((n // 2,), 0.5)])
        r = U.rejection_accuracy(p_mean, mi, labels, threshold=0.1)
        assert float(r["accuracy_all"]) == 0.5
        assert float(r["accuracy_accepted"]) == 1.0
        np.testing.assert_allclose(float(r["rejection_rate"]), 0.5)


@settings(max_examples=25, deadline=None)
@given(s=st.integers(2, 12), b=st.integers(1, 8), c=st.integers(2, 9),
       seed=st.integers(0, 2**31 - 1))
def test_prop_uncertainty_decomposition(s, b, c, seed):
    """H = SE + MI >= both >= 0, for any MC predictive tensor."""
    logits = 3 * jax.random.normal(jax.random.key(seed), (s, b, c))
    m = U.uncertainty_from_logits(logits)
    assert (m["H"] >= -1e-6).all() and (m["SE"] >= -1e-6).all()
    assert (m["MI"] >= -1e-6).all()
    np.testing.assert_allclose(m["H"], m["SE"] + m["MI"], atol=1e-4)
    assert (m["H"] <= np.log(c) + 1e-4).all()
