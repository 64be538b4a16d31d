"""Variational (Bayesian) parameters and layers.

The paper's BNN keeps a *single* probabilistic layer (partial stochasticity,
ref. 15) whose weights carry Gaussian variational posteriors
``q(w) = N(mu, sigma^2)`` trained with SVI against a Gaussian prior.
``sigma`` is parameterized through softplus(rho) for unconstrained
optimization (Blundell et al. 2015).

The sampled forward pass is reparameterized:  w = mu + sigma * eps, with eps
from an ``EntropySource`` -- the digital PRNG baseline, the ASE digital
twin, an explicit entropy-stream operand, or (the fast path) the in-kernel
TPU PRNG: ``KernelEntropy`` carries a base seed and the Pallas ``*_sampled``
kernels draw the variates in-register, so eps never exists in HBM.  The
same code path therefore runs the surrogate (training) and the machine
(prediction) exactly like the paper swaps its surrogate for the photonic
hardware; ``bayes_dense_sampled`` / ``mc_forward_seeded`` are the
seed-driven twins of ``bayes_dense`` / ``mc_forward``.
"""

from __future__ import annotations

import dataclasses
from typing import Callable, Optional

import jax
import jax.numpy as jnp

from repro.core.entropy import EntropySource, KernelEntropy, PRNGEntropy
from repro.core.photonic import quantize_ste


def softplus(x):
    return jnp.logaddexp(x, 0.0)


def inv_softplus(y):
    return jnp.log(jnp.expm1(jnp.maximum(y, 1e-8)))


@dataclasses.dataclass(frozen=True)
class GaussianVariational:
    """q(w) = N(mu, softplus(rho)^2) over a weight tensor."""
    mu: jax.Array
    rho: jax.Array

    @property
    def sigma(self) -> jax.Array:
        return softplus(self.rho)

    @staticmethod
    def init(key: jax.Array, shape: tuple[int, ...], fan_in: int,
             init_sigma: float = 0.05, dtype=jnp.float32) -> "GaussianVariational":
        mu = jax.random.normal(key, shape, dtype) / jnp.sqrt(float(fan_in))
        rho = jnp.full(shape, inv_softplus(init_sigma), dtype)
        return GaussianVariational(mu=mu, rho=rho)

    def sample(self, key: jax.Array, source: Optional[EntropySource] = None,
               num: Optional[int] = None) -> jax.Array:
        src = source or PRNGEntropy()
        shape = self.mu.shape if num is None else (num, *self.mu.shape)
        eps = src.sample(key, shape, self.mu.dtype)
        return self.mu + self.sigma * eps

    def sample_with_eps(self, eps: jax.Array) -> jax.Array:
        """Reparameterized sample from an externally supplied entropy tensor
        (the kernel path: entropy is an operand, not generated inline)."""
        return self.mu + self.sigma * eps

    def kl_to_prior(self, prior_sigma: float = 1.0) -> jax.Array:
        """KL( N(mu, sigma) || N(0, prior_sigma) ), summed over weights."""
        s2 = self.sigma ** 2
        p2 = prior_sigma ** 2
        kl = 0.5 * (s2 / p2 + self.mu ** 2 / p2 - 1.0 - jnp.log(s2 / p2))
        return kl.sum()

    def served(self) -> "GaussianServed":
        """The posterior in the form a serving head reads: sigma made
        once, not recomputed from rho on every read."""
        return GaussianServed(mu=self.mu, sigma=softplus(self.rho))


@dataclasses.dataclass(frozen=True)
class GaussianServed:
    """q(w) = N(mu, sigma^2) with sigma materialized: the inference-only
    form of a ``GaussianVariational`` (same ``.mu`` / ``.sigma``), made
    once by the serving runner.  Training keeps ``rho``."""
    mu: jax.Array
    sigma: jax.Array


jax.tree_util.register_pytree_node(
    GaussianVariational,
    lambda g: ((g.mu, g.rho), None),
    lambda _, c: GaussianVariational(*c),
)
jax.tree_util.register_pytree_node(
    GaussianServed,
    lambda g: ((g.mu, g.sigma), None),
    lambda _, c: GaussianServed(*c),
)


# --------------------------------------------------------------------------
# layer applications (pure functions over a GaussianVariational + inputs)
# --------------------------------------------------------------------------

def bayes_dense(x: jax.Array, q: GaussianVariational, key: jax.Array,
                source: Optional[EntropySource] = None,
                hardware_bits: Optional[int] = None,
                w_range: float = 1.0) -> jax.Array:
    """y = x @ w, w ~ q. One weight draw per call (per MC sample).

    hardware_bits: if set, pass the sampled weights through the machine's
    STE quantizer -- the surrogate's limited-accuracy forward (paper §BNN).
    """
    w = q.sample(key, source)
    if hardware_bits is not None:
        w = quantize_ste(w, hardware_bits, w_range)
    return x @ w


def bayes_conv2d(x: jax.Array, q: GaussianVariational, key: jax.Array,
                 source: Optional[EntropySource] = None,
                 stride: int = 1, groups: int = 1,
                 hardware_bits: Optional[int] = None,
                 w_range: float = 1.0) -> jax.Array:
    """NCHW conv with sampled weights q.mu/q.sigma of shape (O, I/g, kh, kw).

    This is the layer the photonic machine executes: a 3x3 kernel has 9
    weights == the machine's 9 spectral channels; grouped convs minimize
    unique weights (paper: 'favoring highly grouped convolutions').
    """
    w = q.sample(key, source)
    if hardware_bits is not None:
        w = quantize_ste(w, hardware_bits, w_range)
    return jax.lax.conv_general_dilated(
        x, w, window_strides=(stride, stride), padding="SAME",
        feature_group_count=groups,
        dimension_numbers=("NCHW", "OIHW", "NCHW"))


def mc_forward(apply_fn: Callable[[jax.Array], jax.Array], key: jax.Array,
               num_samples: int) -> jax.Array:
    """Run ``apply_fn(key_i)`` for N MC samples; stack on axis 0.

    apply_fn must consume a PRNG key and return class probabilities/logits.
    The paper uses N=10 samples per prediction.
    """
    keys = jax.random.split(key, num_samples)
    return jax.vmap(apply_fn)(keys)


# --------------------------------------------------------------------------
# seed-driven fast path (in-kernel entropy on TPU)
# --------------------------------------------------------------------------

def bayes_dense_sampled(x: jax.Array, q: GaussianVariational,
                        entropy: KernelEntropy, num_samples: int,
                        impl: str = "auto") -> jax.Array:
    """All S MC samples of y = x @ w, w ~ q, in one fused call: (S, M, N).

    On TPU the weight noise is generated inside the kernel from
    ``entropy.seed`` (mu/sigma tiles read once for all S samples — the
    37.5 ps/conv amortization); elsewhere the seeded oracle runs.  The
    per-sample twin is ``bayes_dense`` (one key, one draw).
    """
    from repro.kernels import ops
    return ops.bayes_matmul_sampled(x, q.mu, q.sigma, entropy.fold(),
                                    num_samples=num_samples, impl=impl)


def mc_forward_seeded(apply_fn: Callable[[jax.Array], jax.Array],
                      entropy: KernelEntropy,
                      num_samples: int) -> jax.Array:
    """Seed-driven ``mc_forward``: sample s runs on ``entropy.key(s)``.

    Deterministic per base seed (same KernelEntropy -> same prediction),
    so serving replicas with the same seed agree bit-for-bit off-TPU and
    distributionally on-TPU.
    """
    keys = jax.random.split(entropy.key(), num_samples)
    return jax.vmap(apply_fn)(keys)
