"""Seeded weights of a decoder-only transformer with a Bayesian head.

One jitted call makes every weight on the device, in the type it is
served in, from ``--seed``.  The harness hands them to the program; the
reference makes them again from the same seed, so it takes nothing the
program has made.  The configuration's family
(``bench/families/<reference>.py``) lists its per-layer weights with
``layer_weights(config)``; the embedding, the final norm and the head
are the same for every family and made here.  The scales keep a random
28-layer stack well conditioned: weights N(0, 1/fan_in), norm gains
drawn around their usual values so that a path which drops them shows.
"""

from __future__ import annotations

import functools
from pathlib import Path

import jax
import jax.numpy as jnp

from bench import spec

# sigma of the head's variational posterior: the configuration's init
# sigma times U[0.5, 1.5) per weight, so the variance path is not a
# constant that a wrong operand could still reproduce
SIGMA_SPREAD = 0.5
NORM_GAIN_STD = 0.1


def normal(shape, fan_in, dtype=None) -> tuple:
    """How to draw a weight N(0, 1/fan_in), in the served type unless
    ``dtype`` names another."""
    return ("normal", tuple(shape), fan_in, dtype)


def around(shape, mean, std) -> tuple:
    """How to draw a weight N(mean, std**2) in the served type: norm
    gains and biases."""
    return ("around", tuple(shape), mean, std)


def dims(config: dict) -> tuple:
    """(d, V, init_sigma, dtype) of a configuration file."""
    return (config["hidden_size"], config["vocab_size"],
            config["bayesian_head"]["init_sigma"], config["torch_dtype"])


@functools.partial(jax.jit, static_argnums=(1, 2))
def _generate(key, dm: tuple, layers: tuple) -> dict:
    d, V, sigma0, dtype = dm
    served = jnp.dtype(dtype)
    # one key each: the head's sigma, the embedding, every per-layer
    # weight in the family's order, the final norm, the head's mu
    ks = iter(jax.random.split(key, 4 + len(layers)))

    def draw(how):
        kind, shape, a, b = how
        z = jax.random.normal(next(ks), shape, jnp.float32)
        if kind == "normal":
            return (z / jnp.sqrt(float(a))).astype(b or served)
        return (a + b * z).astype(served)

    sigma = sigma0 * (1.0 + SIGMA_SPREAD * (2.0 * jax.random.uniform(
        next(ks), (d, V), jnp.float32) - 1.0))
    w = {"embed": draw(normal((V, d), d))}
    w.update((name, draw(how)) for name, how in layers)
    w["final_norm"] = draw(around((d,), 1.0, NORM_GAIN_STD))
    w["head_mu"] = draw(normal((d, V), d, "float32"))
    # served as rho with sigma = softplus(rho), as the head stores it
    w["head_rho"] = jnp.log(jnp.expm1(sigma))
    return w


def generate(config: dict, seed: int, root: Path = spec.ROOT) -> dict:
    """Every weight of ``config`` for ``seed``, made on the default
    device in one compiled program."""
    family = spec.family_module(config["reference"], root)
    return _generate(jax.random.key(seed), dims(config),
                     tuple(family.layer_weights(config)))
