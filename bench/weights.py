"""Seeded weights of a decoder-only transformer with a Bayesian head.

One jitted call makes every weight on the device, in the type it is
served in, from ``--seed``.  The harness hands them to the program; the
reference makes them again from the same seed, so it takes nothing the
program has made.  The scales keep a random 28-layer stack well
conditioned: weights N(0, 1/fan_in), norm gains and QKV biases drawn
around their usual values so that a path which drops them shows.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

# sigma of the head's variational posterior: the configuration's init
# sigma times U[0.5, 1.5) per weight, so the variance path is not a
# constant that a wrong operand could still reproduce
SIGMA_SPREAD = 0.5
NORM_GAIN_STD = 0.1
QKV_BIAS_STD = 0.1


def dims(config: dict) -> tuple:
    """(L, d, H, Hkv, hd, ff, V, init_sigma, dtype) of a configuration
    file."""
    return (config["num_hidden_layers"], config["hidden_size"],
            config["num_attention_heads"], config["num_key_value_heads"],
            config["head_dim"], config["intermediate_size"],
            config["vocab_size"],
            config["bayesian_head"]["init_sigma"], config["torch_dtype"])


@functools.partial(jax.jit, static_argnums=1)
def _generate(key, dm: tuple) -> dict:
    L, d, H, Hkv, hd, ff, V, sigma0, dtype = dm
    served = jnp.dtype(dtype)
    ks = iter(jax.random.split(key, 16))

    def normal(shape, fan_in, dtype=served):
        return (jax.random.normal(next(ks), shape, jnp.float32)
                / jnp.sqrt(float(fan_in))).astype(dtype)

    def around(shape, mean, std):
        return (mean + std * jax.random.normal(next(ks), shape,
                                               jnp.float32)).astype(served)

    sigma = sigma0 * (1.0 + SIGMA_SPREAD * (2.0 * jax.random.uniform(
        next(ks), (d, V), jnp.float32) - 1.0))
    return {
        "embed": normal((V, d), d),
        "ln1": around((L, d), 1.0, NORM_GAIN_STD),
        "wq": normal((L, d, H * hd), d),
        "bq": around((L, H * hd), 0.0, QKV_BIAS_STD),
        "wk": normal((L, d, Hkv * hd), d),
        "bk": around((L, Hkv * hd), 0.0, QKV_BIAS_STD),
        "wv": normal((L, d, Hkv * hd), d),
        "bv": around((L, Hkv * hd), 0.0, QKV_BIAS_STD),
        "wo": normal((L, H * hd, d), H * hd),
        "ln2": around((L, d), 1.0, NORM_GAIN_STD),
        "w_gate": normal((L, d, ff), d),
        "w_up": normal((L, d, ff), d),
        "w_down": normal((L, ff, d), ff),
        "final_norm": around((d,), 1.0, NORM_GAIN_STD),
        "head_mu": normal((d, V), d, jnp.float32),
        # served as rho with sigma = softplus(rho), as the head stores it
        "head_rho": jnp.log(jnp.expm1(sigma)),
    }


def generate(config: dict, seed: int) -> dict:
    """Every weight of ``config`` for ``seed``, made on the default
    device in one compiled program."""
    return _generate(jax.random.key(seed), dims(config))
