"""Plain forward of GQA with no q, k or v biases: the test's second
reference, written into its root as ``bench/references/gqa_nobias.py``.

Qwen2's reference (``bench/references/qwen2.py``) without the biases;
the head is the same.
"""

import functools
from pathlib import Path

import jax
import jax.numpy as jnp

from bench import spec

Q = spec.reference_module("qwen2", Path(__file__).resolve().parents[2])
head_stats = Q.head_stats
logits_at = Q.logits_at


def hidden_states(w, tokens, config, precision):
    return _hidden_states(w, tokens, (
        config["num_attention_heads"], config["num_key_value_heads"],
        config["head_dim"], float(config["rope_theta"]),
        float(config["rms_norm_eps"])), precision)


@functools.partial(jax.jit, static_argnums=(2, 3))
def _hidden_states(w, tokens, shape, precision):
    H, Hkv, hd, theta, eps = shape
    T = tokens.shape[0]
    x = w["embed"][tokens].astype(jnp.float32)
    causal = jnp.tril(jnp.ones((T, T), bool))

    def layer(x, lw):
        h = Q._rms_norm(x, lw["ln1"], eps)
        q = Q._mm(h, lw["wq"], precision).reshape(T, H, hd)
        k = Q._mm(h, lw["wk"], precision).reshape(T, Hkv, hd)
        v = Q._mm(h, lw["wv"], precision).reshape(T, Hkv, hd)
        q, k = Q._rope(q, theta), Q._rope(k, theta)
        k = jnp.repeat(k, H // Hkv, axis=1)
        v = jnp.repeat(v, H // Hkv, axis=1)
        s = jnp.einsum("thd,shd->hts", q, k,
                       precision=jax.lax.Precision.HIGHEST) / jnp.sqrt(
                           jnp.float32(hd))
        p = jax.nn.softmax(jnp.where(causal[None], s, -jnp.inf), -1)
        o = jnp.einsum("hts,shd->thd", p, v,
                       precision=jax.lax.Precision.HIGHEST)
        x = x + Q._mm(o.reshape(T, H * hd), lw["wo"], precision)
        h = Q._rms_norm(x, lw["ln2"], eps)
        g = Q._mm(h, lw["w_gate"], precision)
        u = Q._mm(h, lw["w_up"], precision)
        x = x + Q._mm(jax.nn.silu(g) * u, lw["w_down"], precision)
        return x, None

    names = ("ln1", "wq", "wk", "wv", "wo", "ln2", "w_gate", "w_up",
             "w_down")
    x, _ = jax.lax.scan(layer, x, {n: w[n] for n in names})
    return Q._rms_norm(x, w["final_norm"], eps)
