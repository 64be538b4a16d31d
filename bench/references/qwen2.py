"""Plain Qwen2 forward with the Bayesian output head: the reference.

Follows the published architecture (arXiv:2407.10671; the Hugging Face
``Qwen2ForCausalLM``): token embedding, then per layer RMSNorm, GQA
attention with biases on q, k and v, rotary positions (half-split
rotation, ``theta ** (-2i / head_dim)``), a residual, RMSNorm and a
SwiGLU MLP with a residual; a final RMSNorm.  The output projection is
the paper's Gaussian variational head: ``S`` local-reparameterisation
draws ``x @ mu + sqrt(x**2 @ sigma**2) * xi`` per position, reduced to
the predictive entropy H of the mean of the S softmaxes, the expected
entropy SE, MI = max(H - SE, 0), the mean's top probability and its
argmax.

Everything runs in float32 at ``highest`` matmul precision, one request
at a time over its whole sequence, with no cache, kernel or batching.
It imports nothing of the program: weights come from ``bench.weights``,
under the names ``bench/families/qwen2.py`` gives them.

A reference gives ``bench.check`` three calls:
``hidden_states(w, tokens, config, precision)`` (``config`` is the
configuration file's dict, from which it reads its own sizes),
``head_stats(w, x, key, samples, sets, precision)`` and
``logits_at(w, x, tokens)``.

``precision="control"`` is the control: each matmul operand is rounded
to the precision next below the one the configuration states, with
float32 accumulation: the bf16 body's weights and activations to
float8_e4m3fn, the float32 head's x, mu and sigma to bfloat16.
``precision="head_control"`` rounds the head's operands alone (the body
stays float32): what a program with a bfloat16 head would serve.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

# what each stand-in precision rounds each part's matmul operands to
ROUNDING = {"control": {"body": jnp.float8_e4m3fn, "head": jnp.bfloat16},
            "head_control": {"head": jnp.bfloat16}}


def _round(x, precision: str, part: str = "body"):
    dtype = ROUNDING.get(precision, {}).get(part)
    if dtype is not None:
        return x.astype(dtype).astype(jnp.float32)
    return x


def _mm(a, b, precision: str):
    return jnp.dot(_round(a, precision), _round(b.astype(jnp.float32),
                                                precision),
                   precision=jax.lax.Precision.HIGHEST)


def _rms_norm(x, gain, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * gain.astype(jnp.float32)


def _rope(x, theta):
    """x: (T, heads, hd) at positions 0..T-1."""
    T, _, hd = x.shape
    half = hd // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def hidden_states(w: dict, tokens, config: dict, precision: str):
    """Final-norm hidden states (T, d) of one sequence ``tokens`` (T,)
    under the configuration file's dict ``config``."""
    return _hidden_states(w, tokens, (
        config["num_attention_heads"], config["num_key_value_heads"],
        config["head_dim"], float(config["rope_theta"]),
        float(config["rms_norm_eps"])), precision)


@functools.partial(jax.jit, static_argnums=(2, 3))
def _hidden_states(w: dict, tokens, shape: tuple, precision: str):
    """``shape`` = (H, Hkv, hd, rope_theta, rms_norm_eps)."""
    H, Hkv, hd, theta, eps = shape
    T = tokens.shape[0]
    x = w["embed"][tokens].astype(jnp.float32)
    causal = jnp.tril(jnp.ones((T, T), bool))

    def layer(x, lw):
        h = _rms_norm(x, lw["ln1"], eps)
        q = (_mm(h, lw["wq"], precision) + lw["bq"]).reshape(T, H, hd)
        k = (_mm(h, lw["wk"], precision) + lw["bk"]).reshape(T, Hkv, hd)
        v = (_mm(h, lw["wv"], precision) + lw["bv"]).reshape(T, Hkv, hd)
        q, k = _rope(q, theta), _rope(k, theta)
        rep = H // Hkv
        k = jnp.repeat(k, rep, axis=1)          # head h reads kv head h//rep
        v = jnp.repeat(v, rep, axis=1)
        s = jnp.einsum("thd,shd->hts", _round(q, precision),
                       _round(k, precision),
                       precision=jax.lax.Precision.HIGHEST) / jnp.sqrt(
                           jnp.float32(hd))
        p = jax.nn.softmax(jnp.where(causal[None], s, -jnp.inf), -1)
        o = jnp.einsum("hts,shd->thd", _round(p, precision),
                       _round(v, precision),
                       precision=jax.lax.Precision.HIGHEST)
        x = x + _mm(o.reshape(T, H * hd), lw["wo"], precision)
        h = _rms_norm(x, lw["ln2"], eps)
        g = _mm(h, lw["w_gate"], precision)
        u = _mm(h, lw["w_up"], precision)
        x = x + _mm(jax.nn.silu(g) * u, lw["w_down"], precision)
        return x, None

    names = ("ln1", "wq", "bq", "wk", "bk", "wv", "bv", "wo", "ln2",
             "w_gate", "w_up", "w_down")
    x, _ = jax.lax.scan(layer, x, {n: w[n] for n in names})
    return _rms_norm(x, w["final_norm"], eps)


@functools.partial(jax.jit, static_argnums=(3, 4, 5))
def head_stats(w: dict, x, key, samples: int, sets: int, precision: str):
    """The Bayesian head at hidden states x (n, d).

    Returns per position the mean-logit maximum and argmax, and the
    program's estimators averaged over ``sets`` independent sets of
    ``samples`` draws: H, SE, MI; plus the first set's predicted token
    (argmax of the mean softmax) and its top probability."""
    mu = w["head_mu"]
    sigma = jax.nn.softplus(w["head_rho"])
    mean = jnp.dot(_round(x, precision, "head"),
                   _round(mu, precision, "head"),
                   precision=jax.lax.Precision.HIGHEST)       # (n, V)
    var = jnp.dot(_round(x * x, precision, "head"),
                  _round(sigma, precision, "head") ** 2,
                  precision=jax.lax.Precision.HIGHEST)
    std = jnp.sqrt(jnp.maximum(var, 0.0))
    xi = jax.random.normal(key, (sets, samples) + mean.shape, jnp.float32)
    logp = jax.nn.log_softmax(mean + std * xi, -1)            # (R,S,n,V)
    se = -(jnp.exp(logp) * logp).sum(-1).mean(1)             # (R, n)
    pbar = jnp.exp(logp).mean(1)                              # (R, n, V)
    h = -(pbar * jnp.log(pbar + 1e-12)).sum(-1)
    return {"logit_max": mean.max(-1), "argmax": mean.argmax(-1),
            "logz": jax.nn.logsumexp(mean, -1),
            "H": h.mean(0), "SE": se.mean(0),
            "MI": jnp.maximum(h - se, 0.0).mean(0),
            "token": pbar[0].argmax(-1).astype(jnp.int32),
            "p_max": pbar[0].max(-1)}


@functools.partial(jax.jit, static_argnums=(3,))
def logits_at(w: dict, x, tokens, precision: str = "float32"):
    """Mean logit of ``tokens`` (n,) at hidden states x (n, d)."""
    cols = w["head_mu"][:, tokens].T                          # (n, d)
    return jnp.sum(_round(x, precision, "head")
                   * _round(cols, precision, "head"), -1)
