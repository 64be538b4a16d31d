"""Plain DeepSeekMoE forward with the Bayesian output head: the reference.

Follows the published architecture (arXiv:2401.06066; the Hugging Face
``DeepseekForCausalLM``) over one chip's share of it: token embedding,
then per layer RMSNorm, multi-head attention without biases and with
rotary positions, a residual, RMSNorm and, for the first
``first_k_dense_replace`` layers, a SwiGLU MLP; for the others

    x += sum_{s < n_shared} SwiGLU_s(h)
         + sum_{i in top_k(g), i held} g_i * SwiGLU_i(h),
    g = softmax(h @ W_router) over all router_experts,

with the top-k gates renormalised only where ``norm_topk_prob``, and the
routed sum over the experts this share holds (``n_routed_experts`` from
``expert_offset``); a final RMSNorm.  No capacity: every token gets
every pick.  The head is Qwen2's reference head, whose functions this
module takes from ``bench/references/qwen2.py``, with its rotary
positions and norms.

Everything runs in float32 at ``highest`` matmul precision, one request
at a time over its whole sequence, with no cache, kernel or batching,
every held expert over every position.  It imports nothing of the
program.  ``precision="control"`` rounds the body's matmul operands to
float8_e4m3fn as Qwen2's does, the float32 router's and head's to
bfloat16; ``"head_control"`` the head's alone.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from bench import spec

_QWEN2 = spec.reference_module("qwen2")
head_stats = _QWEN2.head_stats
logits_at = _QWEN2.logits_at
_rms_norm, _rope = _QWEN2._rms_norm, _QWEN2._rope

# what each stand-in precision rounds each part's matmul operands to
ROUNDING = {"control": {"body": jnp.float8_e4m3fn, "router": jnp.bfloat16,
                        "head": jnp.bfloat16},
            "head_control": {"head": jnp.bfloat16}}


def _round(x, precision: str, part: str = "body"):
    dtype = ROUNDING.get(precision, {}).get(part)
    return x.astype(dtype).astype(jnp.float32) if dtype is not None else x


def _mm(a, b, precision: str, part: str = "body"):
    return jnp.dot(_round(a, precision, part),
                   _round(b.astype(jnp.float32), precision, part),
                   precision=jax.lax.Precision.HIGHEST)


def _swiglu(h, gate, up, down, precision):
    return _mm(jax.nn.silu(_mm(h, gate, precision))
               * _mm(h, up, precision), down, precision)


def hidden_states(w: dict, tokens, config: dict, precision: str):
    """Final-norm hidden states (T, d) of one sequence ``tokens`` (T,)
    under the configuration file's dict ``config``."""
    return _hidden_states(w, tokens, (
        config["num_attention_heads"], config["num_key_value_heads"],
        config["head_dim"], float(config["rope_theta"]),
        float(config["rms_norm_eps"]), config["first_k_dense_replace"],
        config["num_experts_per_tok"], bool(config["norm_topk_prob"]),
        config["n_routed_experts"], config["expert_offset"],
        config["n_shared_experts"], config["moe_intermediate_size"]),
        precision)


@functools.partial(jax.jit, static_argnums=(2, 3))
def _hidden_states(w: dict, tokens, shape: tuple, precision: str):
    """``shape`` = (H, Hkv, hd, rope_theta, rms_norm_eps, dense layers,
    top_k, renormalise, experts held, first held, shared experts,
    expert width)."""
    H, Hkv, hd, theta, eps, F, K, renorm, held, off, ns, f = shape
    T = tokens.shape[0]
    x = w["embed"][tokens].astype(jnp.float32)
    causal = jnp.tril(jnp.ones((T, T), bool))

    def attention(x, i):
        h = _rms_norm(x, w["ln1"][i], eps)
        q = _mm(h, w["wq"][i], precision).reshape(T, H, hd)
        k = _mm(h, w["wk"][i], precision).reshape(T, Hkv, hd)
        v = _mm(h, w["wv"][i], precision).reshape(T, Hkv, hd)
        q, k = _rope(q, theta), _rope(k, theta)
        k = jnp.repeat(k, H // Hkv, axis=1)
        v = jnp.repeat(v, H // Hkv, axis=1)
        s = jnp.einsum("thd,shd->hts", _round(q, precision),
                       _round(k, precision),
                       precision=jax.lax.Precision.HIGHEST) / jnp.sqrt(
                           jnp.float32(hd))
        p = jax.nn.softmax(jnp.where(causal[None], s, -jnp.inf), -1)
        o = jnp.einsum("hts,shd->thd", _round(p, precision),
                       _round(v, precision),
                       precision=jax.lax.Precision.HIGHEST)
        x = x + _mm(o.reshape(T, H * hd), w["wo"][i], precision)
        return x, _rms_norm(x, w["ln2"][i], eps)

    def moe(h, j):
        g = jax.nn.softmax(_mm(h, w["router"][j], precision, "router"), -1)
        topv, topi = jax.lax.top_k(g, K)
        if renorm:
            topv = topv / topv.sum(-1, keepdims=True)
        y = jnp.zeros_like(h)
        for s in range(ns):
            cols = slice(s * f, (s + 1) * f)
            y = y + _swiglu(h, w["shared_gate"][j][:, cols],
                            w["shared_up"][j][:, cols],
                            w["shared_down"][j][cols], precision)
        for e in range(held):
            gate = jnp.sum(jnp.where(topi == off + e, topv, 0.0), -1)
            gu = w["experts_gate_up"][j, e]
            y = y + gate[:, None] * _swiglu(
                h, gu[:, :f], gu[:, f:], w["experts_down"][j, e], precision)
        return y

    for i in range(F):
        x, h = attention(x, i)
        x = x + _swiglu(h, w["w_gate"][i], w["w_up"][i], w["w_down"][i],
                        precision)

    def layer(x, j):
        x, h = attention(x, F + j)
        return x + moe(h, j), None

    x, _ = jax.lax.scan(layer, x, jnp.arange(w["router"].shape[0]))
    return _rms_norm(x, w["final_norm"], eps)
