"""The expert layer's routed part as grouped matmuls over the held experts.

A chip holds ``held`` routed experts of each of its ``Lm`` MoE layers,
stacked as ``w13`` (Lm, held, d, 2f) (gate columns, then up) and ``w2``
(Lm, held, f, d).  Each token's top-k picks arrive as local expert ids
(``held`` for a pick this chip does not hold, or a row that routes
nowhere) with their gate weights.  The (token, k) assignments are sorted
by expert, and two grouped matmuls (megablox ``gmm``, Pallas on TPU) run
the gate/up projection and the down projection of every held expert
over its rows only: a row's output never depends on another row, so
the layer drops nothing and couples no tokens.

The kernels read the layer's experts straight out of the whole stack:
the group sizes name the layer's ``held`` groups among ``Lm * held``,
and a last group holds the rows that route nowhere, which the kernels
skip.  No layer's weights are sliced or copied per call.

``grouped_ffn_ref`` is the same math in plain ``jnp`` (every held
expert over every row, combined by the gate weights): the path off the
TPU and under a mesh, and the oracle of the tests.
"""

from __future__ import annotations

import functools
import importlib

import jax
import jax.numpy as jnp

# the Pallas grouped matmul jax ships; its un-jitted body, so that the
# kernel runs (and is named in a device trace) inside ``moe_gmm``
_gmm = importlib.import_module(
    "jax.experimental.pallas.ops.tpu.megablox.gmm").gmm.__wrapped__

ROW_TILE = 16          # rows of a grouped-matmul tile (a bf16 sublane pair)


def _act(name: str):
    return jax.nn.silu if name == "silu" else jax.nn.gelu


def _tile(n: int, prefs: tuple) -> int:
    """The largest preferred tile that divides ``n``, else ``n`` whole."""
    return next((t for t in prefs if n % t == 0), n)


def tilings(d: int, f: int) -> tuple[tuple, tuple]:
    """(tm, tk, tn) of the gate/up and the down grouped matmuls: blocks
    of at most 512 x 1408 and 1408 x 1024 bf16 weights, double-buffered
    well inside 16 MiB of scoped VMEM at d=2048, f=1408."""
    return ((ROW_TILE, _tile(d, (512, 256, 128)),
             _tile(2 * f, (1408, 1024, 512, 256, 128))),
            (ROW_TILE, f if f <= 1536 else _tile(f, (1024, 512, 256, 128)),
             _tile(d, (1024, 512, 256, 128))))


def grouped_ffn_ref(x, experts, gates, w13, w2, layer, *, act="silu"):
    """Plain ``jnp``: ``sum_k gates[t, k] * FFN_{experts[t, k]}(x[t])``.

    x: (T, d); experts: (T, K) int32 local ids, ``held`` for none;
    gates: (T, K) float32, 0 where the id is ``held``; w13/w2: the
    (Lm, held, ...) stacks; layer: the MoE layer.  Float32 accumulation,
    the gated activation rounded to ``x.dtype`` before the down
    projection, as the kernel path does.  Returns (T, d) float32."""
    held, f = w13.shape[1], w2.shape[2]
    w13_l = jax.lax.dynamic_index_in_dim(w13, layer, 0, keepdims=False)
    w2_l = jax.lax.dynamic_index_in_dim(w2, layer, 0, keepdims=False)
    gu = jnp.einsum("td,edf->etf", x, w13_l,
                    preferred_element_type=jnp.float32)
    h = (_act(act)(gu[..., :f]) * gu[..., f:]).astype(x.dtype)
    y = jnp.einsum("etf,efd->etd", h, w2_l,
                   preferred_element_type=jnp.float32)
    comb = jnp.einsum("tk,tke->te", gates, jax.nn.one_hot(
        experts, held, dtype=jnp.float32))
    return jnp.einsum("te,etd->td", comb, y)


@functools.partial(jax.jit, static_argnames=("act", "interpret"))
def moe_gmm(x, experts, gates, w13, w2, layer, *, act="silu",
            interpret=False):
    """The kernel path of ``grouped_ffn_ref`` (same arguments and
    result): sort the assignments by expert, one grouped matmul for
    gate/up and one for down over the layer's held experts, then put
    each row back in place, weighted by its gate."""
    T, K = experts.shape
    Lm, held, d, _ = w13.shape
    f = w2.shape[2]
    m = T * K
    mp = -(-m // ROW_TILE) * ROW_TILE
    key = jnp.full((mp,), held, jnp.int32).at[:m].set(experts.reshape(-1))
    wts = jnp.zeros((mp,), jnp.float32).at[:m].set(gates.reshape(-1))
    order = jnp.argsort(key, stable=True)
    counts = jnp.bincount(key, length=held + 1).astype(jnp.int32)
    sizes = jax.lax.dynamic_update_slice(
        jnp.zeros((Lm * held + 1,), jnp.int32), counts[:held],
        (layer * held,)).at[-1].set(counts[held])
    rows = jnp.take(x, jnp.minimum(order // K, T - 1), axis=0)
    t13, t2 = tilings(d, f)
    gu = _gmm(rows, w13.reshape(Lm * held, d, 2 * f), sizes, jnp.float32,
              t13, None, None, False, interpret)
    h = (_act(act)(gu[:, :f]) * gu[:, f:]).astype(x.dtype)
    y = _gmm(h, w2.reshape(Lm * held, f, d), sizes, jnp.float32, t2, None,
             None, False, interpret)
    y = y * jnp.take(wts, order)[:, None]
    back = jnp.zeros((mp,), jnp.int32).at[order].set(
        jnp.arange(mp, dtype=jnp.int32))
    return jnp.take(y, back[:m], axis=0).reshape(T, K, d).sum(1)
