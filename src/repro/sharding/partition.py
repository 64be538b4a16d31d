"""Sharding rules: param-path -> PartitionSpec, activation constraints.

Logical mesh axes:
  * ``pod``   -- inter-pod data parallelism (multi-pod mesh only)
  * ``data``  -- intra-pod data parallelism; also the FSDP shard axis for
                 large-arch weights (ZeRO-3 style via GSPMD)
  * ``model`` -- tensor parallelism (heads / ff / vocab / experts)

Rules are name-based over the flattened param path, so every architecture
in the zoo gets coherent sharding without per-model boilerplate.  Stacked
scan layers contribute a leading ``L`` axis which is never sharded.
"""

from __future__ import annotations

import re
import threading
from typing import Any, Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core.bayesian import GaussianServed, GaussianVariational

# ---------------------------------------------------------------------------
# activation-constraint context (set by launch scripts, no-op otherwise)
# ---------------------------------------------------------------------------

_ctx = threading.local()


def set_mesh_context(mesh: Optional[Mesh]) -> None:
    _ctx.mesh = mesh
    _ctx.batch_axes = None
    if mesh is not None:
        axes = mesh.axis_names
        _ctx.batch_axes = tuple(a for a in ("pod", "data") if a in axes)


def get_mesh() -> Optional[Mesh]:
    return getattr(_ctx, "mesh", None)


def constrain_seq(x: jax.Array, enabled: bool = True) -> jax.Array:
    """Sequence-parallel residual stream: shard (B, S, d) activations'
    S over 'model' (Korthikanti et al.): the attention/MLP row-parallel
    all-reduce becomes reduce-scatter + all-gather (same link bytes) and
    every saved-for-backward residual shrinks by the TP width — the
    capacity fix that keeps 64-layer remat stacks inside HBM
    (EXPERIMENTS.md §Perf/grok iteration 6).

    No-op when S doesn't divide the model axis (decode steps, tests) or
    when the arch opts out (``ArchConfig.seq_parallel``).
    """
    mesh = get_mesh()
    if not enabled:
        return x
    if mesh is None or "model" not in mesh.axis_names or x.ndim != 3:
        return x
    if x.shape[1] % mesh.shape["model"] != 0:
        return x
    return constrain(x, "batch", "model", None)


def constrain(x: jax.Array, *spec) -> jax.Array:
    """with_sharding_constraint if a mesh context is active, else no-op.

    spec entries: "batch" expands to the active DP axes tuple, "model"
    passes through, None is unsharded.
    """
    mesh = get_mesh()
    if mesh is None:
        return x
    resolved = tuple(
        (_ctx.batch_axes if s == "batch" else s) for s in spec)
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(mesh, P(*resolved)))


# ---------------------------------------------------------------------------
# serving tensor-parallel context (set by launch.engine.runner.ModelRunner)
# ---------------------------------------------------------------------------
#
# Serving TP is ALL-GATHER-ONLY: every weight whose contraction dim the
# train rules shard (wo, w2, out_proj, embed-on-vocab, experts) stays
# REPLICATED at serve time, and the few activations that feed a
# contraction over a sharded dim are force-replicated (``gather_rep``)
# first.  The reason is bitwise: a row-parallel matmul ends in an
# all-reduce of per-shard PARTIAL SUMS, and float addition is not
# associative — the sharded engine would drift from the unsharded
# reference in the last ulp.  A forced all-gather is pure data movement
# (no cross-shard reduction ever happens), so every f32 sum runs at full
# extent on every device and the sharded runner replays the unsharded
# token/uncertainty stream bit-for-bit in operand-entropy mode
# (tests/test_mesh_runner.py).  The context is separate from the train
# mesh context above so training sharding is unaffected.

_serve_ctx = threading.local()


def set_serve_mesh(mesh: Optional[Mesh]) -> None:
    """Activate (or clear) the serving-TP mesh for the calling thread.

    ``launch.engine.runner.ModelRunner`` sets this around every jitted
    dispatch so the constraints below bake into the traced program; the
    model code itself never knows whether it is running sharded.

    CAVEAT: the mesh is hidden state that jax's trace cache cannot see.
    Tracing the SAME function object with the same avals first without
    and then with a mesh reuses the meshless jaxpr — every
    ``gather_rep`` silently a no-op in the "sharded" run.  Jit a fresh
    function object (closure/lambda) per mesh context, as
    ``ModelRunner._jit`` does with its per-instance lambdas.
    """
    _serve_ctx.mesh = mesh


def get_serve_mesh() -> Optional[Mesh]:
    return getattr(_serve_ctx, "mesh", None)


def gather_rep(x: jax.Array) -> jax.Array:
    """Force ``x`` to replicated under the serve mesh (no-op otherwise).

    Placed DIRECTLY on the output of each column-sharded matmul (q/k/v
    projections, MLP w1/w3, head mu/rho dots).  Without the constraint
    GSPMD is free to keep the operand sharded into the downstream
    contraction and emit a partial-sum all-reduce, which is not bitwise
    stable; with it the all-gather moves bytes but never reassociates a
    float sum.  Placement matters: a gather deferred past the
    elementwise tail (activation, bias, softcap) gets the elementwise
    ops sunk across it by the partitioner, parking the all-gather
    adjacent to the next dot/reduction — which XLA then still splits
    into per-shard partial sums.  Adjacent to the producer, every
    consumer sees a plain replicated operand and compiles to the same
    single-device reduction as the unsharded module.

    Two sharded shapes never need a gather at all: a BATCH dim of a dot
    (the kv-head axis of the paged pool in ``decode_attention``) keeps
    each per-row reduction at full extent, and elementwise ops on
    identically-sharded operands (bias adds) are exact per shard.
    """
    mesh = get_serve_mesh()
    if mesh is None:
        return x
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, P()))


# Serving param rules: column-parallel shards only.  Axes listed here are
# all OUTPUT (free) dims of their matmuls — attention/ff/vocab columns —
# so each device computes exact full-precision columns and no collective
# ever reduces.  Everything else (wo, w2, embed, experts, router, ssm
# mixers, norms) replicates; ``serve_pspecs`` falls back to replication
# per-dim when a shape doesn't divide the mesh (``sanitize_pspecs``).
_SERVE_RULES: list[tuple[str, dict[int, tuple]]] = [
    # MoE experts / router / shared-expert stacks ("shared/w1", not the
    # hybrid "shared/attn" block) and every ssm mixer stay replicated:
    # their contractions (expert-combine sum over E, ssm recurrence)
    # would cross shards.  Matched FIRST so the w1/w3/head column rules
    # below cannot reach into these subtrees.
    (r"(experts_|router|shared/w|in_proj|out_proj|conv_|A_log|D$|dt_)",
     {}),
    (r"head.*(mu|rho|w)$", {2: (None, "model")}),     # vocab columns
    (r"(wq|wk|wv)$", {2: (None, "model")}),           # head columns
    (r"(bq|bk|bv)$", {1: ("model",)}),
    (r"(w1|w3)$", {2: (None, "model")}),              # ff columns
    (r".*", {}),
]


def _serve_spec_for(path: str, ndim: int) -> P:
    for pat, table in _SERVE_RULES:
        if re.search(pat, path):
            dims = table.get(ndim)
            if dims is None:
                for nd, d in table.items():
                    if nd < ndim:
                        dims = (None,) * (ndim - nd) + d
                        break
            return P(*dims) if dims is not None else P()
    return P()


def serve_pspecs(params: Any) -> Any:
    """All-gather-only serving-TP PartitionSpec tree for ``params``.

    Same name-based machinery as ``param_pspecs`` but over
    ``_SERVE_RULES``: only column-parallel dims shard, so the sharded
    decode stays bitwise equal to the unsharded reference (see the
    module comment above).  Callers sanitize against the actual mesh
    (``sanitize_pspecs``) before building shardings.
    """

    def spec_leaf(path, leaf):
        if isinstance(leaf, (GaussianVariational, GaussianServed)):
            s = _serve_spec_for(path + "/mu", leaf.mu.ndim)
            return type(leaf)(s, s)  # type: ignore
        return _serve_spec_for(path, getattr(leaf, "ndim", 0))

    def walk(path, node):
        if isinstance(node, (GaussianVariational, GaussianServed)):
            return spec_leaf(path, node)
        if isinstance(node, dict):
            return {k: walk(f"{path}/{k}", v) for k, v in node.items()}
        if isinstance(node, (list, tuple)):
            t = [walk(f"{path}/{i}", v) for i, v in enumerate(node)]
            return type(node)(t)
        return spec_leaf(path, node)

    return walk("", params)


def serve_shardings_for(params: Any, mesh: Mesh) -> Any:
    """NamedSharding tree placing ``params`` for the serving runner."""
    specs = sanitize_pspecs(serve_pspecs(params), params, mesh)
    return jax.tree.map(lambda s: NamedSharding(mesh, s), specs,
                        is_leaf=lambda x: isinstance(x, P))


# ---------------------------------------------------------------------------
# parameter partition rules
# ---------------------------------------------------------------------------

# (path regex, ndim -> PartitionSpec dims for the trailing ndim axes).
# FSDP ('data') is applied on the non-'model' big axis when fsdp=True.
_RULES: list[tuple[str, dict[int, tuple]]] = [
    # embeddings: vocab on model, d_model FSDP.  (A d_model-on-model
    # layout would keep the token gather local, but XLA's gather
    # partitioner emits an invalid dynamic-slice for it (verifier
    # failure, see EXPERIMENTS.md §Perf/grok iteration 3 — refuted);
    # the vocab-sharded gather costs one table AG per microbatch.)
    (r"embed.*table$", {2: ("model", "data")}),
    # bayesian / plain head: d_model REPLICATED (contraction dim), vocab
    # sharded over both axes.  FSDP on the contraction dim turned the
    # head matmul into partial sums + an all-reduce of the full (B, S,
    # vocab) logits (17 GB/microbatch for grok) — §Perf/grok iteration 2.
    (r"head.*(mu|rho|w)$", {2: (None, ("data", "model"))}),
    # attention projections
    (r"(wq|wk|wv)$", {2: ("data", "model")}),
    (r"wo$", {2: ("model", "data")}),
    (r"(bq|bk|bv)$", {1: ("model",)}),
    # dense mlp
    (r"(w1|w3)$", {2: ("data", "model")}),
    (r"w2$", {2: ("model", "data")}),
    # MoE experts, EP layout: experts on model axis, ff FSDP
    (r"experts_ep.*(w1|w3)$", {3: ("model", None, "data")}),
    (r"experts_ep.*w2$", {3: ("model", "data", None)}),
    # MoE experts, TP layout (num_experts < model axis): column-parallel
    # w1/w3 and row-parallel w2 over ff (Megatron), FSDP share on ff.
    # FSDP on the d_model contraction dim forced an all-reduce of the
    # full (E, C, ff) activations per layer — §Perf/grok iteration 1.
    (r"experts_tp.*(w1|w3)$", {3: (None, None, ("data", "model"))}),
    (r"experts_tp.*w2$", {3: (None, ("data", "model"), None)}),
    (r"router.*w$", {2: (None, None)}),
    # mamba2
    (r"in_proj$", {2: ("data", "model")}),
    (r"out_proj$", {2: ("model", "data")}),
    (r"(conv_w|conv_b|A_log|D|dt_bias)$", {1: ("model",), 2: (None, "model")}),
    # norms / scalars: replicated
    (r".*", {}),
]


def _spec_for(path: str, ndim: int, fsdp: bool,
              pod_fsdp: bool = False) -> P:
    def expand(d):
        """'data' -> ('pod','data') when ZeRO spans the pod (DCN) axis."""
        if not pod_fsdp:
            return d
        if d == "data":
            return ("pod", "data")
        if isinstance(d, tuple):
            return tuple(x for e in d for x in
                         (("pod", "data") if e == "data" else (e,)))
        return d

    for pat, table in _RULES:
        if re.search(pat, path):
            dims = table.get(ndim)
            if dims is None:
                # stacked-layer leading axes: match on trailing dims
                for nd, d in table.items():
                    if nd < ndim:
                        dims = (None,) * (ndim - nd) + d
                        break
            if dims is None:
                return P()
            if not fsdp:
                dims = tuple(None if d == "data" else d for d in dims)
            dims = tuple(expand(d) for d in dims)
            return P(*dims)
    return P()


def _flatten_with_paths(tree: Any):
    flat = jax.tree_util.tree_flatten_with_path(
        tree, is_leaf=lambda x: isinstance(x, GaussianVariational))[0]
    out = []
    for kp, leaf in flat:
        path = "/".join(
            str(getattr(k, "key", getattr(k, "idx", k))) for k in kp)
        out.append((path, leaf))
    return out


def param_pspecs(params: Any, fsdp: bool = True,
                 pod_fsdp: bool = False) -> Any:
    """PartitionSpec pytree matching ``params`` (GaussianVariational leaves
    get identical specs for mu and rho)."""

    def spec_leaf(path, leaf):
        if isinstance(leaf, GaussianVariational):
            s = _spec_for(path + "/mu", leaf.mu.ndim, fsdp, pod_fsdp)
            return GaussianVariational(mu=s, rho=s)  # type: ignore
        return _spec_for(path, getattr(leaf, "ndim", 0), fsdp, pod_fsdp)

    paths = {id(leaf): p for p, leaf in _flatten_with_paths(params)}

    def walk(path, node):
        if isinstance(node, GaussianVariational):
            return spec_leaf(path, node)
        if isinstance(node, dict):
            return {k: walk(f"{path}/{k}", v) for k, v in node.items()}
        if isinstance(node, (list, tuple)):
            t = [walk(f"{path}/{i}", v) for i, v in enumerate(node)]
            return type(node)(t)
        return spec_leaf(path, node)

    return walk("", params)


def sanitize_pspecs(specs: Any, shapes: Any, mesh: Mesh) -> Any:
    """Drop mesh axes from any spec dim that does not divide the shape.

    Published vocab sizes are not always mesh-divisible (mamba2 50280,
    seamless 256206); GSPMD handles uneven sharding for constraints but
    ``jit(in_shardings=...)`` requires exact divisibility, so those dims
    fall back to replication.  This keeps the name-rules table clean and
    the fallback decision local to the actual (shape, mesh) pair.
    """

    def fix(spec, shaped):
        if not isinstance(spec, P):
            return spec
        shape = getattr(shaped, "shape", None)
        if shape is None:
            return spec
        dims = list(spec) + [None] * (len(shape) - len(spec))
        out = []
        for size, d in zip(shape, dims):
            if d is None:
                out.append(None)
                continue
            axes = (d,) if isinstance(d, str) else tuple(d)
            n = 1
            for a in axes:
                n *= mesh.shape[a]
            out.append(d if (n and size % n == 0) else None)
        return P(*out)

    return jax.tree.map(
        fix, specs, shapes,
        is_leaf=lambda x: isinstance(x, P))


def shardings_for(params: Any, mesh: Mesh, fsdp: bool = True) -> Any:
    specs = sanitize_pspecs(param_pspecs(params, fsdp), params, mesh)
    return jax.tree.map(lambda s: NamedSharding(mesh, s), specs,
                        is_leaf=lambda x: isinstance(x, P))
