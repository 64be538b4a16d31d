"""Arch-agnostic step builders shared by dryrun / train / serve.

``build_train_step`` returns a pure ``(state, batch) -> (state, metrics)``
function implementing the paper's SVI objective (ELBO = NLL + beta*KL of
the Bayesian head) with gradient accumulation, global-norm clipping and
AdamW.  ``build_prefill_step`` / ``build_decode_step`` wrap the model
zoo's serving API; the decode step emits the paper's uncertainty triplet
(H, SE, MI) per generated token from ``cfg.mc_samples`` MC head draws.

``input_specs`` produces ShapeDtypeStruct stand-ins for every input of a
given (arch x shape-cell), and ``*_pspecs`` the matching PartitionSpecs --
this is everything the multi-pod dry-run lowers against (no allocation).
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from repro.configs.base import ArchConfig, ShapeCell
from repro.core.svi import SVIConfig, elbo_loss
from repro.models import registry as M
from repro.optim import adamw
from repro.launch import mesh as meshlib
from repro.sharding.partition import param_pspecs, sanitize_pspecs


# ---------------------------------------------------------------------------
# train step
# ---------------------------------------------------------------------------

def build_train_step(cfg: ArchConfig, opt_cfg: adamw.AdamWConfig,
                     svi_cfg: Optional[SVIConfig] = None,
                     micro_batches: int = 1, seed: int = 0):
    """(state, batch) -> (state, metrics); state = {params, opt}.

    micro_batches > 1 scans over leading-dim splits of the batch,
    accumulating grads in f32 (bounds activation memory; the MoE dispatch
    buffer scales with the microbatch, DESIGN.md §5).

    ``seed`` roots the SVI noise stream: the per-step key is
    fold_in(PRNGKey(seed), step), so two runs with different seeds draw
    different head samples (and two runs with the same seed replay the
    same stream -- crash/resume stays bit-exact).
    """
    svi = svi_cfg or SVIConfig()

    def loss_fn(params, batch, key, step):
        return elbo_loss(lambda p, b, k: M.nll_loss(p, cfg, b, k),
                         params, batch, key, step, svi)

    grad_fn = jax.value_and_grad(loss_fn, has_aux=True)

    def train_step(state, batch):
        params, opt = state["params"], state["opt"]
        step = opt["step"]
        key = jax.random.fold_in(jax.random.PRNGKey(seed), step)

        if micro_batches == 1:
            (loss, aux), grads = grad_fn(params, batch, key, step)
        else:
            def split(x):
                b = x.shape[0]
                return x.reshape(micro_batches, b // micro_batches,
                                 *x.shape[1:])

            mbatches = jax.tree.map(split, batch)
            g0 = jax.tree.map(
                lambda p: jnp.zeros(p.shape, jnp.float32), params)

            def mb_step(carry, mb):
                g_acc, l_acc, i = carry
                (l, aux), g = grad_fn(params, mb,
                                      jax.random.fold_in(key, i), step)
                g_acc = jax.tree.map(
                    lambda a, b: a + b.astype(jnp.float32), g_acc, g)
                return (g_acc, l_acc + l, i + 1), aux

            (grads, loss, _), auxs = jax.lax.scan(
                mb_step, (g0, jnp.zeros(()), jnp.zeros((), jnp.int32)),
                mbatches)
            inv = 1.0 / micro_batches
            grads = jax.tree.map(lambda g: g * inv, grads)
            loss = loss * inv
            aux = jax.tree.map(lambda a: a.mean(0), auxs)

        new_params, new_opt, om = adamw.apply_updates(
            params, grads, opt, opt_cfg)
        metrics = {"loss": loss, **aux, **om}
        return {"params": new_params, "opt": new_opt}, metrics

    return train_step


# ---------------------------------------------------------------------------
# serving steps
# ---------------------------------------------------------------------------

def build_prefill_step(cfg: ArchConfig, max_len: int):
    def prefill_step(params, batch):
        modality = batch.get("frames", batch.get("prefix_embeds"))
        hidden, cache = M.prefill(params, cfg, batch["tokens"], max_len,
                                  modality)
        return hidden, cache

    return prefill_step


def _decode_base_key(entropy):
    """Base PRNG key of the decode noise stream.

    ``entropy`` (a ``core.entropy.KernelEntropy``) selects the seed-driven
    path: the Bayesian head's MC draws are generated in-kernel on TPU
    (zero HBM entropy operand).  ``None`` keeps the legacy fixed-key
    stream.
    """
    return entropy.key() if entropy is not None else jax.random.PRNGKey(17)


def _folds_step_key(cfg: ArchConfig, entropy) -> bool:
    """Whether the per-step key folds in the global step index.

    The seeded kernel path derives its in-kernel stream from the folded
    key, so it keeps the global-step convention.  Operand mode instead
    passes the UNFOLDED base key down to the models, whose
    ``layers.decode_head_noise`` folds (slot, depth) — making each
    slot's noise a function of its own token position, independent of
    the engine's scheduling (chunked prefill interleavings, pauses,
    chunk sizes).
    """
    return entropy is not None or cfg.head_entropy == "kernel"


def build_decode_step(cfg: ArchConfig, entropy=None):
    """Single uncertain decode step: (params, token, cache, step) ->
    (outputs, cache).  Keys follow the same convention
    ``build_scan_decode`` uses — fold_in(base, step) on the seeded
    kernel path, the raw base key in operand mode (the models fold
    (slot, depth) themselves; see ``_folds_step_key``) — so the two
    paths draw identical noise at identical (slot, depth) sites."""
    base = _decode_base_key(entropy)
    fold = _folds_step_key(cfg, entropy)

    def decode_step(params, token, cache, step):
        key = jax.random.fold_in(base, step) if fold else base
        return M.decode_step(params, cfg, token, cache, key)

    return decode_step


def build_scan_decode(cfg: ArchConfig, entropy=None, chunk: int = 8,
                      mi_threshold: float = 0.05,
                      se_threshold: float = 1.0):
    """Chunked on-device decode: ``chunk`` tokens per host round-trip.

    Returns ``scan_decode(params, token, cache, step0, active, flags) ->
    (token, cache, flags, ys)`` where the inner ``jax.lax.scan`` carries
    (token, slot-indexed cache, cumulative per-slot epistemic/aleatoric
    flag counters) and stacks per-step outputs ``ys`` = {token, H, SE,
    MI, p_max, epistemic, aleatoric}, each (chunk, B), plus the family's
    per-step ``counters`` (each (chunk,) int32; moe) where it has them.
    No per-token host sync: the caller transfers ``ys`` once per chunk.

    ``active`` (B,) bool gates the carried counters: only occupied slots
    accumulate, so a pure-device driver can read per-slot flag totals
    without ever syncing ``ys``.  The counters are device telemetry: a
    request finishing mid-chunk keeps counting until the chunk boundary
    (the host can't evict inside the scan), so they upper-bound the
    exact per-request host accounting done from ``ys``.  ``active`` is
    also the decode body's ``rows``: a family that can (moe) spends no
    routed-expert work on the other slots.

    Noise stream under scan: in operand mode the UNFOLDED base key is
    passed down every step and the models fold (slot, depth) into it
    (``layers.decode_head_noise``), so a slot's draws depend only on
    its own token position — scan decode replays the per-step loop's
    stream bit-for-bit at equal (slot, depth) sites regardless of how
    the engine interleaves admissions, chunked prefill, or pauses
    around it.  On the seeded kernel path step t of the chunk uses key
    fold_in(base, step0 + t) -- the global-step convention of
    ``build_decode_step`` -- and the folded key reaches the
    uncertainty-head kernel as an int32 seed whose in-kernel PRNG
    re-mixes it with the grid coordinates, so every (slot, step) site
    owns a distinct replayable stream with zero HBM entropy traffic.

    Per-slot cache depths (``cache['len']``) give per-slot RoPE
    positions, so slots admitted mid-stream decode correctly alongside
    older slots.  A slot's capacity is enforced by the engine at
    admission (prompt + max-new-tokens must fit ``max_len``); writes of
    an over-deep slot would be dropped by the scatter.

    Paged KV: when the cache carries a ``block_table`` (the engine's
    ``--kv-layout paged``), the (slot, logical_pos) -> (block, offset)
    indirection rides through the scan unchanged in the carry — every
    decode step inside the chunk reads/writes the block pool through the
    same table, and the host refreshes the table between chunks as the
    scheduler grants blocks.  The scan itself is layout-agnostic, and
    that includes the read path ``cfg.decode_attn`` selects: the
    block-sparse decode kernel (``--decode-attn kernel``) consumes the
    same carried table and pool leaves per step, so it needs no carry
    change — only the per-step HBM traffic differs (mapped blocks vs
    the full logical span; see kernels/paged_attention.py).
    """
    base = _decode_base_key(entropy)
    fold = _folds_step_key(cfg, entropy)

    def scan_decode(params, token, cache, step0, active, flags):
        def body(carry, t):
            tok, cache, epi, alea = carry
            key = jax.random.fold_in(base, step0 + t) if fold else base
            with jax.named_scope("decode_step"):
                out, cache = M.decode_step(params, cfg, tok, cache, key,
                                           rows=active)
            is_epi = out["MI"] > mi_threshold
            is_alea = (out["SE"] > se_threshold) & ~is_epi
            ys = {"token": out["next_token"], "H": out["H"],
                  "SE": out["SE"], "MI": out["MI"], "p_max": out["p_max"],
                  "epistemic": is_epi, "aleatoric": is_alea}
            if "counters" in out:
                ys["counters"] = out["counters"]
            carry = (out["next_token"], cache,
                     epi + (is_epi & active).astype(jnp.int32),
                     alea + (is_alea & active).astype(jnp.int32))
            return carry, ys

        (token, cache, epi, alea), ys = jax.lax.scan(
            body, (token, cache, flags["epistemic"], flags["aleatoric"]),
            jnp.arange(chunk, dtype=jnp.int32))
        return token, cache, {"epistemic": epi, "aleatoric": alea}, ys

    return scan_decode


# ---------------------------------------------------------------------------
# speculative decoding (draft / verify / commit)
# ---------------------------------------------------------------------------

def build_spec_draft(cfg: ArchConfig, entropy=None, k: int = 4,
                     draft_samples: int = 1):
    """``k``-step draft pass for uncertainty-gated speculative decoding.

    Operand-entropy mode ONLY (the engine validates): the head noise is
    then a pure function of (slot, depth), never of the global step, so
    draft and verify can replay plain decode's stream at equal sites.

    The draft SHARES the full model body: each step runs
    ``M.decode_hidden`` — whose KV/state writes at the slot's pre-step
    depth are bitwise the writes plain decode would do for the same fed
    token — and proposes with a cheap ``draft_samples``-draw head
    (0 = the deterministic mean head).  No separate draft cache exists;
    a rejected suffix leaves junk KV above the rolled-back ``len``,
    which every decode read masks and later writes overwrite.

    Returns ``spec_draft(params, token, cache) -> (token, cache, ys)``
    with ``ys = {token (k, B) proposals, hidden (k, B, d) pre-head
    hiddens}`` plus the post-step recurrent leaves (``ssm``/``conv``)
    stacked for rollback (``build_spec_commit``).
    """
    base = _decode_base_key(entropy)

    def spec_draft(params, token, cache):
        def body(carry, _):
            tok, cache = carry
            depth = cache["len"]
            hidden, cache = M.decode_hidden(params, cfg, tok, cache)
            out = M.head_outputs(params, cfg, hidden, depth, base,
                                 num_samples=draft_samples)
            ys = {"token": out["next_token"], "hidden": hidden}
            for leaf in M.RECURRENT_LEAVES:
                if leaf in cache:
                    ys[leaf] = cache[leaf]
            return (out["next_token"], cache), ys

        (token, cache), ys = jax.lax.scan(body, (token, cache), None,
                                          length=k)
        return token, cache, ys

    return spec_draft


def build_spec_verify(cfg: ArchConfig, entropy=None, k: int = 4,
                      mi_threshold: float = 0.05,
                      se_threshold: float = 1.0):
    """ONE batched full-S-sample verify over the k draft positions.

    ``spec_verify(params, hiddens, lens0)``: ``hiddens`` are the draft
    pass's stacked (k, B, d) pre-head hiddens, ``lens0`` the (B,)
    pre-round depths.  Runs the family's exact uncertain head
    (``M.head_outputs``) vmapped over positions, at depth ``lens0 + j``
    for position j — in operand mode the depth-keyed noise
    (``layers.decode_head_noise`` folds (slot, depth), never the step)
    makes the vmapped head BITWISE identical to k sequential per-step
    heads, so verify output j IS what plain decode would have emitted
    there (tests/test_spec_decode.py).  Also emits the engine's
    epistemic/aleatoric gating flags per position.
    """
    base = _decode_base_key(entropy)

    def spec_verify(params, hiddens, lens0):
        def one(j, h):
            out = M.head_outputs(params, cfg, h, lens0 + j, base)
            is_epi = out["MI"] > mi_threshold
            is_alea = (out["SE"] > se_threshold) & ~is_epi
            return dict(out, epistemic=is_epi, aleatoric=is_alea)

        return jax.vmap(one)(jnp.arange(k, dtype=jnp.int32), hiddens)

    return spec_verify


def build_spec_commit(cfg: ArchConfig):
    """Device-side rollback/commit after a speculative round.

    ``spec_commit(cache, token, mask, new_tok, new_len, states, idx)``:
    ``mask`` (B,) selects the slots keeping spec-round results (active
    participants that did not finish); their carry token and depth are
    pinned to ``new_tok``/``new_len`` (= pre-round len + emitted).  KV
    written above the rolled-back ``len`` needs no cleanup — decode
    attention masks positions >= len and later steps overwrite — but
    the hybrid/ssm RECURRENT state must rewind: ``states`` holds the
    draft scan's stacked (k, L, B, ...) post-step leaves and ``idx``
    (B,) picks index ``emitted - 1`` (the state after the last kept
    step) per slot.  Unmasked slots keep their (junk-advanced) state,
    exactly like inactive slots under a plain scan chunk.
    """
    def spec_commit(cache, token, mask, new_tok, new_len, states, idx):
        token = jnp.where(mask, new_tok, token)
        cache = dict(cache, len=jnp.where(mask, new_len, cache["len"]))
        for leaf in M.RECURRENT_LEAVES:
            if leaf not in cache:
                continue
            st = jnp.moveaxis(states[leaf], 2, 0)          # (B, k, L, ...)
            picked = jax.vmap(lambda s, i: s[i])(st, idx)  # (B, L, ...)
            picked = jnp.moveaxis(picked, 0, 1)            # (L, B, ...)
            keep = mask.reshape((1, -1) + (1,) * (picked.ndim - 2))
            cache[leaf] = jnp.where(
                keep, picked.astype(cache[leaf].dtype), cache[leaf])
        return token, cache

    return spec_commit


# ---------------------------------------------------------------------------
# dry-run input specs + shardings
# ---------------------------------------------------------------------------

def train_state_specs(cfg: ArchConfig, opt_cfg: adamw.AdamWConfig):
    params = M.init_params_shape(cfg)
    opt = jax.eval_shape(lambda p: adamw.init_state(p, opt_cfg), params)
    return {"params": params, "opt": opt}


def input_specs(cfg: ArchConfig, cell: ShapeCell) -> dict:
    """ShapeDtypeStruct stand-ins for every model input of this cell."""
    if cell.kind == "train":
        return {"batch": M.make_batch_specs(cfg, cell.global_batch,
                                            cell.seq_len)}
    if cell.kind == "prefill":
        return {"batch": M.make_batch_specs(cfg, cell.global_batch,
                                            cell.seq_len)}
    # decode: one new token against a seq_len-deep cache
    cache = jax.eval_shape(
        lambda: M.make_cache(cfg, cell.global_batch, cell.seq_len))
    return {
        "token": jax.ShapeDtypeStruct((cell.global_batch,), jnp.int32),
        "cache": cache,
        "step": jax.ShapeDtypeStruct((), jnp.int32),
    }


def batch_pspecs(mesh: Mesh, specs: dict) -> dict:
    """Data batches shard their leading (global batch) dim over DP axes."""
    out = {}
    for name, s in specs.items():
        dims = ["batch"] + [None] * (len(s.shape) - 1)
        out[name] = meshlib.spec_if(mesh, s.shape, *dims)
    return out


_CACHE_AXES = {
    # leaf-name -> axis roles per trailing dims (L, B, S, H, D) etc.
    "k": ("layer", "batch", "seq", "heads", None),
    "v": ("layer", "batch", "seq", "heads", None),
    "attn_k": ("layer", "batch", "seq", "heads", None),
    "attn_v": ("layer", "batch", "seq", "heads", None),
    "ck": ("layer", "batch", "seq", "heads", None),
    "cv": ("layer", "batch", "seq", "heads", None),
    "conv": ("layer", "batch", None, "model_dim"),
    "ssm": ("layer", "batch", "heads", None, None),
    "len": ("batch",),
}


def cache_pspecs(mesh: Mesh, cache_specs: Any) -> Any:
    """KV/SSM cache shardings with divisibility-aware fallbacks.

    Preference order per leaf: batch over DP axes; heads/model_dim over
    the model axis.  If the batch dim does not divide (long_500k, B=1),
    the sequence dim takes the DP axes instead (sequence parallelism for
    the long-context KV cache).
    """

    def leaf_spec(path, s):
        name = path[-1] if path else ""
        roles = _CACHE_AXES.get(name, (None,) * len(s.shape))
        dims: list = []
        batch_taken = False
        for size, role in zip(s.shape, roles):
            if role == "batch" and meshlib.shardable(
                    size, mesh, meshlib.batch_axes(mesh)):
                dims.append("batch")
                batch_taken = True
            elif role == "seq" and not batch_taken and meshlib.shardable(
                    size, mesh, meshlib.batch_axes(mesh)):
                dims.append("batch")
                batch_taken = True
            elif role in ("heads", "model_dim") and meshlib.shardable(
                    size, mesh, "model"):
                dims.append("model")
            else:
                dims.append(None)
        return meshlib.spec_if(mesh, s.shape, *dims)

    flat = jax.tree_util.tree_flatten_with_path(cache_specs)[0]
    specs = {}
    for kp, leaf in flat:
        path = tuple(str(getattr(k, "key", getattr(k, "idx", k)))
                     for k in kp)
        specs[path] = leaf_spec(path, leaf)

    def walk(path, node):
        if isinstance(node, dict):
            return {k: walk(path + (k,), v) for k, v in node.items()}
        return specs[path]

    return walk((), cache_specs)


def state_pspecs(cfg: ArchConfig, mesh: Mesh, state_specs: dict) -> dict:
    """Partition specs for {params, opt}: params by the name rules, opt
    moments like their params (ZeRO: FSDP axis shards moments too)."""
    fsdp = cfg.fsdp_params and "data" in mesh.axis_names
    pod_fsdp = fsdp and "pod" in mesh.axis_names
    pspec = param_pspecs(state_specs["params"], fsdp=fsdp,
                         pod_fsdp=pod_fsdp)
    pspec = sanitize_pspecs(pspec, state_specs["params"], mesh)
    opt = state_specs["opt"]
    out_opt: dict = {"mu": pspec, "nu": pspec, "step": P()}
    if "error" in opt:
        out_opt["error"] = pspec
    return {"params": pspec, "opt": out_opt}
