"""Model dispatch: ArchConfig.family -> implementation module.

Uniform API across families:
    init_params(key, cfg) -> params
    nll_loss(params, cfg, batch, key) -> (nll, aux)
    make_cache(cfg, batch, max_len, layout=..., ...) -> cache
    prefill(params, cfg, tokens, max_len, **modality) -> (hidden, cache)
    decode_step(params, cfg, token, cache, key) -> (outputs, cache)
    write_slot(cfg, cache, slot, sub, block_row=None) -> cache

Caches are slot-indexed: every leaf carries the slot (batch) axis and
``cache["len"]`` is a per-slot (batch,) depth vector, so a continuous-
batching engine can admit/evict requests into individual slots while the
others keep decoding.  Under ``layout='paged'`` the self-attention KV
leaves (``PAGED_KV_LEAVES``) instead live in a global pool of fixed-size
blocks addressed through a per-slot ``block_table`` (-1 = unmapped);
``layout='dense'`` remains the bit-exact reference layout.

Paged decode reads are selected by ``cfg.decode_attn``, which every
family threads to ``layers.apply_attention`` untouched: ``'gather'``
(reference) materializes the logical span, ``'kernel'`` runs the
block-sparse Pallas kernel over the pool (kernels/paged_attention.py)
— no per-family code, the dispatch lives in the shared attention.

``batch_spec``/``cache_spec``/modality stubs are centralized here so the
launcher's ``input_specs`` stays arch-agnostic.
"""

from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.models import encdec, hybrid, moe, ssm, transformer
from repro.models.layers import (copy_block as _copy_block_1l,
                                 mapped_span,  # noqa: F401 (re-export)
                                 paged_gather,  # noqa: F401 (re-export)
                                 paged_scatter,
                                 paged_table_width)


def module_for(cfg: ArchConfig):
    return {
        "dense": transformer,
        "vlm": transformer,
        "audio": encdec,
        "encdec": encdec,
        "moe": moe,
        "ssm": ssm,
        "hybrid": hybrid,
    }[cfg.family]


def init_params(key, cfg: ArchConfig):
    return module_for(cfg).init_params(key, cfg)


def init_params_shape(cfg: ArchConfig):
    """Shape-only params (no allocation) for dry-run lowering."""
    return jax.eval_shape(
        lambda: module_for(cfg).init_params(jax.random.key(0), cfg))


def make_batch_specs(cfg: ArchConfig, batch: int, seq: int) -> dict:
    """ShapeDtypeStruct training batch for this family."""
    tok = jax.ShapeDtypeStruct((batch, seq), jnp.int32)
    specs = {"tokens": tok, "labels": tok}
    if cfg.family == "encdec":
        from repro.models.encdec import ENC_LEN
        specs["frames"] = jax.ShapeDtypeStruct(
            (batch, ENC_LEN, cfg.d_model), jnp.float32)
    if cfg.family == "vlm":
        specs["prefix_embeds"] = jax.ShapeDtypeStruct(
            (batch, cfg.num_prefix_embeds, cfg.d_model), jnp.float32)
    return specs


def make_batch(key, cfg: ArchConfig, batch: int, seq: int) -> dict:
    """Concrete random batch matching make_batch_specs."""
    specs = make_batch_specs(cfg, batch, seq)
    out = {}
    for name, s in specs.items():
        key, k = jax.random.split(key)
        if jnp.issubdtype(s.dtype, jnp.integer):
            out[name] = jax.random.randint(k, s.shape, 0, cfg.vocab_size,
                                           s.dtype)
        else:
            out[name] = jax.random.normal(k, s.shape, s.dtype) * 0.02
    return out


def nll_loss(params, cfg: ArchConfig, batch: dict, key):
    return module_for(cfg).nll_loss(params, cfg, batch, key)


def supports_paged(cfg: ArchConfig) -> bool:
    """Whether this family has KV strips that benefit from paging.

    Pure-SSM caches are O(1) in context (recurrent state + conv tail),
    so the paged layout is a no-op there and the engine keeps the dense
    layout; every attention-bearing family (dense, vlm, moe, hybrid,
    encdec, audio) pages its self-attention KV.
    """
    return cfg.family != "ssm"


def supports_prompt_padding(cfg: ArchConfig) -> bool:
    """Whether a prompt may be right-padded with junk tokens at prefill.

    Attention-only prompt state is positional: junk rows past the true
    prompt end are causally invisible to real rows, masked below
    ``len`` at decode, and overwritten by the first decode writes — so
    the engine can bucket prompt lengths to ``kv_block`` multiples and
    bound prefill recompiles.  Recurrent families (``ssm``, ``hybrid``)
    would fold the junk tokens into their SSM/conv state, so they keep
    exact-length prefill.
    """
    return cfg.family in ("dense", "vlm", "moe", "encdec", "audio")


def supports_chunked_prefill(cfg: ArchConfig) -> bool:
    """Whether the family implements ``prefill_chunk`` (incremental
    prompt processing against the paged pool).  Requires the paged
    layout — chunks scatter straight into pool blocks.  ``vlm``/
    ``audio`` prompts splice modality embeddings into mid-prompt
    positions, which the chunk walker does not slice yet; they fall
    back to batch prefill.
    """
    return supports_paged(cfg) and cfg.family in ("dense", "moe",
                                                  "hybrid", "encdec")


def prefill_chunk(params, cfg: ArchConfig, tokens, cache, slot, offset,
                  new_len, span: int, **kw):
    """One incremental prefill chunk for ``slot`` (paged layout only).

    tokens: (1, S) chunk at absolute positions ``offset + [0, S)``;
    ``span``: static attention-reduction extent of the whole prompt.
    Family-specific keywords: ``state``/``finalize`` (hybrid, returns
    ``(cache, new_state)``), ``frames`` (encdec first chunk).  A family
    may return counters in the cache under ``COUNTERS``
    (``take_counters``).  See ``supports_chunked_prefill`` for the
    dispatch gate."""
    if not supports_chunked_prefill(cfg):
        raise ValueError(f"family {cfg.family!r} has no chunked prefill")
    return module_for(cfg).prefill_chunk(params, cfg, tokens, cache,
                                         slot, offset, new_len, span,
                                         **kw)


def supports_prefix_cache(cfg: ArchConfig) -> bool:
    """Whether prompt KV can be shared across requests by token prefix.

    Sound only when per-position prompt state is a pure function of the
    token prefix: ``vlm``/``encdec``/``audio`` mix non-token modality
    inputs (prefix embeds, encoder frames) into the cache, and ``ssm``/
    ``hybrid`` carry recurrent state that a KV-block prefix cannot
    reconstruct.  That leaves the token-only families: ``dense`` and
    ``moe``, whose dropless expert layer gives a token the same experts
    whatever else shares its batch.
    """
    return cfg.family in ("dense", "moe")


def prefill_suffix(params, cfg: ArchConfig, tokens, prefix_kv: dict,
                   prefix_len):
    """Prefill only the uncached suffix of a prefix-cache hit; see the
    family implementations (``supports_prefix_cache`` gates dispatch)."""
    if not supports_prefix_cache(cfg):
        raise ValueError(f"family {cfg.family!r} cannot prefix-share "
                         "prompt KV")
    return module_for(cfg).prefill_suffix(params, cfg, tokens, prefix_kv,
                                          prefix_len)


def make_cache(cfg: ArchConfig, batch: int, max_len: int,
               layout: str = "dense", kv_block: int = 16,
               num_blocks: int = 0):
    """``layout='dense'``: one contiguous max_len strip per slot (the
    reference layout).  ``layout='paged'``: self-attention KV lives in a
    global pool of ``num_blocks`` x ``kv_block``-token blocks behind a
    per-slot ``block_table`` (see ``launch.serve.BlockAllocator``)."""
    if layout == "paged" and supports_paged(cfg):
        return module_for(cfg).make_cache(cfg, batch, max_len,
                                          layout="paged",
                                          kv_block=kv_block,
                                          num_blocks=num_blocks)
    return module_for(cfg).make_cache(cfg, batch, max_len)


def prefill(params, cfg: ArchConfig, tokens, max_len: int,
            modality: Any = None):
    mod = module_for(cfg)
    if cfg.family == "encdec":
        return mod.prefill(params, cfg, tokens, max_len, frames=modality)
    if cfg.family == "vlm":
        return mod.prefill(params, cfg, tokens, max_len,
                           prefix_embeds=modality)
    return mod.prefill(params, cfg, tokens, max_len)


# a family's decode body or prefill chunk may return small per-call
# int32 counters in its new cache under this key (moe: ``moe.rows``,
# ``moe.experts_hit``); ``take_counters`` moves them out, so the cache
# keeps the structure a scan carries
COUNTERS = "counters"

# families whose decode body takes ``rows``: the slots whose tokens are
# real, so that the others cost no routed-expert work
_ROW_MASKED = ("moe",)


def take_counters(cache) -> tuple[Any, dict]:
    """(cache without ``COUNTERS``, the counters or {})."""
    if not isinstance(cache, dict) or COUNTERS not in cache:
        return cache, {}
    cache = dict(cache)
    return cache, cache.pop(COUNTERS)


def _rows_kw(cfg: ArchConfig, rows) -> dict:
    return {"rows": rows} if rows is not None \
        and cfg.family in _ROW_MASKED else {}


def decode_step(params, cfg: ArchConfig, token, cache, key, rows=None):
    """One decode step: ``(outputs, new_cache)``.  ``rows`` (B,) bool,
    optional, marks the slots whose tokens are real (see
    ``_ROW_MASKED``); a family's counters come back as
    ``outputs["counters"]``."""
    out, new_cache = module_for(cfg).decode_step(params, cfg, token,
                                                 cache, key,
                                                 **_rows_kw(cfg, rows))
    new_cache, counters = take_counters(new_cache)
    if counters:
        out = dict(out, counters=counters)
    # reattach the paged block table centrally so the scan carry keeps
    # its structure without every family hand-copying it
    if isinstance(cache, dict) and "block_table" in cache:
        new_cache.setdefault("block_table", cache["block_table"])
    return out, new_cache


def decode_hidden(params, cfg: ArchConfig, token, cache):
    """The KV-writing decode BODY alone: embed -> blocks -> final norm,
    returning ``(hidden, new_cache)`` with the step's cache writes done
    and ``len`` advanced, but NO head.  ``decode_step`` is exactly this
    followed by ``head_outputs`` at the pre-step depths — the split the
    speculative-decoding draft/verify passes build on (the draft shares
    the body, so its KV writes are bitwise plain decode's; the verify
    re-runs only the head over the stacked draft hiddens)."""
    hidden, new_cache = module_for(cfg).decode_hidden(params, cfg, token,
                                                      cache)
    new_cache, _ = take_counters(new_cache)
    if isinstance(cache, dict) and "block_table" in cache:
        new_cache.setdefault("block_table", cache["block_table"])
    return hidden, new_cache


def head_outputs(params, cfg: ArchConfig, hidden, cache_len, key,
                 num_samples=None):
    """The family-shared uncertain head (see models.uncertain_head):
    {next_token, H, SE, MI, p_max} from ``num_samples`` (default
    ``cfg.mc_samples``) LRT draws over ``hidden`` at depth
    ``cache_len``."""
    from repro.models.uncertain_head import head_outputs as _head
    return _head(params, cfg, hidden, cache_len, key,
                 num_samples=num_samples)


def supports_spec_decode(cfg: ArchConfig) -> bool:
    """Whether uncertainty-gated speculative decoding serves this family.

    Every family exposes the ``decode_hidden``/``head_outputs`` split,
    so all of them speculate.  Losslessness rests on per-slot decode
    state being independent across slots given the fed tokens, which
    holds in every family: MoE's expert layer is dropless, so a token's
    experts never depend on the other slots (the bitwise parity harness,
    tests/test_spec_decode.py, checks it).
    """
    return True


# cache leaves that live in the global block pool under the paged layout
PAGED_KV_LEAVES = ("k", "v", "attn_k", "attn_v")

# per-slot recurrent state leaves (hybrid/ssm) that speculative-decode
# rollback must restore to the accepted step (KV pool junk above the
# rolled-back ``len`` is masked instead; see steps.build_spec_commit)
RECURRENT_LEAVES = ("ssm", "conv")


def kv_bytes(cache) -> int:
    """Total allocated bytes of the self-attention KV leaves of a cache
    (dense: the per-slot strips; paged: the whole block pool).  The
    serving engine divides by the pool's block count to price one block.
    """
    return sum(cache[n].size * cache[n].dtype.itemsize
               for n in PAGED_KV_LEAVES if n in cache)


def copy_block(cfg: ArchConfig, cache, src, dst):
    """Copy-on-write: duplicate physical block ``src`` into ``dst``
    across every paged KV leaf (vmapped over the layer axis).  The
    caller swaps the slot's table entry to ``dst`` host-side before the
    divergent write; non-pool leaves pass through untouched."""
    out = dict(cache)
    for name in PAGED_KV_LEAVES:
        if name in cache:
            out[name] = jax.vmap(
                lambda pool: _copy_block_1l(pool, src, dst))(cache[name])
    return out


def write_slot(cfg: ArchConfig, cache, slot, sub, block_row=None,
               offset=None):
    """Write a batch-1 request cache ``sub`` into decode slot ``slot``.

    Family-agnostic by layout convention: every cache leaf carries the
    slot (batch) axis at position 1 -- (L, B, ...) KV stacks, SSM/conv
    states, cross-attention KV -- except the per-slot ``len`` vector,
    which carries it at position 0.  ``slot`` may be traced (one compile
    serves every slot).

    Paged layout (``cache`` has a ``block_table``): ``block_row`` is the
    slot's (MB,) physical-block row from the host allocator; the
    ``PAGED_KV_LEAVES`` of ``sub`` (dense batch-1 strips from prefill)
    are scattered from logical position ``offset`` (default 0; a
    prefix-cache hit passes the matched prefix length so the suffix
    strip lands after the shared blocks) through the shared
    ``layers.paged_scatter`` indirection (vmapped over the layer axis),
    the remaining leaves take the dense slot write, and the
    slot's table row is installed.  Strip tokens past the mapped blocks
    drop (mode='drop'), so a strip padded beyond the prompt is safe.
    """
    if not isinstance(cache, dict) or "block_table" not in cache:
        def w(c, s):
            s = s.astype(c.dtype)
            if c.ndim == 1:                  # the (B,) len vector
                return jax.lax.dynamic_update_slice(c, s, (slot,))
            start = (0, slot) + (0,) * (c.ndim - 2)
            return jax.lax.dynamic_update_slice(c, s, start)

        return jax.tree.map(w, cache, sub)

    if block_row is None:
        raise ValueError("paged cache write needs the slot's block_row")
    lens0 = jnp.zeros((1,), jnp.int32) if offset is None else \
        jnp.reshape(jnp.asarray(offset, jnp.int32), (1,))
    out = {}
    for name, c in cache.items():
        if name == "block_table":
            out[name] = jax.lax.dynamic_update_slice(
                c, block_row[None].astype(c.dtype), (slot, jnp.int32(0)))
        elif name in PAGED_KV_LEAVES:
            strip = sub[name].astype(c.dtype)      # (A, 1, S, Hkv, hd)
            table = block_row[None].astype(jnp.int32)
            out[name] = jax.vmap(
                lambda pool, new: paged_scatter(pool, table, lens0, new)
            )(c, strip)
        elif c.ndim == 1:                          # the (B,) len vector
            out[name] = jax.lax.dynamic_update_slice(
                c, sub[name].astype(c.dtype), (slot,))
        else:
            s = sub[name].astype(c.dtype)
            start = (0, slot) + (0,) * (c.ndim - 2)
            out[name] = jax.lax.dynamic_update_slice(c, s, start)
    return out
