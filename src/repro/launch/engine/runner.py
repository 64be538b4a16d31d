"""Device-facing model runner (the serving engine's execution layer).

``ModelRunner`` owns everything that touches jax for one serving run:
the (possibly mesh-sharded) parameters, every compiled callable the
engine dispatches — batch/suffix/chunked prefill, slot writes, CoW
block copies, the scan decode — and the placement of the KV cache and
block tables.  The engine above it is pure host-side policy; this is
the ONLY module where device placement decisions live.

Mesh mode (``mesh=resolve_mesh("1x4")``) shards decode tensor-parallel
over the mesh's ``model`` axis: parameters by the serve-TP rules
(``sharding/partition.serve_shardings_for`` — attention/ff/vocab
COLUMNS sharded, every contraction-feeding weight replicated), the
paged KV pool on its kv-head axis when divisible, and nothing else —
host-side scheduler state never leaves numpy.  Every callable is
dispatched under the serve-mesh context so the forced all-gathers in
``models.layers`` (``partition.gather_rep``) bake into the traced
computation: only column-parallel shards exist, and each is
all-gathered (pure data movement, no re-reduction) before any consumer
contracts over it, so sharded decode replays the unsharded runner's
tokens in operand-entropy mode.  Validated on a forced-host 4-device
CPU mesh by ``launch.engine.mesh_check`` / tests/test_mesh_runner.py,
and on four TPU chips by ``chip_smoke.py --mesh 1x4``.
"""

from __future__ import annotations

import time
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core.bayesian import GaussianServed, GaussianVariational
from repro.core.entropy import KernelEntropy
from repro.launch import mesh as meshlib
from repro.launch import steps as S
from repro.models import registry as M
from repro.sharding.partition import serve_shardings_for, set_serve_mesh

# cache leaves carrying a per-head KV axis (self-attention pool or
# strips, hybrid attention pool, encdec self + cross strips): at -2 of
# a (…, S, Hkv, D) strip, at -3 of an (…, NB, Hkv, BS, D) block pool;
# all other leaves (lens, tables, ssm/conv state) stay replicated
_KV_HEAD_LEAVES = ("k", "v", "attn_k", "attn_v", "ck", "cv")

# Every bf16 rounding the program states is kept.  By default XLA may
# skip a bf16 round trip inside a fusion (computing on in f32), and
# where it may depends on the fusions: an all-gather between a column
# dot and its elementwise consumer forces the bf16 rounding that the
# unsharded program elides, so a mesh runner would drift from the
# unsharded one.
_COMPILER_OPTIONS = {"xla_allow_excess_precision": False}


def served_params(params):
    """``params`` with every ``GaussianVariational`` in its served form.

    All the sigmas are made in one program (scope
    ``bayes_head/prepare``), once, so no decode step recomputes
    softplus(rho).  mu and every other leaf are the caller's arrays,
    neither copied nor donated; a tree with nothing to prepare comes
    back as it is.
    """
    is_q = lambda x: isinstance(x, GaussianVariational)  # noqa: E731
    leaves, tree = jax.tree.flatten(params, is_leaf=is_q)
    qs = [x for x in leaves if is_q(x)]
    if not qs:
        return params

    def sigmas(qs):
        with jax.named_scope("bayes_head/prepare"):
            return [q.served().sigma for q in qs]

    made = iter(jax.jit(sigmas)(qs))
    return jax.tree.unflatten(tree, [
        GaussianServed(mu=x.mu, sigma=next(made)) if is_q(x) else x
        for x in leaves])


def resolve_mesh(spec: Optional[str]) -> Optional[Mesh]:
    """Parse a ``--mesh DxM`` flag ("1x4" → a (data=1, model=4) mesh).

    None/""/"none" mean single-device serving (no mesh).  The shape
    must tile the process's device count exactly (``make_debug_mesh``
    raises otherwise): ``--mesh 1x4`` on one chip is an error, not an
    unsharded run.  A ``1x1`` mesh is legal and shards nothing — every
    serve-TP spec degrades to replication.
    """
    if not spec or spec == "none":
        return None
    parts = spec.lower().split("x")
    if len(parts) != 2 or not all(p.isdigit() for p in parts):
        raise ValueError(f"--mesh wants DxM (e.g. 1x4), got {spec!r}")
    return meshlib.make_debug_mesh((int(parts[0]), int(parts[1])),
                                   ("data", "model"))


class ModelRunner:
    """Compiled callables + device placement for one engine config.

    Receives POLICY-RESOLVED knobs from ``ServeEngine`` (kv_layout
    after the family fallback, cfg with ``decode_attn`` already
    substituted, prefill_mode after the support gate) and builds the
    jitted callables the engine's chunk loop dispatches.  With a
    ``mesh``, parameters are placed by the serve-TP rules, the cache's
    KV leaves are sharded on their head axis (replicating when the
    head count doesn't divide the model axis), and every callable runs
    under the serve-mesh context so the layer-level all-gather
    constraints bake in at trace time.  The Bayesian head is served in
    the form its kernel reads (``served_params``), made before any
    placement.
    """

    def __init__(self, params, cfg, *, max_len: int, chunk: int,
                 entropy: Optional[KernelEntropy],
                 mi_threshold: float, se_threshold: float,
                 kv_layout: str, kv_block: int, kv_blocks: int,
                 prefix_cache: bool, prefill_mode: str,
                 mesh: Optional[Mesh] = None,
                 spec_decode: bool = False, spec_k: int = 4,
                 spec_draft_s: int = 1):
        self.cfg = cfg
        self.max_len = max_len
        self.kv_layout = kv_layout
        self.kv_block = kv_block
        self.kv_blocks = kv_blocks
        self.mesh = mesh
        params = served_params(params)
        self.params = params if mesh is None else jax.device_put(
            params, serve_shardings_for(params, mesh))
        paged = kv_layout == "paged"
        if paged:
            # paged prefill builds a minimal prompt-length strip (the
            # scatter pages it out token by token); dense keeps the
            # engine-wide max_len strip its slot write needs
            self._prefill = self._jit(
                lambda p, t, m: M.prefill(p, cfg, t, t.shape[1], m))
            self._write = self._jit(
                lambda c, slot, sub, row: M.write_slot(cfg, c, slot, sub,
                                                       row),
                donate_argnums=(0,))
        else:
            self._prefill = self._jit(
                lambda p, t, m: M.prefill(p, cfg, t, max_len, m))
            self._write = self._jit(
                lambda c, slot, sub: M.write_slot(cfg, c, slot, sub),
                donate_argnums=(0,))
        self._chunk_fn = self._chunk_first = None
        if prefill_mode == "chunked":
            # one jitted walker per family kwarg shape; span (the whole
            # prompt's static attention-reduction extent) is static, so
            # compiles scale with distinct (chunk, span) pairs — bucketed
            # prompts collapse most of those (see prefill_compiles)
            if cfg.family == "hybrid":
                self._chunk_fn = self._jit(
                    lambda p, t, c, s, o, n, st, span, fin:
                    M.prefill_chunk(p, cfg, t, c, s, o, n, span,
                                    state=st, finalize=fin),
                    static_argnums=(7, 8), donate_argnums=(2,))
            else:
                if cfg.family == "encdec":
                    self._chunk_first = self._jit(
                        lambda p, t, c, s, o, n, fr, span: M.prefill_chunk(
                            p, cfg, t, c, s, o, n, span, frames=fr),
                        static_argnums=(7,), donate_argnums=(2,))
                # -> (cache, the chunk's counters: {} outside moe)
                self._chunk_fn = self._jit(
                    lambda p, t, c, s, o, n, span: M.take_counters(
                        M.prefill_chunk(p, cfg, t, c, s, o, n, span)),
                    static_argnums=(6,), donate_argnums=(2,))
        self._suffix = self._copy = None
        if prefix_cache:
            # prefix-hit fast paths.  _suffix gathers the slot's cached
            # prefix strips from the pool, prefills ONLY the uncached
            # suffix against them (bit-exact vs the cold flash-attention
            # path; see layers.apply_attention_suffix) and scatters the
            # suffix KV at its logical offset.  _copy is the device-side
            # CoW block duplicate.
            def suffix_fn(p, c, slot, row, toks, plen):
                # gather only the blocks the hit spans (plen is static),
                # not the full table-width logical strip
                nb = -(-plen // kv_block)
                strips = {
                    n: jax.vmap(lambda pool: M.paged_gather(
                        pool, row[None, :nb]))(c[n])
                    for n in M.PAGED_KV_LEAVES if n in c}
                _, sub = M.prefill_suffix(p, cfg, toks, strips, plen)
                return M.write_slot(cfg, c, slot, sub, row, offset=plen)

            # plen is STATIC: bit-exactness vs the cold path needs the
            # suffix attention to reduce over exactly prefix + suffix
            # keys, so each (hit, suffix) length pair compiles once
            self._suffix = self._jit(suffix_fn, static_argnums=(5,),
                                     donate_argnums=(1,))
            self._copy = self._jit(
                lambda c, src, dst: M.copy_block(cfg, c, src, dst),
                donate_argnums=(0,))
        # depth pinning: bucketed/suffix/chunked prefill all write
        # strips wider than the true prompt, then fix the slot's len to
        # the real token count (full-prompt prefix hits need nothing
        # else at all)
        self._set_len = self._jit(
            lambda c, slot, n: dict(c, len=c["len"].at[slot].set(n)),
            donate_argnums=(0,))
        self._scan = self._jit(
            S.build_scan_decode(cfg, entropy=entropy, chunk=chunk,
                                mi_threshold=mi_threshold,
                                se_threshold=se_threshold),
            donate_argnums=(2,))
        self._draft = self._verify = self._spec_commit = None
        # per-k jit cache for speculative draft/verify: k is STATIC in
        # both builders, so the adaptive-k engine asks ``spec_fns(k)``
        # for each depth it visits and pays one trace per distinct k
        # (the commit is k-independent — it retraces per stacked shape)
        self._entropy = entropy
        self._mi_threshold = mi_threshold
        self._se_threshold = se_threshold
        self._spec_draft_s = spec_draft_s
        self._spec_k_fns: dict[int, tuple] = {}
        if spec_decode:
            # speculative round: k-step shared-body draft (cache donated
            # forward like the scan's), ONE vmapped full-S verify over
            # the stacked hiddens, then the masked rollback/commit
            self._draft, self._verify = self.spec_fns(spec_k)
            self._spec_commit = self._jit(S.build_spec_commit(cfg),
                                          donate_argnums=(0,))

    def spec_fns(self, k: int):
        """(draft, verify) compiled callables for draft depth ``k``,
        built lazily and cached per k — the adaptive-k rounds walk
        depths between ``--spec-k-min`` and ``--spec-k-max`` and reuse
        each depth's jits after its first visit."""
        if k not in self._spec_k_fns:
            draft = self._jit(
                S.build_spec_draft(self.cfg, entropy=self._entropy, k=k,
                                   draft_samples=self._spec_draft_s),
                donate_argnums=(2,))
            verify = self._jit(
                S.build_spec_verify(self.cfg, entropy=self._entropy, k=k,
                                    mi_threshold=self._mi_threshold,
                                    se_threshold=self._se_threshold))
            self._spec_k_fns[k] = (draft, verify)
        return self._spec_k_fns[k]

    def _jit(self, fn, **kw):
        """jit + serve-mesh context around every dispatch: tracing
        happens inside the wrapped call, so the ``gather_rep`` seams in
        models.layers see the mesh and bake their all-gather
        constraints into the compiled computation.  The context is
        cleared on exit so co-resident training code (whose sharding
        uses the separate train-mesh context) is never affected."""
        jitted = jax.jit(fn, compiler_options=_COMPILER_OPTIONS, **kw)
        if self.mesh is None:
            return jitted
        mesh = self.mesh

        def dispatch(*args):
            set_serve_mesh(mesh)
            try:
                return jitted(*args)
            finally:
                set_serve_mesh(None)
        return dispatch

    def make_cache(self, num_slots: int):
        """Build (and in mesh mode, place) the engine's KV cache: only
        the per-head KV leaves shard (heads axis over ``model``, with
        the usual divisibility fallback to replication); slot lens,
        block tables and recurrent ssm/conv state replicate — the host
        scheduler keeps mutating its numpy copies obliviously."""
        cache = M.make_cache(self.cfg, num_slots, self.max_len,
                             layout=self.kv_layout,
                             kv_block=self.kv_block,
                             num_blocks=self.kv_blocks)
        if self.mesh is None:
            return cache
        shardings = {}
        for name, leaf in cache.items():
            if name in _KV_HEAD_LEAVES:
                dims = [None] * leaf.ndim
                pooled = self.kv_layout == "paged" \
                    and name in M.PAGED_KV_LEAVES
                dims[-3 if pooled else -2] = "model"
                spec = meshlib.spec_if(self.mesh, leaf.shape, *dims)
            else:
                spec = P()
            shardings[name] = NamedSharding(self.mesh, spec)
        return jax.device_put(cache, shardings)

    def place_table(self, table: np.ndarray) -> jax.Array:
        """Upload a host block table; replicated across the mesh so
        every shard of the pool gathers through identical indices."""
        if self.mesh is None:
            return jnp.asarray(table)
        return jax.device_put(jnp.asarray(table),
                              NamedSharding(self.mesh, P()))

    def put_replicated(self, x) -> jax.Array:
        """Replicate a small carry array (tokens / active mask / flag
        counters) across the mesh; identity off-mesh."""
        if self.mesh is None:
            return x
        return jax.device_put(x, NamedSharding(self.mesh, P()))


# ---------------------------------------------------------------------------
# per-token reference loop (parity oracle + benchmark baseline)
# ---------------------------------------------------------------------------

def decode_loop_reference(params, cfg, tokens, gen_len: int, *,
                          entropy: Optional[KernelEntropy] = None,
                          max_len: Optional[int] = None,
                          modality=None, decode_fn=None) -> dict:
    """The pre-engine decode driver: one jitted step + one host sync per
    token over a statically batched prompt matrix.  Scan decode must
    reproduce this loop's token stream exactly in operand-entropy mode
    (same fold_in(base, global_step) noise; tested in test_serve.py).

    ``decode_fn`` lets benchmarks pass a pre-compiled step so the timed
    loop measures steady-state dispatch, not compilation.
    """
    tokens = jnp.asarray(tokens)
    B, P_ = tokens.shape
    max_len = max_len or P_ + gen_len
    _, cache = M.prefill(params, cfg, tokens, max_len, modality)
    decode = decode_fn or jax.jit(S.build_decode_step(cfg, entropy=entropy),
                                  donate_argnums=(2,))
    tok = tokens[:, -1]
    rows = {"token": [], "H": [], "SE": [], "MI": [], "p_max": []}
    t0 = time.perf_counter()
    for i in range(gen_len):
        out, cache = decode(params, tok, cache, jnp.asarray(i, jnp.int32))
        tok = out["next_token"]
        rows["token"].append(np.asarray(tok))        # per-token sync
        for k in ("H", "SE", "MI", "p_max"):
            rows[k].append(np.asarray(out[k]))
    decode_s = time.perf_counter() - t0
    return {name: np.stack(vals) for name, vals in rows.items()} | {
        "decode_s": decode_s,
        "decode_tok_per_s": gen_len * B / max(decode_s, 1e-9),
    }
