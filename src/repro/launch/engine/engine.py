"""The continuous-batching serving engine (policy + chunk loop).

``ServeEngine`` is the top of the layered engine package: it resolves
the serving POLICY (layout/kernel/prefill-mode fallbacks per family
support), then drives the per-chunk loop — admit via
``scheduler.SlotScheduler``, prefill through ``runner.ModelRunner``'s
compiled callables, grant/preempt against ``block_pool``, harvest the
scan outputs, account everything into ``stats.ServeStats``.  Nothing
here touches device placement (runner) or block accounting (scheduler/
pool) directly; the split is the module map in docs/architecture.md.
"""

from __future__ import annotations

import collections
import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.entropy import KernelEntropy
from repro.kernels.paged_attention import kv_blocks_read
from repro.launch.engine.block_pool import BlockAllocator
from repro.launch.engine.escalate import EscalationLane
from repro.launch.engine.policy import SchedPolicy, get_policy
from repro.launch.engine.runner import ModelRunner
from repro.launch.engine.scheduler import Request, SlotScheduler
from repro.launch.engine.stats import ServeStats
from repro.models import registry as M


class ServeEngine:
    """Continuous-batching scan-decoded uncertainty engine.

    ``num_slots`` concurrent decode slots over one slot-indexed KV cache
    of depth ``max_len``; ``chunk`` tokens decoded per device call.
    ``entropy`` (KernelEntropy) selects the seeded head-draw stream
    (in-kernel on TPU); None keeps the legacy operand stream.

    ``kv_layout`` picks the cache layout.  Both layouts bound a request
    to ``prompt + gen <= max_len`` (block tables span ``max_len``
    logical tokens).  ``'dense'`` — the bit-exact reference — gives
    every slot one contiguous ``max_len`` KV strip, so mixed-length
    traffic pays full padding waste.  ``'paged'`` backs the self-attention KV
    with a global pool of ``kv_blocks`` blocks of ``kv_block`` tokens:
    admission reserves a request's whole-lifetime block budget ("are
    enough blocks free", deferring instead of crashing when the pool is
    exhausted), decode blocks are granted chunk by chunk, and eviction
    returns everything — KV bytes in use track the tokens actually
    resident instead of ``num_slots * max_len``.  Paged decode is
    bit-exact against dense when ``max_len`` is a ``kv_block`` multiple
    (equal logical spans; tested in tests/test_paged_kv.py).  Families
    without KV strips (ssm) fall back to dense.

    ``prefix_cache=True`` (paged only) puts a host-side radix tree
    (``launch.prefix_cache.RadixPrefixCache``) over the block pool:
    admission walks the tree, maps the longest cached token prefix's
    blocks into the slot's table read-only (refcounted sharing), and
    prefill runs only on the uncached suffix — a full-prompt hit costs
    zero prefill compute.  A token-granular partial match into a shared
    block triggers copy-on-write (device-side block duplicate + table
    swap) before the slot writes at the divergence point.  Evicted
    requests donate their prompt blocks to the tree; cached-but-
    unreferenced blocks are LRU-evicted under pool pressure.  Restricted
    to families whose prompt KV is a pure function of token IDs
    (``registry.supports_prefix_cache``); hit decode is bit-exact vs the
    cold path under the same admission schedule (tested in
    tests/test_prefix_cache.py).

    ``decode_attn`` (paged only) selects the decode-attention read path:
    ``'gather'`` — the bit-exact reference — materializes each slot's
    full ``MB*BS`` logical strip per layer per step, so decode HBM
    traffic is identical to dense strips; ``'kernel'`` runs the
    block-sparse Pallas kernel (``kernels/paged_attention.py``) that
    reads only mapped blocks under each slot's depth straight from the
    pool, bit-exact vs gather in operand/interpret mode (tested in
    tests/test_paged_attention.py).  ``trace_every`` downsamples the
    per-chunk scheduler/pool snapshot (1 = every chunk) so long runs
    don't grow host memory linearly in chunks decoded.

    ``mesh`` (a ``Mesh`` from ``runner.resolve_mesh``) serves decode
    tensor-parallel over the mesh's ``model`` axis — the runner shards
    parameters and the paged KV pool, scheduler state stays host-side,
    and decode is bit-exact vs the unsharded engine in operand-entropy
    mode (tests/test_mesh_runner.py).  The block-sparse decode kernel
    does not partition under GSPMD, so a multi-device mesh silently
    keeps the gather read path, like the family fallbacks above.
    """

    def __init__(self, params, cfg, *, num_slots: int, max_len: int,
                 chunk: int = 8, entropy: Optional[KernelEntropy] = None,
                 mi_threshold: float = 0.05, se_threshold: float = 1.0,
                 eos_id: Optional[int] = None, kv_layout: str = "dense",
                 kv_block: int = 16, kv_blocks: Optional[int] = None,
                 prefix_cache: bool = False, decode_attn: str = "gather",
                 prefill_mode: str = "batch", prefill_chunk: int = 32,
                 trace_every: int = 1, mesh=None,
                 spec_decode: bool = False, spec_k: int = 4,
                 spec_mi_threshold: Optional[float] = None,
                 spec_draft_s: int = 1,
                 spec_k_min: Optional[int] = None,
                 spec_k_max: Optional[int] = None,
                 policy="fifo",
                 escalate_mi: Optional[float] = None,
                 escalate_s: Optional[int] = None):
        if kv_layout not in ("dense", "paged"):
            raise ValueError(f"unknown kv_layout {kv_layout!r}")
        if kv_block < 1:
            raise ValueError(f"kv_block must be >= 1, got {kv_block}")
        if prefix_cache and kv_layout != "paged":
            raise ValueError("prefix cache shares blocks of the paged "
                             "pool; run with kv_layout='paged'")
        if decode_attn not in ("gather", "kernel"):
            raise ValueError(f"unknown decode_attn {decode_attn!r}")
        if decode_attn == "kernel" and kv_layout != "paged":
            raise ValueError("the block-sparse decode kernel reads "
                             "through the paged block table; run with "
                             "kv_layout='paged'")
        if prefill_mode not in ("batch", "chunked"):
            raise ValueError(f"unknown prefill_mode {prefill_mode!r}")
        if prefill_mode == "chunked" and kv_layout != "paged":
            raise ValueError("chunked prefill scatters prompt chunks "
                             "into pool blocks; run with "
                             "kv_layout='paged'")
        if prefill_chunk < 1:
            raise ValueError(f"prefill_chunk must be >= 1, got "
                             f"{prefill_chunk}")
        if trace_every < 1:
            raise ValueError(f"trace_every must be >= 1, got {trace_every}")
        if spec_decode:
            if spec_k < 1:
                raise ValueError(f"spec_k must be >= 1, got {spec_k}")
            if spec_draft_s < 0:
                raise ValueError(
                    f"spec_draft_s must be >= 0, got {spec_draft_s}")
            # losslessness hinges on the head noise being a pure function
            # of (slot, depth): the seeded/kernel streams fold the GLOBAL
            # step into the key, so a verify replayed at the same depth
            # but a different step could not reproduce plain decode's draw
            if entropy is not None or cfg.head_entropy == "kernel":
                raise ValueError(
                    "speculative decoding requires the operand entropy "
                    "mode (depth-keyed head noise); the seeded/kernel "
                    "streams fold the global step and cannot replay "
                    "plain decode's draws at draft positions")
            if not M.supports_spec_decode(cfg):
                raise ValueError(
                    f"family {cfg.family!r} does not support speculative "
                    "decoding")
        self.spec_decode = spec_decode
        self.spec_k = spec_k
        self.spec_mi_threshold = mi_threshold if spec_mi_threshold is None \
            else spec_mi_threshold
        self.spec_draft_s = spec_draft_s
        # adaptive draft depth: per-slot acceptance EMA walks each
        # slot's k inside [k_min, k_max] (defaults pin both to spec_k,
        # which disables adaptation and keeps the fixed-k stream
        # bitwise); a round drafts at the drafting slots' minimum
        self.spec_k_min = spec_k if spec_k_min is None else spec_k_min
        self.spec_k_max = spec_k if spec_k_max is None else spec_k_max
        if spec_decode and not (1 <= self.spec_k_min <= spec_k
                                <= self.spec_k_max):
            raise ValueError(
                f"adaptive spec-k bounds must satisfy 1 <= k_min <= k "
                f"<= k_max, got k_min={self.spec_k_min} k={spec_k} "
                f"k_max={self.spec_k_max}")
        # admission/eviction decision layer (policy.SchedPolicy): a name
        # from --policy or a ready instance; fifo is the bit-exact
        # reference the priority policy is anchored against
        self.policy = policy if isinstance(policy, SchedPolicy) \
            else get_policy(policy)
        # MI-triggered OOD escalation: a slot whose carried MI reaches
        # escalate_mi finishes on a high-S sidecar runner (escalate_s
        # MC samples; default 4x the serving S)
        if escalate_mi is not None and escalate_mi < 0:
            raise ValueError(
                f"escalate_mi must be >= 0, got {escalate_mi}")
        self.escalate_mi = escalate_mi
        self.escalate_s = escalate_s if escalate_s is not None \
            else 4 * cfg.mc_samples
        if self.escalate_s < 1:
            raise ValueError(
                f"escalate_s must be >= 1, got {self.escalate_s}")
        self.num_slots = num_slots
        self.max_len = max_len
        self.chunk = chunk
        self.eos_id = eos_id
        self.trace_every = trace_every
        self.mesh = mesh
        self.kv_layout = kv_layout if M.supports_paged(cfg) else "dense"
        # the block-sparse decode kernel reads through the block table,
        # so it only exists on the paged layout; families that fell back
        # to dense silently keep the gather/dense read path, mirroring
        # the ssm dense fallback below
        self.decode_attn = decode_attn if self.kv_layout == "paged" \
            else "gather"
        if mesh is not None and mesh.devices.size > 1 \
                and self.decode_attn == "kernel":
            # the Pallas kernel body can't be partitioned by GSPMD over
            # the pool's head shards; a real multi-device mesh keeps the
            # (shardable) gather read path, silently like the fallbacks
            # around it.  A 1-device fallback mesh shards nothing, so
            # the kernel stays available there.
            self.decode_attn = "gather"
        # decode_attn rides ArchConfig (like head_entropy) so every
        # family's decode threads it to layers.apply_attention without
        # signature churn; params are structure-independent of it
        self.cfg = cfg = dataclasses.replace(cfg,
                                             decode_attn=self.decode_attn)
        # prefix reuse additionally needs prompt KV that is a pure
        # function of the token IDs (see registry.supports_prefix_cache);
        # unsupported families silently serve cold, like the ssm
        # dense fallback above
        self.prefix_cache = (prefix_cache and self.kv_layout == "paged"
                             and M.supports_prefix_cache(cfg))
        self.kv_block = kv_block
        self.table_width = M.paged_table_width(max_len, kv_block)
        # default pool = full dense capacity: no admission change, the
        # savings then show up as peak blocks in use < blocks allocated
        self.kv_blocks = (kv_blocks if kv_blocks is not None
                          else num_slots * self.table_width)
        if self.kv_blocks < 1:
            raise ValueError(f"kv_blocks must be >= 1, got {kv_blocks}")
        paged = self.kv_layout == "paged"
        # prompt-length bucketing: padding-safe families right-pad cold
        # prompts to the next kv_block multiple, so the jitted batch
        # prefill compiles once per BUCKET instead of once per distinct
        # prompt length (prefill_compiles in the run stats); recurrent
        # families keep exact lengths
        self.pad_prompts = M.supports_prompt_padding(cfg)
        # chunked prefill needs the per-family prefill_chunk walker and
        # the paged layout; others fall back to batch silently, like the
        # ssm dense fallback above
        self.prefill_mode = prefill_mode if paged \
            and M.supports_chunked_prefill(cfg) else "batch"
        self.prefill_chunk = prefill_chunk
        if self.prefill_mode == "chunked" and cfg.family == "hybrid":
            # hybrid chunks walk the SSM in ssm_chunk segments; round
            # the knob up so every full chunk is a clean multiple
            sc = cfg.ssm_chunk
            self.prefill_chunk = -(-prefill_chunk // sc) * sc
        self.runner = ModelRunner(
            params, cfg, max_len=max_len, chunk=chunk, entropy=entropy,
            mi_threshold=mi_threshold, se_threshold=se_threshold,
            kv_layout=self.kv_layout, kv_block=kv_block,
            kv_blocks=self.kv_blocks, prefix_cache=self.prefix_cache,
            prefill_mode=self.prefill_mode, mesh=mesh,
            spec_decode=spec_decode, spec_k=spec_k,
            spec_draft_s=spec_draft_s)
        # mesh mode re-places params by the serve-TP rules; the engine
        # always dispatches the runner's copy
        self.params = self.runner.params
        # escalation sidecars: unplaced params + head-draw knobs so
        # escalation_runner can build a second ModelRunner (its own jit
        # cache) per distinct verify S, on demand; off-mesh they are the
        # runner's, whose head sigma is already made
        self._base_params = params if mesh is not None \
            else self.runner.params
        self._entropy = entropy
        self._mi_threshold = mi_threshold
        self._se_threshold = se_threshold
        self._esc_runners: dict[int, ModelRunner] = {}
        # compiled-callable aliases: run() dispatches through self so
        # tests can interpose on a single engine attribute (e.g. the
        # mid-run fault injection in tests/test_paged_attention.py)
        self._prefill = self.runner._prefill
        self._write = self.runner._write
        self._chunk_fn = self.runner._chunk_fn
        self._chunk_first = self.runner._chunk_first
        self._suffix = self.runner._suffix
        self._copy = self.runner._copy
        self._set_len = self.runner._set_len
        self._scan = self.runner._scan
        self._draft = self.runner._draft
        self._verify = self.runner._verify
        self._spec_commit = self.runner._spec_commit

    def escalation_runner(self, s: int) -> ModelRunner:
        """The high-S verify runner for ``s`` MC head samples — a
        second ModelRunner jit cache KEYED BY S (each distinct verify S
        compiles its own prefill/scan once, then every escalated
        request at that S reuses them).  Single-slot dense sidecar: S
        only changes head draws, so the cheap layout is fine, and the
        gather read path is the dense reference
        (tests/test_policy.py::TestEscalation)."""
        if s not in self._esc_runners:
            cfg = dataclasses.replace(self.cfg, mc_samples=s,
                                      decode_attn="gather")
            self._esc_runners[s] = ModelRunner(
                self._base_params, cfg, max_len=self.max_len,
                chunk=self.chunk, entropy=self._entropy,
                mi_threshold=self._mi_threshold,
                se_threshold=self._se_threshold, kv_layout="dense",
                kv_block=self.kv_block, kv_blocks=self.table_width,
                prefix_cache=False, prefill_mode="batch")
        return self._esc_runners[s]

    def _bucket(self, n: int) -> int:
        """Prompt-length bucket: next kv_block multiple (dense strips
        additionally clamp to max_len).  The static attention span every
        prefill path of a bucketed prompt reduces over."""
        if not self.pad_prompts:
            return n
        w = -(-n // self.kv_block) * self.kv_block
        return min(w, self.max_len) if self.kv_layout == "dense" else w

    def _start_job(self, req: Request, hit_len: int, span: int,
                   cache) -> dict:
        """Open a chunked-prefill walk over ``req``'s prompt.

        The job carries the walk offset plus whatever state the family's
        ``prefill_chunk`` threads between chunks: SSM/conv recurrent
        state for hybrid, and the encoder-frames-pending flag for encdec.
        """
        job = {"req": req, "P": len(req.prompt), "span": span,
               "off": hit_len, "first": True}
        cfg = self.cfg
        if cfg.family == "hybrid":
            from repro.models.ssm import dims
            d_in, H, Pd, N = dims(cfg)
            job["state"] = {
                "ssm": jnp.zeros((cfg.num_layers, 1, H, Pd, N),
                                 jnp.float32),
                "conv": jnp.zeros((cfg.num_layers, 1,
                                   cfg.ssm_conv_width - 1, d_in + 2 * N),
                                  cache["conv"].dtype)}
        return job

    def _run_chunk(self, cache, slot: int, job: dict, stats):
        """Advance ``job`` by one prompt chunk; returns
        ``(cache, done, shape_key)``.  The chunk's counters (moe), device
        values, go to ``stats.counted`` unread.

        Padding-safe families pad every chunk to exactly prefill_chunk
        tokens (one compile per (chunk, span) pair; trailing junk either
        scatters into the in-bucket pad region the batch path also
        writes, or drops at unmapped blocks).  Hybrid walks exact
        ssm_chunk-multiple segments instead — its recurrence is not
        padding-safe.
        """
        off, P, W = job["off"], job["P"], job["span"]
        pc = self.prefill_chunk
        real = min(pc, P - off)
        S_len = pc if self.pad_prompts else real
        toks = np.zeros((S_len,), np.int32)
        toks[:real] = job["req"].prompt[off:off + real]
        new_len = off + real
        done = new_len >= P
        args = (self.params, jnp.asarray(toks)[None], cache,
                jnp.asarray(slot, jnp.int32), jnp.asarray(off, jnp.int32),
                jnp.asarray(new_len, jnp.int32))
        fam = self.cfg.family
        variant = ""
        if fam == "hybrid":
            cache, job["state"] = self._chunk_fn(*args, job["state"], W,
                                                 done)
            variant = "final" if done else ""
        elif fam == "encdec" and job["first"]:
            cache = self._chunk_first(*args, self._modality(1), W)
            variant = "first"
        else:
            cache, counters = self._chunk_fn(*args, W)
            if counters:
                stats.counted("prefill", counters, 1)
        job["first"] = False
        job["off"] = new_len
        return cache, done, ("chunk", S_len, W, variant)

    def _modality(self, batch: int):
        cfg = self.cfg
        if cfg.family == "encdec":
            from repro.models.encdec import ENC_LEN
            return jnp.zeros((batch, ENC_LEN, cfg.d_model), jnp.float32)
        if cfg.family == "vlm":
            return jnp.zeros((batch, cfg.num_prefix_embeds, cfg.d_model),
                             jnp.float32)
        return None

    def _spec_round(self, sched, stats, decoding, tok, cache, active,
                    flags, *, k=None, escalate=None):
        """One uncertainty-gated speculative round (replaces a scan
        chunk): a k-step shared-body draft proposes cheap-head tokens
        for every slot, ONE batched full-S-sample verify re-draws the
        uncertain head over the draft's stacked hiddens at the same
        (slot, depth) noise sites, and the host keeps each slot's
        longest agreeing prefix plus the first verified correction.

        Because the draft runs the SAME body (same params, same cache)
        as plain decode, every accepted position's KV/state writes are
        bitwise what plain decode would have written, and the verify
        head at depth-keyed operand noise reproduces plain decode's
        emissions exactly — so the accepted stream is bitwise identical
        to spec-decode off (tests/test_spec_decode.py).  Slots whose
        carried MI sits at/above the gate ride the round as plain
        decode: position 1's verified token only.  Rejected suffixes
        roll back host-side (``scheduler.rollback`` frees the decode
        blocks past the kept depth) and device-side (``spec_commit``
        pins tok/len and rewinds recurrent ssm/conv state); junk KV
        above the kept depth stays masked until overwritten.
        """
        runner = self.runner
        k = self.spec_k if k is None else k
        # the engine-attribute aliases stay the dispatch point at the
        # default depth (tests interpose on engine._draft); other
        # adaptive depths resolve through the runner's per-k jit cache
        draft_fn, verify_fn = (self._draft, self._verify) \
            if k == self.spec_k else runner.spec_fns(k)
        stats.record_round_k(k)
        parts = [(slot, req) for slot, req in sched.active()
                 if slot in decoding]
        lens0 = np.zeros((self.num_slots,), np.int32)
        for slot, req in parts:
            lens0[slot] = len(req.prompt) + len(req.tokens)
        with stats.span("serve.spec_round"):
            stats.dispatched(len(parts))
            tok, cache, dys = draft_fn(self.params, tok, cache)
            vys = verify_fn(self.params, dys["hidden"],
                            runner.put_replicated(jnp.asarray(lens0)))
            out = jax.copy_to_host_async({"draft": dys["token"], **vys})
        with stats.span("serve.decode_wait"):
            jax.block_until_ready(out)
        with stats.span("serve.decode_fetch"):
            host = jax.device_get(out)
        with stats.span("serve.harvest"):
            return self._spec_harvest(sched, stats, decoding, parts, k,
                                      lens0, host, dys, tok, cache,
                                      active, flags, escalate)

    def _spec_harvest(self, sched, stats, decoding, parts, k, lens0, host,
                      dys, tok, cache, active, flags, escalate):
        """Keep each slot's agreeing prefix of a speculative round's
        drafts plus the verified correction, then commit on device."""
        runner = self.runner
        stats.spec_rounds += 1
        stats.full_model_calls += 1          # ONE batched verify
        stats.steps_run += k
        commit_mask = np.zeros((self.num_slots,), bool)
        commit_tok = np.zeros((self.num_slots,), np.int32)
        commit_len = np.zeros((self.num_slots,), np.int32)
        commit_idx = np.zeros((self.num_slots,), np.int32)
        epi_add = np.zeros((self.num_slots,), np.int32)
        alea_add = np.zeros((self.num_slots,), np.int32)
        for slot, req in parts:
            if req.last_mi < self.spec_mi_threshold:
                a = 0
                while a < k and host["draft"][a, slot] \
                        == host["next_token"][a, slot]:
                    a += 1
                stats.spec_drafted += k
                stats.spec_accepted += a
                # adaptive depth: acceptance EMA per slot walks its k
                # inside [k_min, k_max]; at pinned bounds neither
                # branch can fire and the fixed-k stream is untouched
                rate = a / k
                req.spec_ema = rate if req.spec_ema is None \
                    else 0.5 * req.spec_ema + 0.5 * rate
                cur = req.spec_k_cur or self.spec_k
                if req.spec_ema >= 0.8 and cur < self.spec_k_max:
                    req.spec_k_cur = cur + 1
                    stats.spec_k_up += 1
                elif req.spec_ema <= 0.4 and cur > self.spec_k_min:
                    req.spec_k_cur = cur - 1
                    stats.spec_k_down += 1
                else:
                    req.spec_k_cur = cur
            else:
                # carried MI at/above the gate: no drafting credit —
                # the slot emits position 1's verified token only,
                # exactly one plain decode step's worth
                a = 0
                stats.spec_gated += 1
            m = min(a + 1, k)
            emitted = 0
            finished = False
            for j in range(m):
                tk = int(host["next_token"][j, slot])
                req.tokens.append(tk)
                for name in ("H", "SE", "MI", "p_max"):
                    getattr(req, name).append(float(host[name][j, slot]))
                req.epistemic_flags += int(host["epistemic"][j, slot])
                req.aleatoric_flags += int(host["aleatoric"][j, slot])
                epi_add[slot] += int(host["epistemic"][j, slot])
                alea_add[slot] += int(host["aleatoric"][j, slot])
                req.last_mi = float(host["MI"][j, slot])
                emitted = j + 1
                done_eos = self.eos_id is not None and tk == self.eos_id
                if done_eos or len(req.tokens) >= req.max_new_tokens:
                    req.transition("finished",
                                   reason="eos" if done_eos else "length")
                    sched.evict(slot)
                    decoding.discard(slot)
                    active = active.at[slot].set(False)
                    finished = True
                    break
            stats.spec_emitted += emitted
            if finished:
                continue
            if escalate is not None and escalate(slot, req):
                # handed to the high-S lane: the eviction already freed
                # every block (draft tail included), the slot goes
                # inactive, and no commit pin is needed
                active = active.at[slot].set(False)
                continue
            # keep depth lens0+emitted: free the decode blocks the
            # rejected draft tail grew into (host) and pin the slot's
            # carry token / device len / recurrent state (device).
            # emitted == k still commits — the carry token must be the
            # VERIFIED v_k, not the draft's final proposal.
            if emitted < k:
                stats.spec_rollbacks += 1
                sched.rollback(slot, int(lens0[slot]) + emitted)
            commit_mask[slot] = True
            commit_tok[slot] = host["next_token"][emitted - 1, slot]
            commit_len[slot] = lens0[slot] + emitted
            commit_idx[slot] = emitted - 1
        states = {leaf: dys[leaf] for leaf in M.RECURRENT_LEAVES
                  if leaf in dys}
        tok, cache = self._spec_commit(
            cache, tok,
            runner.put_replicated(jnp.asarray(commit_mask)),
            runner.put_replicated(jnp.asarray(commit_tok)),
            runner.put_replicated(jnp.asarray(commit_len)),
            states,
            runner.put_replicated(jnp.asarray(commit_idx)))
        # device flag telemetry: exactly the emitted positions' flags
        # (the scan carry instead counts junk steps to the chunk edge)
        flags = {"epistemic": flags["epistemic"]
                 + runner.put_replicated(jnp.asarray(epi_add)),
                 "aleatoric": flags["aleatoric"]
                 + runner.put_replicated(jnp.asarray(alea_add))}
        return tok, cache, active, flags

    def run(self, requests: list[Request]) -> dict:
        """Serve ``requests`` to completion; returns engine metrics.

        One host sync per admission (prefill) and one per decoded chunk
        (the stacked (chunk, B) outputs) -- never per token.
        """
        paged = self.kv_layout == "paged"
        for r in requests:
            if len(r.prompt) == 0:
                raise ValueError(f"request {r.rid}: empty prompt")
            if r.max_new_tokens < 1:
                raise ValueError(
                    f"request {r.rid}: max_new_tokens must be >= 1")
            # paged tables GROW on demand (grant widens them past the
            # admission-time span), so only dense strips — whose depth
            # is baked into the cache shape — bound prompt + gen
            if not paged and len(r.prompt) + r.max_new_tokens \
                    > self.max_len:
                raise ValueError(
                    f"request {r.rid}: prompt {len(r.prompt)} + "
                    f"max_new_tokens {r.max_new_tokens} exceeds the "
                    f"slot capacity max_len={self.max_len}; cache writes "
                    f"past capacity would be dropped silently")
        alloc = None
        pcache = None
        if paged:
            alloc = BlockAllocator(self.kv_blocks, self.kv_block)
            for r in requests:
                need = alloc.blocks_for(len(r.prompt) + r.max_new_tokens)
                if need > self.kv_blocks:
                    raise ValueError(
                        f"request {r.rid}: needs {need} KV blocks but the "
                        f"pool only has {self.kv_blocks}; it could never "
                        f"be admitted")
            if self.prefix_cache:
                from repro.launch.prefix_cache import RadixPrefixCache
                pcache = RadixPrefixCache(alloc, self.kv_block)
        sched = SlotScheduler(self.num_slots, allocator=alloc,
                              table_width=self.table_width,
                              prefix_cache=pcache, policy=self.policy)
        # observable post-mortem (tests assert the pool balances even
        # when run() raises mid-decode)
        self._last_alloc, self._last_pcache = alloc, pcache
        stats = ServeStats(trace_every=self.trace_every)
        # open-loop arrivals: requests with arrival_step > 0 join the
        # queue only once the engine has decoded that many steps (the
        # bursty traces bench_serve drives); step-0 requests submit now
        pending = collections.deque(
            sorted((r for r in requests if r.arrival_step > 0),
                   key=lambda r: r.arrival_step))
        for r in requests:
            if r.arrival_step <= 0:
                sched.submit(r)
        # MI-triggered escalation lane: a one-slot high-S sidecar the
        # harvest paths hand flagged requests to (None keeps every
        # escalation branch dead and the loop byte-for-byte)
        lane = None
        if self.escalate_mi is not None:
            lane = EscalationLane(
                self.escalation_runner(self.escalate_s),
                chunk=self.chunk, eos_id=self.eos_id,
                pad_to=self.kv_block if self.pad_prompts else None,
                modality=self._modality(1))
        esc_skipped_rids: set = set()

        def maybe_escalate(slot, req):
            """Hand a flagged slot to the lane; the CALLER clears the
            slot's active lane in the device mask (this closure cannot
            rebind the loop's `active` from inside _spec_round)."""
            if lane is None or req.last_mi < self.escalate_mi:
                return False
            if not lane.fits(req):
                # dense sidecar can't hold prompt + budget: keep
                # decoding in the main engine, counted once
                if req.rid not in esc_skipped_rids:
                    esc_skipped_rids.add(req.rid)
                    stats.esc_skipped += 1
                return False
            req.transition("escalated")
            sched.evict(slot)
            decoding.discard(slot)
            lane.submit(req)
            stats.escalations += 1
            stats.esc_by_class[req.priority] += 1
            return True

        runner = self.runner
        tok = runner.put_replicated(jnp.zeros((self.num_slots,), jnp.int32))
        cache = runner.make_cache(self.num_slots)
        active = runner.put_replicated(jnp.zeros((self.num_slots,), bool))
        flags = {
            "epistemic": runner.put_replicated(
                jnp.zeros((self.num_slots,), jnp.int32)),
            "aleatoric": runner.put_replicated(
                jnp.zeros((self.num_slots,), jnp.int32))}
        step0 = 0
        table_synced = -1            # device block-table version synced
        modality1 = self._modality(1)
        # chunked-prefill bookkeeping: slot -> in-flight prompt walk
        # (offset + family carry), FIFO order of pending walks, and the
        # slots currently DECODING (mid-prefill slots sit in the scan
        # batch inactive; their junk steps are overwritten by the next
        # chunk's scatter, see models.layers.apply_attention_chunk)
        prefilling: dict[int, dict] = {}
        jobs: collections.deque[int] = collections.deque()
        decoding: set[int] = set()

        def activate(slot, req):
            nonlocal tok, active, flags
            # four small device updates, each its own dispatch
            with stats.span("serve.activate"):
                req.transition("decoding")
                req.spec_k_cur = self.spec_k
                tok = tok.at[slot].set(int(req.prompt[-1]))
                active = active.at[slot].set(True)
                flags = {k: v.at[slot].set(0) for k, v in flags.items()}
                decoding.add(slot)

        def sync_table():
            # re-upload the device block table (tiny: slots x MB) only
            # when the host copy changed; a width change alters the
            # cache shape, so downstream jits retrace once per growth
            nonlocal cache, table_synced
            if sched.table_version != table_synced:
                cache = dict(cache, block_table=runner.place_table(
                    sched.block_tables))
                table_synced = sched.table_version

        def admit_one(slot, req):
            """Map one admission's prompt: a prefix hit, a queued
            chunked walk, or a batch prefill (``serve.prefill``)."""
            nonlocal cache
            info = sched.prefix_admit(slot) if paged else None
            hit_len = info.tokens if info is not None else 0
            P = len(req.prompt)
            W = self._bucket(P)
            if info is not None and info.cow is not None:
                # the shared tail block is about to be written at the
                # divergence point: duplicate it device-side and let the
                # scheduler drop this slot's ref on the original
                src, dst = info.cow
                cache = self._copy(cache, jnp.asarray(src, jnp.int32),
                                   jnp.asarray(dst, jnp.int32))
                sched.finish_cow(slot)
                stats.pc_cow += 1
            slot_ = jnp.asarray(slot, jnp.int32)
            if info is not None:
                stats.record_admission(P, hit_len)
            if self.prefill_mode == "chunked" and hit_len < P:
                # enqueue an incremental prompt walk (suffix-only on a
                # partial prefix hit — CoW already settled above) and
                # pin the slot's depth to the resident span NOW:
                # interleaved scans write junk at [len, len+chunk) for
                # every slot, and a stale len would point into shared
                # prefix blocks
                cache = self._set_len(cache, slot_,
                                      jnp.asarray(hit_len, jnp.int32))
                prefilling[slot] = self._start_job(req, hit_len, W, cache)
                jobs.append(slot)
                return
            with stats.span("serve.prefill") as sp:
                if hit_len == P:
                    # whole prompt resident: zero prefill compute — the
                    # decode loop only needs the slot's depth
                    cache = self._set_len(cache, slot_,
                                          jnp.asarray(P, jnp.int32))
                    shape_key = ("hit",)
                elif hit_len > 0:
                    # suffix padded to the same bucketed span the cold
                    # path reduces over (W - hit junk tokens): equal
                    # extents keep hit and cold bit-identical
                    stoks = np.zeros((W - hit_len,), np.int32)
                    stoks[:P - hit_len] = req.prompt[hit_len:]
                    cache = self._suffix(
                        self.params, cache, slot_,
                        runner.place_table(sched.block_tables[slot]),
                        jnp.asarray(stoks)[None], hit_len)
                    if W > P:
                        cache = self._set_len(cache, slot_,
                                              jnp.asarray(P, jnp.int32))
                    shape_key = ("suffix", hit_len, W - hit_len)
                else:
                    toks = np.zeros((W,), np.int32)
                    toks[:P] = req.prompt
                    _, sub = self._prefill(self.params,
                                           jnp.asarray(toks)[None],
                                           modality1)
                    if paged:
                        cache = self._write(
                            cache, slot_, sub,
                            runner.place_table(sched.block_tables[slot]))
                    else:
                        cache = self._write(cache, slot_, sub)
                    if W > P:
                        # junk pad KV stays masked above the true len
                        cache = self._set_len(cache, slot_,
                                              jnp.asarray(P, jnp.int32))
                    shape_key = ("cold", W)
                activate(slot, req)
            with stats.span("serve.prefill_wait") as wt:
                jax.block_until_ready(cache)
            stats.classify(shape_key, sp.s + wt.s)

        def tally_attn_blocks(ahead):
            # the gather path materializes every slot's full logical
            # span each step, occupied or not (a spec round's draft
            # reads decode attention for its k steps exactly like k
            # scan steps; the verify is head-only and touches no KV)
            MB = sched.block_tables.shape[1]
            stats.attn_blocks_span += self.num_slots * MB * ahead
            if self.decode_attn == "kernel":
                # the kernel reads only mapped blocks under each
                # occupied slot's depth
                for slot, occupant in sched.active():
                    if slot in prefilling:
                        continue
                    len0 = len(occupant.prompt) + len(occupant.tokens)
                    mapped = sched.mapped_blocks(slot)
                    stats.attn_blocks_read += sum(
                        kv_blocks_read(len0 + t + 1, mapped,
                                       self.kv_block, MB)
                        for t in range(ahead))

        def harvest(ys):
            nonlocal active
            for slot, req in sched.active():
                if slot in prefilling:
                    continue             # mid-prefill: junk steps, no harvest
                finished = False
                for t in range(self.chunk):
                    tk = int(ys["token"][t, slot])
                    req.tokens.append(tk)
                    for name in ("H", "SE", "MI", "p_max"):
                        getattr(req, name).append(float(ys[name][t, slot]))
                    req.epistemic_flags += int(ys["epistemic"][t, slot])
                    req.aleatoric_flags += int(ys["aleatoric"][t, slot])
                    req.last_mi = float(ys["MI"][t, slot])
                    done_eos = self.eos_id is not None and tk == self.eos_id
                    if done_eos or len(req.tokens) >= req.max_new_tokens:
                        req.transition(
                            "finished",
                            reason="eos" if done_eos else "length")
                        sched.evict(slot)
                        decoding.discard(slot)
                        active = active.at[slot].set(False)
                        finished = True
                        break
                # escalation check on the slot's CARRIED (chunk-end)
                # MI: unfinished flagged slots finish on the lane
                if not finished and maybe_escalate(slot, req):
                    active = active.at[slot].set(False)

        def iterate():
            """One pass of the engine loop: admissions, at most one
            prompt chunk, grants, one unit of lane work, then one decode
            chunk (or speculative round) and its harvest."""
            nonlocal tok, cache, active, flags, step0
            with stats.span("serve.admit"):
                # fire every arrival whose step has come; when the
                # engine is otherwise idle, fast-forward to the next
                # arrival group instead of spinning on empty iterations
                fired = 0
                while pending \
                        and pending[0].arrival_step <= stats.steps_run:
                    sched.submit(pending.popleft())
                    fired += 1
                if not fired and pending and not sched.has_work() \
                        and not (lane is not None and lane.has_work()):
                    nxt = pending[0].arrival_step
                    while pending and pending[0].arrival_step == nxt:
                        sched.submit(pending.popleft())
                        fired += 1
                admitted = sched.admit()
                # admission-pressure preemptions (priority policy): the
                # victims' requests are already requeued; drop the slots
                # from the engine's decode set before the new admissions
                # (possibly into those slots) re-arm them
                for slot, _req in sched.take_preempted():
                    decoding.discard(slot)
                    active = active.at[slot].set(False)
                if paged:
                    # admissions mutate the host tables (and may WIDEN
                    # them); the device copy must match before any
                    # prefill write installs a row at the new width
                    sync_table()
                for slot, req in admitted:
                    admit_one(slot, req)

            if jobs:
                # at most ONE prompt chunk per engine iteration
                # (Sarathi-style): the head walk advances by
                # prefill_chunk tokens, then the decode scan below still
                # runs for every active slot
                slot = jobs[0]
                job = prefilling[slot]
                req = job["req"]
                with stats.span("serve.prefill_chunk") as sp:
                    req.prefill_started(sp.t0)
                    cache, done, shape_key = self._run_chunk(cache, slot,
                                                             job, stats)
                    stats.prefill_chunks += 1
                with stats.span("serve.prefill_wait") as wt:
                    jax.block_until_ready(cache)
                stats.classify(shape_key, sp.s + wt.s)
                if done:
                    jobs.popleft()
                    del prefilling[slot]
                    # activate BEFORE this iteration's scan: the slot's
                    # first real decode tokens come from it (no junk
                    # window between prefill and decode)
                    activate(slot, req)

            with stats.span("serve.grant"):
                # a speculative round replaces this iteration's scan
                # chunk when ANY decoding slot's carried MI sits strictly
                # below the gate (threshold 0 therefore never drafts and
                # the loop is byte-for-byte the plain scan path); decided
                # before grants so the lookahead matches what the round
                # will write (k draft positions instead of a chunk).
                # Adaptive depth: the round drafts at the drafting
                # slots' MINIMUM current k, so no slot overdrafts past
                # its own EMA-chosen depth.
                drafting = [req for slot, req in sched.active()
                            if slot in decoding
                            and req.last_mi < self.spec_mi_threshold]
                run_spec = self.spec_decode and bool(drafting)
                k_round = min(req.spec_k_cur or self.spec_k
                              for req in drafting) if run_spec \
                    else self.spec_k
                ahead = k_round if run_spec else self.chunk
                if paged:
                    # incremental grant: map the blocks the coming chunk
                    # can write, on demand from the pool (capped at each
                    # request's prompt+max_new budget); re-upload the
                    # device table (tiny: slots x MB) only when
                    # something actually changed since the last chunk
                    for slot, req in sched.active():
                        if slot in prefilling:
                            continue     # prompt blocks mapped at admission
                        ids = sched.grant(slot, len(req.prompt)
                                          + min(len(req.tokens) + ahead,
                                                req.max_new_tokens))
                        if ids is None:
                            # the pool cannot grow this slot even after
                            # LRU-evicting cached blocks: preempt —
                            # blocks release, the lifecycle transition
                            # clears the output, the request restarts
                            # from the queue FRONT
                            sched.preempt(slot)
                            decoding.discard(slot)
                            active = active.at[slot].set(False)
                    sync_table()
                    if decoding:
                        tally_attn_blocks(ahead)
                stats.trace(sched)

            # ONE unit of lane work per iteration (admission or a chunk
            # at the verify S): escalations drain alongside the main
            # pool without stalling its decode cadence
            lane_ran = False
            if lane is not None:
                with stats.span("serve.escalate"):
                    lane_ran = lane.step(stats)
            if not decoding:
                if not jobs and not admitted and not lane_ran \
                        and not fired:
                    raise RuntimeError(
                        "scheduler stalled: queued requests, no "
                        "admission, nothing prefilling or decoding")
                return                   # prefill-only iteration: no scan

            if run_spec:
                tok, cache, active, flags = self._spec_round(
                    sched, stats, decoding, tok, cache, active, flags,
                    k=k_round, escalate=maybe_escalate)
                return

            stats.chunks_run += 1
            stats.full_model_calls += self.chunk
            stats.steps_run += self.chunk
            with stats.span("serve.decode_dispatch"):
                stats.dispatched(len(decoding))
                tok, cache, flags, ys = self._scan(
                    self.params, tok, cache, jnp.asarray(step0, jnp.int32),
                    active, flags)
                # queue the outputs' copies to the host behind the scan
                # (what device_get starts with), so that they run as
                # the device finishes and not once the host has woken
                jax.copy_to_host_async(ys)
            # the chunk's single sync, split into the wait for the
            # device and the rest of the copy of its outputs
            with stats.span("serve.decode_wait"):
                jax.block_until_ready(ys)
            with stats.span("serve.decode_fetch"):
                ys = jax.device_get(ys)
            step0 += self.chunk
            with stats.span("serve.harvest"):
                harvest(ys)
                if "counters" in ys:
                    stats.counted("decode", ys["counters"], self.chunk)

        try:
            it = 0
            while sched.has_work() or pending \
                    or (lane is not None and lane.has_work()):
                with stats.iteration(it):
                    iterate()
                it += 1

        except BaseException:
            # eviction / exception / early-exit path: slots mid-decode
            # still hold blocks — release them so the pool balances even
            # when the run dies (evict also settles any pending CoW ref
            # and donates prompt blocks to the prefix tree, exactly like
            # a clean eviction would have)
            for slot, _ in list(sched.active()):
                sched.evict(slot)
            raise
        finally:
            # leak check on EVERY exit path, clean drain or not: each
            # block is either free or held by the prefix cache (cached
            # refcounts included) and no reservation is outstanding
            # (tests/test_paged_attention.py::TestEngineRobustness::
            # test_mid_run_exception_releases_blocks)
            if alloc is not None:
                cached_end = pcache.cached_blocks() if pcache else 0
                if alloc._reserved or alloc.in_use != cached_end:
                    raise RuntimeError(
                        f"block leak after drain: {alloc.in_use} in use "
                        f"vs {cached_end} cached, {alloc._reserved} "
                        "reserved")

        return stats.results(self, requests, sched=sched, alloc=alloc,
                             pcache=pcache, cache=cache, flags=flags)
