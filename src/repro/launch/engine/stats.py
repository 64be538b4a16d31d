"""Per-run serving telemetry (the engine's observability layer).

``ServeStats`` owns every counter the engine run accumulates — prefill
compile-vs-steady classification, prefix-cache hit accounting, the
decode-attention HBM block tally, the downsampled scheduler trace —
plus the span recorder the engine loop runs inside, and builds the
results dict ``ServeEngine.run`` returns.

Counters: ``counted`` keeps the per-call counters a program returns
beside its outputs (the moe family's ``moe.rows`` and
``moe.experts_hit``), as ``CountRecord``s; a decode chunk's come with
the tokens the harvest already fetched, a prompt chunk's stay on the
device until ``results``, so neither costs a device sync.

Spans: ``span(name)`` marks one phase of the loop and ``iteration(i)``
one pass of it.  Each enters a ``jax.profiler`` annotation (a host
event on the device trace's clock whenever a trace is running; about
a microsecond when none is) and stamps ``time.perf_counter`` into
per-phase totals (seconds, count, longest).  Each iteration appends
one ``IterationRecord`` to a bounded deque.  The names are in
docs/serving.md ("Spans and counters").
"""

from __future__ import annotations

import collections
import time
from collections import Counter
from typing import NamedTuple

import jax
import numpy as np

from repro.models import registry as M

ITERATION = "serve.iteration"
# phases in which the host only waits for the device
WAITS = ("serve.prefill_wait", "serve.decode_wait", "serve.escalate_wait")
# phases whose time is decode: the main scan's dispatch, wait and
# fetch, a speculative round's dispatch, the escalation lane's chunks
DECODE = ("serve.decode_dispatch", "serve.decode_wait",
          "serve.decode_fetch", "serve.spec_round", "serve.escalate_decode")
# iteration records kept: about 21 minutes of the ~310 ms iterations of
# a 24-slot qwen2-1.5B engine on one TPU v5e, 4096 small tuples
ITERATIONS_KEPT = 4096
# program counter records kept (one per decode chunk and prompt chunk)
COUNTED_KEPT = 2 * ITERATIONS_KEPT


class CountRecord(NamedTuple):
    """A program's counters for one call: the host time it was recorded
    (decode: at its harvest; prefill: at its dispatch), ``kind``
    ("decode" or "prefill"), the decode steps it ran (1 for a prompt
    chunk), and each counter summed over them."""

    t: float
    kind: str
    steps: int
    values: dict


class IterationRecord(NamedTuple):
    """One pass of the engine loop: its start and end on
    ``time.perf_counter``, the seconds inside it spent in ``WAITS``, and
    the slots decoding in the scan (or speculative round) it dispatched
    (0 when it dispatched none)."""

    t0: float
    t1: float
    wait_s: float
    rows: int


class Span:
    """One timed phase: a ``jax.profiler.TraceAnnotation`` plus host
    stamps; ``t0`` and ``s`` (its duration) are set as it runs."""

    __slots__ = ("_stats", "_ann", "name", "t0", "s")

    def __init__(self, stats: "ServeStats", name: str, ann):
        self._stats, self._ann, self.name = stats, ann, name
        self.t0 = self.s = 0.0

    def __enter__(self) -> "Span":
        self._ann.__enter__()
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc) -> None:
        self.s = time.perf_counter() - self.t0
        self._ann.__exit__(*exc)
        self._stats._close(self)


def _pcts(values) -> tuple[float, float]:
    """(p50, p99) with nearest-rank p99 — at small N an interpolated
    p99 fabricates a latency no request experienced."""
    arr = np.array(values) if len(values) else np.zeros((1,))
    return (float(np.percentile(arr, 50)),
            float(np.percentile(arr, 99, method="higher")))


class ServeStats:
    """Counters for one ``ServeEngine.run`` + the results-dict builder.

    The engine mutates the counter attributes directly inside its chunk
    loop (they are the same names the monolithic loop used as locals);
    ``span``/``iteration`` time its phases, ``classify`` splits each
    prefill dispatch into compile-vs-steady by whether its shape key was
    seen before, ``trace`` appends the scheduler/pool snapshot every
    ``trace_every``-th chunk, and ``results`` assembles the full metrics
    payload.
    """

    def __init__(self, *, trace_every: int):
        self.trace_every = trace_every
        self.t_start = time.perf_counter()
        # span recorder: per-phase [seconds, count, longest], the
        # iteration records, and the open iteration's wait and rows
        self.phases: dict[str, list] = {}
        self.iterations: collections.deque = collections.deque(
            maxlen=ITERATIONS_KEPT)
        self._iter_wait = 0.0
        self._iter_rows = 0
        # the programs' own counters (moe: ``moe.rows``,
        # ``moe.experts_hit``), one record per call; a prompt chunk's
        # values stay on the device until ``results`` reads them all
        self.counts: collections.deque = collections.deque(
            maxlen=COUNTED_KEPT)
        # the jitted prefill compiles once per distinct prompt length
        # (suffix prefill: per distinct suffix length); classify each
        # admission's time accordingly so mixed-length traffic doesn't
        # launder recompiles into the steady-state stat
        self.compile_s = 0.0
        self.steady_s = 0.0
        self.steady_n = 0
        self.seen_prefill_shapes: set[tuple] = set()
        # prefix-cache counters + per-chunk scheduler/pool trace
        self.pc_hits = self.pc_misses = self.pc_cow = 0
        self.pc_tokens = self.pc_saved = 0
        self.sched_trace: list[dict] = []
        self.chunks_run = 0
        # decode-attention HBM accounting (paged): physical KV blocks the
        # selected read path touches per decode step vs the full logical
        # span the gather path materializes (kernel skip rule in host
        # arithmetic, kernels.paged_attention.kv_blocks_read)
        self.attn_blocks_read = 0
        self.attn_blocks_span = 0
        self.prefill_chunks = 0
        # OOD escalation: requests handed to the high-S verify lane when
        # their carried MI crossed --escalate-mi, the tokens that lane
        # finished for them, and the requests it could NOT take (prompt
        # + budget exceeding the lane's max_len)
        self.escalations = 0
        self.esc_by_class: Counter = Counter()
        self.esc_tokens = 0
        self.esc_skipped = 0
        self.esc_steps = 0
        # adaptive spec-decode depth: per-slot EMA grow/shrink events
        # and the range of round depths actually drafted
        self.spec_k_up = 0
        self.spec_k_down = 0
        self.spec_round_k_min = None
        self.spec_round_k_max = None
        # speculative decoding: rounds run, proposals drafted/accepted/
        # emitted, partial-round rollbacks, and MI-gated (non-drafting)
        # slot-rounds.  full_model_calls counts full-S-sample dispatches
        # (chunk per scan, ONE per batched verify) — the quantity spec
        # decode exists to reduce; steps_run the real KV-advancing steps
        # either path executed (replaces the chunks_run*chunk estimate)
        self.spec_rounds = 0
        self.spec_drafted = 0
        self.spec_accepted = 0
        self.spec_emitted = 0
        self.spec_rollbacks = 0
        self.spec_gated = 0
        self.full_model_calls = 0
        self.steps_run = 0

    def span(self, name: str) -> Span:
        """``with stats.span("serve.harvest"):`` times one phase."""
        return Span(self, name, jax.profiler.TraceAnnotation(name))

    def iteration(self, i: int) -> Span:
        """``with stats.iteration(i):`` around one pass of the engine
        loop: a step annotation on the trace, an ``IterationRecord``."""
        self._iter_wait = 0.0
        self._iter_rows = 0
        return Span(self, ITERATION, jax.profiler.StepTraceAnnotation(
            ITERATION, step_num=i))

    def dispatched(self, rows: int) -> None:
        """The open iteration's decode dispatch serves ``rows`` slots."""
        self._iter_rows = rows

    def counted(self, kind: str, counters: dict, steps: int) -> None:
        """Record one program call's ``counters`` (name -> per-step
        values, host or device) over ``steps`` decode steps."""
        self.counts.append(CountRecord(time.perf_counter(), kind, steps,
                                       counters))

    def _close(self, sp: Span) -> None:
        tot = self.phases.get(sp.name)
        if tot is None:
            tot = self.phases[sp.name] = [0.0, 0, 0.0]
        tot[0] += sp.s
        tot[1] += 1
        tot[2] = max(tot[2], sp.s)
        if sp.name in WAITS:
            self._iter_wait += sp.s
        elif sp.name == ITERATION:
            self.iterations.append(IterationRecord(
                sp.t0, sp.t0 + sp.s, self._iter_wait, self._iter_rows))

    def phase_s(self, *names: str) -> float:
        """Total seconds recorded under ``names``."""
        return sum(self.phases[n][0] for n in names if n in self.phases)

    def classify(self, shape_key: tuple, dt: float) -> None:
        if shape_key in self.seen_prefill_shapes:
            self.steady_s += dt
            self.steady_n += 1
        else:
            self.seen_prefill_shapes.add(shape_key)
            self.compile_s += dt

    def record_round_k(self, k: int) -> None:
        """Track the range of draft depths adaptive-k rounds used."""
        self.spec_round_k_min = k if self.spec_round_k_min is None \
            else min(self.spec_round_k_min, k)
        self.spec_round_k_max = k if self.spec_round_k_max is None \
            else max(self.spec_round_k_max, k)

    def record_admission(self, prompt_len: int, hit_len: int) -> None:
        """Prefix-cache hit accounting for one paged admission."""
        self.pc_hits += bool(hit_len)
        self.pc_misses += not hit_len
        self.pc_tokens += prompt_len
        self.pc_saved += hit_len

    def trace(self, sched) -> None:
        """Downsampled pool/queue snapshot: a long run would otherwise
        grow host memory (and the results payload) by one dict per
        chunk, unbounded."""
        if self.chunks_run % self.trace_every == 0:
            self.sched_trace.append(sched.pool_stats())

    def results(self, engine, requests, *, sched, alloc, pcache, cache,
                flags) -> dict:
        """Assemble the engine metrics payload from the run's counters
        plus the terminal scheduler / allocator / cache state."""
        paged = engine.kv_layout == "paged"
        total_s = time.perf_counter() - self.t_start
        decode_s = self.phase_s(*DECODE)
        gen_tokens = sum(len(r.tokens) for r in requests)
        # KV residency accounting: dense permanently owns num_slots
        # strips of max_len; paged owns only the blocks actually mapped
        # (peak over the run), which is what mixed-length traffic saves
        kv_alloc_bytes = M.kv_bytes(cache)
        if paged:
            token_bytes = kv_alloc_bytes / (engine.kv_blocks
                                            * engine.kv_block)
            block_bytes = kv_alloc_bytes // engine.kv_blocks
            kv_stats = {
                "layout": "paged",
                "block_tokens": engine.kv_block,
                "blocks_total": engine.kv_blocks,
                "blocks_peak": alloc.peak_in_use,
                "bytes_in_use_peak": alloc.peak_in_use * block_bytes,
                "bytes_dense_equiv": int(token_bytes * engine.num_slots
                                         * engine.max_len),
            }
        else:
            kv_stats = {
                "layout": "dense",
                "bytes_in_use_peak": kv_alloc_bytes,
                "bytes_dense_equiv": kv_alloc_bytes,
            }
        # block-sparse decode attention accounting: KV bytes the selected
        # read path pulls from HBM per decode step vs the full logical
        # span (what gather materializes regardless of residency)
        steps_run = self.steps_run
        if paged:
            read_blocks = self.attn_blocks_read \
                if engine.decode_attn == "kernel" else self.attn_blocks_span
            decode_attn_stats = {
                "mode": engine.decode_attn,
                "kv_bytes_read_per_step": read_blocks * block_bytes
                / max(steps_run, 1),
                "kv_bytes_span_per_step": self.attn_blocks_span
                * block_bytes / max(steps_run, 1),
                "kv_blocks_read": read_blocks,
                "kv_blocks_span": self.attn_blocks_span,
            }
        else:
            decode_attn_stats = {"mode": "gather"}
        lat = np.array([r.latency_s for r in requests]) if requests \
            else np.zeros((1,))
        queue_p50, queue_p99 = _pcts([r.queue_time_s for r in requests])
        svc_p50, svc_p99 = _pcts([r.service_time_s for r in requests])
        # per-priority-class breakdown: under a priority policy the
        # aggregate p99 hides exactly the split the policy exists to
        # create, so report latency AND its queue/service decomposition
        # per class alongside that class's escalation/preemption counts
        per_class = {}
        for cls in sorted({r.priority for r in requests}):
            group = [r for r in requests if r.priority == cls]
            c_lat = _pcts([r.latency_s for r in group])
            c_queue = _pcts([r.queue_time_s for r in group])
            c_svc = _pcts([r.service_time_s for r in group])
            per_class[cls] = {
                "num_requests": len(group),
                "latency_p50_s": c_lat[0], "latency_p99_s": c_lat[1],
                "queue_p50_s": c_queue[0], "queue_p99_s": c_queue[1],
                "service_p50_s": c_svc[0], "service_p99_s": c_svc[1],
                "escalations": sum(r.was_escalated for r in group),
                "preemptions": sum(r.preempt_count for r in group),
            }
        served = [it.t1 for it in self.iterations if it.rows]
        # one transfer for every prompt chunk's device counters
        counts = [r._replace(values={k: int(np.sum(v))
                                     for k, v in vals.items()})
                  for r, vals in zip(self.counts, jax.device_get(
                      [r.values for r in self.counts]))]
        totals: Counter = Counter()
        for r in counts:
            totals.update(r.values)
        epi = sum(r.epistemic_flags for r in requests)
        alea = sum(r.aleatoric_flags for r in requests)
        return {
            "requests": requests,
            "num_requests": len(requests),
            "gen_tokens": gen_tokens,
            "total_s": total_s,
            "decode_s": decode_s,
            # first prefill per prompt length includes compilation; the
            # rest are steady-state dispatch
            "prefill_compile_s": self.compile_s,
            "prefill_steady_s": self.steady_s / self.steady_n
            if self.steady_n else 0.0,
            "decode_tok_per_s": gen_tokens / max(decode_s, 1e-9),
            "e2e_tok_per_s": gen_tokens / max(total_s, 1e-9),
            "latency_p50_s": float(np.percentile(lat, 50)),
            # nearest-rank (no interpolation): at small N a linear-
            # interpolated p99 fabricates a tail latency no request
            # experienced; "higher" reports a latency that actually
            # happened (= max below 100 requests)
            "latency_p99_s": float(np.percentile(lat, 99,
                                                 method="higher")),
            "latency_max_s": float(lat.max()),
            # latency decomposition: time queued (admission pressure,
            # what a priority policy trades between classes) vs time in
            # a slot (prefill + decode + any escalation tail)
            "queue_time_p50_s": queue_p50,
            "queue_time_p99_s": queue_p99,
            "service_time_p50_s": svc_p50,
            "service_time_p99_s": svc_p99,
            "policy": sched.policy.name,
            "per_class": per_class,
            "kv": kv_stats,
            # block-sparse decode kernel vs gather HBM traffic
            "decode_attn": decode_attn_stats,
            # radix prefix cache over the paged pool: zero-compute hit
            # spans, CoW divergence copies, LRU pressure evictions
            "prefix_cache": {
                "enabled": engine.prefix_cache,
                "hits": self.pc_hits,
                "misses": self.pc_misses,
                "hit_rate": self.pc_hits / max(self.pc_hits
                                               + self.pc_misses, 1),
                "prompt_tokens": self.pc_tokens,
                "prompt_tokens_saved": self.pc_saved,
                "saved_frac": self.pc_saved / max(self.pc_tokens, 1),
                "cow_copies": self.pc_cow,
                "cache_evictions": pcache.evictions if pcache else 0,
                "blocks_cached_end": (pcache.cached_blocks()
                                      if pcache else 0),
            },
            # scheduler snapshot (queue depth + pool occupancy) every
            # trace_every chunks — downsampled so long runs don't grow
            # host memory linearly in chunks decoded
            "sched_trace": self.sched_trace,
            "sched_trace_every": self.trace_every,
            "chunks_run": self.chunks_run,
            # chunked-prefill / growable-table telemetry
            "prefill_mode": engine.prefill_mode,
            "prefill_chunk": engine.prefill_chunk,
            "prefill_chunks": self.prefill_chunks,
            # distinct prefill/chunk shapes traced (bucketing collapses
            # per-prompt-length recompiles to one per kv_block bucket)
            "prefill_compiles": len(self.seen_prefill_shapes),
            "table_growths": sched.table_growths,
            # single source of truth is the scheduler: every preemption
            # (admission-pressure victim or grant-failure last resort)
            # goes through SlotScheduler.preempt
            "preemptions": sched.preemptions,
            # MI-triggered OOD escalation: requests finished on the
            # high-S sidecar runner after their carried MI crossed the
            # --escalate-mi threshold (cf. examples/blood_cell_ood.py)
            "escalation": {
                "enabled": engine.escalate_mi is not None,
                "mi_threshold": engine.escalate_mi,
                "verify_samples": engine.escalate_s,
                "escalations": self.escalations,
                "by_class": dict(self.esc_by_class),
                "tokens": self.esc_tokens,
                "skipped_too_long": self.esc_skipped,
                "decode_s": self.phase_s("serve.escalate_decode"),
                "steps": self.esc_steps,
            },
            # uncertainty-gated speculative decoding: acceptance per
            # drafted proposal, emitted tokens per round, and the
            # full-S-sample dispatch count the rounds amortize (a scan
            # chunk costs ``chunk`` full-model calls, a verify ONE)
            "spec_decode": {
                "enabled": engine.spec_decode,
                "k": engine.spec_k,
                "mi_threshold": engine.spec_mi_threshold,
                "draft_samples": engine.spec_draft_s,
                "rounds": self.spec_rounds,
                "drafted": self.spec_drafted,
                "accepted": self.spec_accepted,
                "acceptance_rate": self.spec_accepted
                / max(self.spec_drafted, 1),
                "emitted": self.spec_emitted,
                "tokens_per_round": self.spec_emitted
                / max(self.spec_rounds, 1),
                "rollbacks": self.spec_rollbacks,
                "gated_slot_rounds": self.spec_gated,
                "full_model_calls": self.full_model_calls,
                # adaptive draft depth: per-slot acceptance EMA walks k
                # inside [k_min, k_max]; with k_min == k == k_max the
                # depth is pinned and the engine is bitwise-identical
                # to the fixed-k build
                "k_min": engine.spec_k_min,
                "k_max": engine.spec_k_max,
                "k_up": self.spec_k_up,
                "k_down": self.spec_k_down,
                "round_k_min": self.spec_round_k_min,
                "round_k_max": self.spec_round_k_max,
            },
            # worst gap between consecutive decode-serving iterations
            # (over the records kept): the stall a monolithic batch
            # prefill injects mid-stream, which interleaved chunked
            # prefill bounds at ~one chunk's compute
            "decode_interarrival_p99_s": float(np.percentile(
                np.diff(served), 99, method="higher"))
            if len(served) >= 2 else 0.0,
            # the span recorder: per-phase totals over the whole run and
            # the last ITERATIONS_KEPT iteration records
            "spans": {
                "phases": {n: {"s": t[0], "count": t[1], "max_s": t[2]}
                           for n, t in self.phases.items()},
                "iterations": list(self.iterations),
                "iterations_kept": self.iterations.maxlen,
            },
            # the programs' counters: totals over the run, and the last
            # COUNTED_KEPT per-call ``CountRecord``s
            "counters": {"totals": dict(totals), "records": counts},
            "epistemic_flags": int(epi),
            "aleatoric_flags": int(alea),
            "flags_per_1k_tokens": {
                "epistemic": 1000.0 * epi / max(gen_tokens, 1),
                "aleatoric": 1000.0 * alea / max(gen_tokens, 1),
            },
            # device-side telemetry from the scan carry: per-slot totals a
            # pure-device driver could read without syncing ys.  Upper-
            # bounds the exact host accounting above (a request finishing
            # mid-chunk keeps counting until its chunk boundary).
            "device_flag_counters": {
                k: np.asarray(v).tolist() for k, v in flags.items()
            },
        }
