"""Host-side request scheduling (the serving engine's admission layer).

``Request`` is the unit of work — an explicit lifecycle state machine
(``new → queued → prefilling → decoding → finished``, with
``preempted`` re-entering at ``queued`` and ``escalated`` finishing on
the high-sample lane) whose every edge funnels through ONE audited
``transition`` method, so scheduler, engine, spec-decode rollback and
stats never mutate lifecycle fields ad hoc.  ``SlotScheduler`` maps
queued requests onto fixed decode slots through a pluggable
``policy.SchedPolicy`` (fifo = the bit-exact reference; priority adds
classes + SLO deadlines + admission-time preemption) and — on the
paged KV layout — owns the per-slot block tables over a
``block_pool.BlockAllocator``: admission, on-demand decode grants
(tables WIDEN when a grant outruns them), LRU pressure eviction
through the prefix cache, and preemption as the last resort.
Everything here is plain Python + numpy; device work (prefill, CoW
copies, table uploads) is the engine's job, driven by the records this
layer produces.
"""

from __future__ import annotations

import collections
import dataclasses
import time
from typing import Optional

import numpy as np

from repro.launch.engine.block_pool import BlockAllocator
from repro.launch.engine.policy import FifoPolicy, SchedPolicy

# the request lifecycle: every legal edge of the state machine.  One
# transition method audits against this map, so an illegal move (e.g.
# harvesting into a preempted request, finishing twice) raises instead
# of silently corrupting per-request accounting.
LIFECYCLE = {
    "new": ("queued",),
    "queued": ("prefilling",),
    "prefilling": ("decoding", "preempted"),
    "decoding": ("finished", "preempted", "escalated"),
    "preempted": ("queued",),
    "escalated": ("finished",),
    "finished": (),
}


@dataclasses.dataclass
class Request:
    """One serving request plus its accumulated results."""

    rid: int
    prompt: np.ndarray                    # (S,) int32
    max_new_tokens: int
    # priority CLASS (lower value = better class; 0 is the best) and
    # optional SLO deadline offset — only the priority policy reads
    # them, fifo traffic leaves the defaults
    priority: int = 0
    slo_s: Optional[float] = None
    # engine step count (stats.steps_run) at which this request joins
    # the queue; 0 = submitted up front.  Bursty arrival traces for the
    # priority benchmarks are built from this
    arrival_step: int = 0
    t_submit: float = 0.0
    t_finish: float = 0.0
    finish_reason: str = ""
    tokens: list = dataclasses.field(default_factory=list)
    H: list = dataclasses.field(default_factory=list)
    SE: list = dataclasses.field(default_factory=list)
    MI: list = dataclasses.field(default_factory=list)
    p_max: list = dataclasses.field(default_factory=list)
    epistemic_flags: int = 0
    aleatoric_flags: int = 0
    # MI of the most recently harvested token; the engine's speculative
    # rounds gate on it (only slots with last_mi strictly below the
    # spec threshold draft).  +inf until the first token lands — a fresh
    # or just-preempted slot never speculates before the model has shown
    # it is confident there.
    last_mi: float = float("inf")
    # the slot this request was (last) admitted into.  Telemetry, but
    # load-bearing for parity tests: operand-mode decode noise folds the
    # slot index, so two runs only produce bitwise-equal streams for
    # requests that landed in the same slot.
    slot: Optional[int] = None
    # lifecycle state + audited history of (state, timestamp) edges
    state: str = "new"
    history: list = dataclasses.field(default_factory=list)
    # wall time spent waiting in the queue (accumulates across preempt
    # re-entries); latency_s - queue_time_s is the service time
    queue_time_s: float = 0.0
    # wall time between admission to a slot and the dispatch of that
    # admission's first prefill chunk: the wait in the chunked-prefill
    # queue behind other prompts (accumulates across preempt re-entries;
    # 0 on the prefix-hit and batch-prefill paths, which prefill at
    # admission)
    prefill_wait_s: float = 0.0
    preempt_count: int = 0
    # submission order (assigned once by the scheduler); the priority
    # policy's final tie-break, so equal-priority traffic stays FIFO
    seq: int = -1
    # adaptive speculative draft depth: the slot's current k and the
    # acceptance-rate EMA driving it (engine-owned, reset on preempt)
    spec_k_cur: int = 0
    spec_ema: Optional[float] = None
    _t_queued: float = dataclasses.field(default=0.0, repr=False)
    # admission time while the prefill-queue clock runs (None: closed)
    _t_admitted: Optional[float] = dataclasses.field(default=None,
                                                     repr=False)

    def transition(self, to: str, *, reason: str = "") -> None:
        """THE audited lifecycle edge — every state change funnels
        through here.  Raises on an illegal move; applies the edge's
        side effects exactly once: ``queued`` stamps t_submit (first
        entry) and opens the queue-wait clock, ``prefilling`` closes
        it into queue_time_s and opens the prefill-queue clock
        (``prefill_started`` closes that; ``decoding`` drops it
        uncounted), ``preempted`` clears the accumulated
        output (re-admission replays from the prompt) and resets the
        spec-decode EMA, ``finished`` stamps t_finish/finish_reason."""
        if to not in LIFECYCLE[self.state]:
            raise ValueError(
                f"request {self.rid}: illegal lifecycle transition "
                f"{self.state!r} -> {to!r} (legal: "
                f"{LIFECYCLE[self.state]})")
        now = time.perf_counter()
        if to == "queued":
            if self.state == "new":
                self.t_submit = now
            self._t_queued = now
        elif to == "prefilling":
            self.queue_time_s += now - self._t_queued
            self._t_admitted = now
        elif to == "decoding":
            self._t_admitted = None
        elif to == "preempted":
            if self._t_admitted is not None:
                # preempted while still waiting for its first chunk
                self.prefill_wait_s += now - self._t_admitted
                self._t_admitted = None
            self.preempt_count += 1
            self.tokens.clear()
            for name in ("H", "SE", "MI", "p_max"):
                getattr(self, name).clear()
            self.epistemic_flags = 0
            self.aleatoric_flags = 0
            self.last_mi = float("inf")
            self.spec_k_cur = 0
            self.spec_ema = None
        elif to == "finished":
            self.t_finish = now
            self.finish_reason = reason
        self.state = to
        self.history.append((to, now))

    def prefill_started(self, t: float) -> None:
        """Close the prefill-queue clock at ``t``, the dispatch of this
        admission's first prefill chunk, into prefill_wait_s."""
        if self._t_admitted is not None:
            self.prefill_wait_s += t - self._t_admitted
            self._t_admitted = None

    @property
    def latency_s(self) -> float:
        return self.t_finish - self.t_submit

    @property
    def service_time_s(self) -> float:
        """Latency net of queue wait: admission + prefill + decode
        (+ replayed work after a preemption — the preempted tokens are
        re-decoded, which is service, not queueing)."""
        return self.latency_s - self.queue_time_s

    @property
    def was_escalated(self) -> bool:
        return any(s == "escalated" for s, _ in self.history)


@dataclasses.dataclass
class PrefixAdmit:
    """Per-slot prefix-cache admission record the engine acts on.

    ``tokens`` of the prompt are already resident in shared blocks
    mapped read-only into the slot's table; prefill runs only on the
    suffix.  ``cow`` is a pending ``(src, dst)`` device-side block copy:
    the partially-matched tail block ``src`` stays referenced until the
    engine copies it into ``dst`` (already swapped into the table) and
    calls ``finish_cow``.
    """

    tokens: int
    cow: Optional[tuple] = None


class SlotScheduler:
    """Policy-driven admission of queued requests into fixed decode
    slots.

    Pure host-side bookkeeping (no jax): ``admit`` fills free slots in
    slot order with whatever request the ``policy`` selects (fifo — the
    default and the bit-exact reference — always picks the queue
    front), ``evict`` frees a slot for reuse.  When admission fails for
    the selected request (no free slot, or not enough pool), the policy
    may name a strictly-lower-priority DECODING slot to preempt on its
    behalf; preemptions performed inside ``admit`` are surfaced through
    ``take_preempted`` so the engine can deactivate those slots before
    acting on the new placements.

    With a ``BlockAllocator`` the scheduler also owns the paged-KV block
    tables: admission switches from "is a slot free" to "are enough
    blocks free" — the PROMPT's blocks plus a WATERMARK of free headroom
    (``num_slots`` blocks by default, waived when no slot is running) so
    in-flight decoders keep growing while the queue head defers (FIFO,
    no skip-ahead).  ``grant`` maps decode blocks on demand as slots
    deepen, capped at each request's ``prompt + max_new_tokens`` budget,
    WIDENING the block tables when a grant outruns them (the table
    width is a floor, not a ceiling); a grant the pool cannot cover
    even after LRU-evicting unreferenced cached blocks returns None and
    the engine preempts the slot (``preempt``: blocks released, request
    requeued at the queue front).  ``evict`` returns every block.

    With a ``prefix_cache`` (``launch.prefix_cache.RadixPrefixCache``)
    admission first walks the radix tree: the matched prefix's blocks
    are mapped into the slot's table shared (incref, read-only), only
    the uncached span reserves fresh blocks, a token-granular partial
    match allocates one extra block for the copy-on-write of the shared
    tail, and eviction INSERTS the request's prompt blocks into the tree
    (ownership transfers to the cache) before the slot's decref.  Under
    pool pressure admission asks the cache to LRU-evict unreferenced
    blocks before deferring.
    """

    def __init__(self, num_slots: int,
                 allocator: Optional[BlockAllocator] = None,
                 table_width: int = 0, prefix_cache=None,
                 watermark: Optional[int] = None,
                 policy: Optional[SchedPolicy] = None):
        self.slots: list[Optional[Request]] = [None] * num_slots
        self.queue: collections.deque[Request] = collections.deque()
        self.allocator = allocator
        self.prefix_cache = prefix_cache
        self.policy = policy if policy is not None else FifoPolicy()
        self.preemptions = 0
        self._seq = 0
        self._admit_preempted: list[tuple[int, Request]] = []
        # free-block headroom admission must leave for running decoders'
        # on-demand grants (now that their budgets are no longer
        # reserved up front); waived when nothing is running, so an
        # empty engine admits exactly what fits
        self.watermark = num_slots if watermark is None else watermark
        self.table_growths = 0
        if prefix_cache is not None and allocator is None:
            raise ValueError("prefix cache requires a BlockAllocator")
        if allocator is not None:
            if table_width < 1:
                raise ValueError("paged scheduling needs table_width "
                                 "(initial blocks per slot)")
            self.block_tables = np.full((num_slots, table_width), -1,
                                        np.int32)
            self._slot_blocks: list[list[int]] = \
                [[] for _ in range(num_slots)]
            # decode blocks still grantable per slot (budget, NOT an
            # allocator reservation): blocks_for(prompt + max_new) minus
            # what the slot already holds
            self._slot_budget = [0] * num_slots
            self._slot_prefix: list[Optional[PrefixAdmit]] = \
                [None] * num_slots
            self._slot_cow_src: list[Optional[int]] = [None] * num_slots
            # bumped on every table mutation (admit/grant/evict) so the
            # engine only re-uploads the device table when it changed
            self.table_version = 0
            self.table_growths = 0

    def submit(self, req: Request) -> None:
        if req.seq < 0:
            req.seq = self._seq
            self._seq += 1
        req.transition("queued")
        self.queue.append(req)

    def _pop(self, qi: int) -> Request:
        if qi == 0:
            return self.queue.popleft()
        req = self.queue[qi]
        del self.queue[qi]
        return req

    def _ensure_width(self, want: int) -> None:
        """Widen the host block tables to hold ``want`` blocks per slot
        (doubling, -1-padded).  The engine notices via table_version:
        the device table re-uploads at the new shape and the decode
        scan retraces once per growth."""
        w = self.block_tables.shape[1]
        if want <= w:
            return
        grown = np.full((len(self.slots), max(want, 2 * w)), -1, np.int32)
        grown[:, :w] = self.block_tables
        self.block_tables = grown
        self.table_growths += 1
        self.table_version += 1

    def _try_reserve(self, need: int, protect: frozenset) -> bool:
        """Reserve ``need`` blocks for an admission, LRU-evicting
        cached-but-unreferenced blocks first when the pool is short
        (``protect`` pins the hit being admitted).  On top of ``need``
        the pool must keep ``watermark`` blocks free for running slots'
        decode grants — waived when no slot is running (nothing to
        starve, and the head request could otherwise never admit)."""
        alloc = self.allocator
        wm = self.watermark if any(r is not None for r in self.slots) \
            else 0
        short = need + wm - alloc.available()
        if short > 0 and self.prefix_cache is not None:
            self.prefix_cache.evict_lru(short, protect=protect)
        if alloc.available() < need + wm:
            return False
        return alloc.reserve(need)

    def _admit_paged(self, slot: int, qi: int = 0) -> Optional[Request]:
        alloc = self.allocator
        req = self.queue[qi]
        P = len(req.prompt)
        nprompt = alloc.blocks_for(P)
        # grant cap, NOT a reservation: decode blocks are drawn from the
        # pool on demand, so admission only needs the prompt's blocks
        total = alloc.blocks_for(P + req.max_new_tokens)
        hit = self.prefix_cache.match(req.prompt) \
            if self.prefix_cache is not None else None
        if hit is not None and hit.tokens:
            # uncached span + one extra block when the shared tail needs
            # a copy-on-write duplicate before this slot writes into it
            need = nprompt - len(hit.blocks) + (1 if hit.partial else 0)
            if not self._try_reserve(need, frozenset(hit.blocks)):
                # liveness: when no live slot will ever free a block
                # (everything left is cache-held, pinned by this very
                # hit), fall back to a cold admission rather than
                # deadlocking on the hit's own protection
                if alloc.in_use > self.prefix_cache.cached_blocks():
                    return None           # a running slot will free some
                hit = None
        if hit is None or not hit.tokens:
            if not self._try_reserve(nprompt, frozenset()):
                return None               # pool exhausted: defer
            self._pop(qi)
            ids = alloc.alloc(nprompt)
            if self.prefix_cache is not None:
                self._slot_prefix[slot] = PrefixAdmit(tokens=0)
        else:
            self._pop(qi)
            self.prefix_cache.lock(hit)   # slot refs on shared blocks
            ids = list(hit.blocks)
            cow = None
            if hit.partial:
                [dst] = alloc.alloc(1)
                cow = (ids[-1], dst)      # src stays ref'd: finish_cow
                self._slot_cow_src[slot] = ids[-1]
                ids[-1] = dst
            ids += alloc.alloc(nprompt - len(hit.blocks))
            self._slot_prefix[slot] = PrefixAdmit(tokens=hit.tokens,
                                                  cow=cow)
        self._slot_budget[slot] = total - nprompt
        self._slot_blocks[slot] = ids
        self._ensure_width(len(ids))
        self.block_tables[slot, :] = -1
        self.block_tables[slot, :len(ids)] = ids
        self.table_version += 1
        return req

    def prefix_admit(self, slot: int) -> Optional[PrefixAdmit]:
        """The slot's prefix-cache admission record (None when the cache
        is off)."""
        return self._slot_prefix[slot] if self.prefix_cache is not None \
            else None

    def finish_cow(self, slot: int) -> None:
        """The engine copied the shared tail block device-side; release
        this slot's reference on the source (the tree keeps its own)."""
        src = self._slot_cow_src[slot]
        if src is None:
            raise ValueError(f"no pending CoW on slot {slot}")
        self._slot_cow_src[slot] = None
        self.allocator.free([src])

    def _preempt_for(self, candidate: Request) -> bool:
        """Ask the policy for a decoding slot to preempt so
        ``candidate`` can admit; False defers the candidate instead.
        Only DECODING occupants are offered (a preempted decode replays
        bit-exactly from its prompt; aborting a mid-prefill walk would
        throw away chunks already paid for), and every preemption
        strictly shrinks that set, so the admit loop terminates."""
        running = [(i, r) for i, r in enumerate(self.slots)
                   if r is not None and r.state == "decoding"]
        victim = self.policy.victim(candidate, running)
        if victim is None:
            return False
        self._admit_preempted.append((victim, self.preempt(victim)))
        return True

    def take_preempted(self) -> list[tuple[int, Request]]:
        """(slot, request) pairs the policy preempted inside the last
        ``admit`` call; the engine must deactivate those slots before
        acting on the new placements."""
        out = self._admit_preempted
        self._admit_preempted = []
        return out

    def admit(self) -> list[tuple[int, Request]]:
        placed = []
        self._admit_preempted = []
        while self.queue:
            qi = self.policy.select(self.queue)
            if qi is None:
                break
            candidate = self.queue[qi]
            slot = next((i for i, r in enumerate(self.slots)
                         if r is None), None)
            if slot is None:
                # every slot busy: the policy may preempt a strictly
                # lower-priority decoding slot for the candidate (fifo
                # never does — all slots busy simply ends admission)
                if not self._preempt_for(candidate):
                    break
                continue
            if self.allocator is not None:
                req = self._admit_paged(slot, qi)
                if req is None:
                    # pool short for the selected request: preempt for
                    # it (freed blocks retry the admission) or defer
                    if not self._preempt_for(candidate):
                        break
                    continue
            else:
                req = self._pop(qi)
            req.slot = slot
            req.transition("prefilling")
            self.slots[slot] = req
            placed.append((slot, req))
        return placed

    def grant(self, slot: int, target_len: int) -> Optional[list[int]]:
        """Map blocks so slot ``slot`` can hold ``target_len`` tokens.

        Draws from the pool on demand, capped at the request's
        ``prompt + max_new_tokens`` budget (junk steps a finished
        request runs until its chunk boundary drop against the unmapped
        tail instead of consuming pool) and widening the block tables
        when the target outruns them.  Returns the granted ids ([] when
        nothing is needed) or None when the pool cannot cover the
        shortfall even after LRU-evicting cached-but-unreferenced
        prefix blocks — the engine preempts the slot."""
        alloc = self.allocator
        have = len(self._slot_blocks[slot])
        want = min(alloc.blocks_for(target_len),
                   have + self._slot_budget[slot])
        if want <= have:
            return []
        n = want - have
        if alloc.available() < n and self.prefix_cache is not None:
            # a cached-but-unreferenced prefix must never starve a
            # running decoder (or livelock a deferred admission behind
            # it): reclaim before giving up
            self.prefix_cache.evict_lru(n - alloc.available(),
                                        protect=frozenset())
        if not alloc.reserve(n):
            return None
        ids = alloc.alloc(n)
        self._slot_budget[slot] -= n
        self._ensure_width(want)
        self.block_tables[slot, have:want] = ids
        self._slot_blocks[slot].extend(ids)
        self.table_version += 1
        return ids

    def rollback(self, slot: int, target_len: int) -> int:
        """Shrink a slot back to ``target_len`` tokens after a partially
        rejected speculative round: decode-granted blocks beyond
        ``blocks_for(target_len)`` return to the pool and re-credit the
        slot's grant budget.  Only ever drops blocks this slot drew via
        ``grant`` AFTER its prompt landed (target_len >= prompt length
        + 1 by construction), so every freed block is exclusively owned
        (refcount 1, never a shared prefix-cache block).  Junk KV the
        draft wrote into the kept tail block is masked by decode
        attention (positions >= len) and overwritten by later steps.
        Returns the number of blocks released."""
        alloc = self.allocator
        if alloc is None:
            return 0
        keep = alloc.blocks_for(target_len)
        blocks = self._slot_blocks[slot]
        if keep >= len(blocks):
            return 0
        drop = blocks[keep:]
        del blocks[keep:]
        alloc.free(drop)
        self._slot_budget[slot] += len(drop)
        self.block_tables[slot, keep:] = -1
        self.table_version += 1
        return len(drop)

    def preempt(self, slot: int) -> Request:
        """Evict a slot (growth grant failed, or the policy claimed it
        for a better candidate) and requeue its request at the queue
        FRONT (FIFO order preserved; the priority policy re-ranks
        anyway).  The audited ``preempted`` transition clears the
        request's accumulated output — on readmission it restarts from
        its prompt (depth-keyed decode noise replays the aborted
        stream bit-exactly when it lands in the same slot)."""
        req = self.evict(slot)
        req.transition("preempted")
        req.transition("queued")
        self.queue.appendleft(req)
        self.preemptions += 1
        return req

    def evict(self, slot: int) -> Request:
        req = self.slots[slot]
        if req is None:
            raise ValueError(f"evict of empty slot {slot}")
        self.slots[slot] = None
        if self.allocator is not None:
            if self.prefix_cache is not None:
                # adopt the prompt's blocks into the radix tree BEFORE
                # the slot lets go: chunks already cached share the
                # existing nodes, fresh ones transfer to the cache
                nprompt = self.allocator.blocks_for(len(req.prompt))
                self.prefix_cache.insert(req.prompt,
                                         self._slot_blocks[slot][:nprompt])
                if self._slot_cow_src[slot] is not None:
                    self.allocator.free([self._slot_cow_src[slot]])
                    self._slot_cow_src[slot] = None
                self._slot_prefix[slot] = None
            self.allocator.free(self._slot_blocks[slot])
            self._slot_blocks[slot] = []
            self._slot_budget[slot] = 0
            self.block_tables[slot, :] = -1
            self.table_version += 1
        return req

    def pool_stats(self) -> dict:
        """Queue depth + block-pool occupancy snapshot (free / reserved
        / cached / in-use counts), so allocator behavior is observable
        per chunk without a debugger."""
        out = {"queue_depth": len(self.queue),
               "active_slots": sum(r is not None for r in self.slots)}
        if self.allocator is not None:
            a = self.allocator
            out.update(
                blocks_free=len(a._free), blocks_reserved=a._reserved,
                blocks_in_use=a.in_use,
                blocks_utilization=a.utilization(),
                blocks_cached=(self.prefix_cache.cached_blocks()
                               if self.prefix_cache is not None else 0))
        return out

    def mapped_blocks(self, slot: int) -> int:
        """Physical blocks currently mapped into the slot's table (what
        the block-sparse decode kernel can actually read)."""
        return len(self._slot_blocks[slot])

    def active(self) -> list[tuple[int, Request]]:
        return [(i, r) for i, r in enumerate(self.slots) if r is not None]

    def has_work(self) -> bool:
        return bool(self.queue) or any(r is not None for r in self.slots)
