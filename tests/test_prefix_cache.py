"""Copy-on-write radix prefix cache over the paged KV pool (ISSUE 4).

The acceptance contract:

  * prefix-hit decode is BIT-EXACT vs ``prefix_cache=False`` in
    operand-entropy mode on staggered shared-prefix traffic, including
    post-divergence copy-on-write;
  * the suffix prefill reproduces the cold flash-attention prefill's
    suffix KV bit for bit (equal reduction extents);
  * refcount churn leaks nothing — the randomized admit/evict/CoW leak
    fuzz lives in test_block_fuzz.py;
  * hit/miss/saved-token accounting is exact;
  * LRU eviction only touches cached-but-unreferenced blocks.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.registry import get_config, reduced
from repro.launch.prefix_cache import RadixPrefixCache
from repro.launch.serve import (BlockAllocator, Request, ServeEngine,
                                SlotScheduler)
from repro.models import registry as M

from conftest import family_setup
from conftest import make_request as _req


@pytest.fixture(scope="module")
def setup():
    # dense cfg/params shared with the other engine modules; this module
    # additionally needs shared-prefix prompt material, so it overrides
    # the conftest fixture with a wider tuple
    cfg, params, _ = family_setup("dense")
    key = jax.random.key(0)
    shared = np.asarray(
        jax.random.randint(key, (20,), 0, cfg.vocab_size), np.int32)
    tails = np.asarray(
        jax.random.randint(jax.random.key(1), (8, 8), 0, cfg.vocab_size),
        np.int32)
    return cfg, params, shared, tails


# ---------------------------------------------------------------------------
# allocator refcounts
# ---------------------------------------------------------------------------

class TestAllocatorRefcounts:
    def test_incref_keeps_block_alive_through_one_free(self):
        a = BlockAllocator(4, block_size=2)
        a.reserve(2)
        ids = a.alloc(2)
        assert all(a.refcount(i) == 1 for i in ids)
        a.incref(ids)                       # a second holder
        a.free(ids)                         # first holder lets go
        assert a.in_use == 2                # still alive
        a.free(ids)                         # last holder
        assert a.in_use == 0
        assert a.available() == 4

    def test_incref_of_free_block_raises(self):
        a = BlockAllocator(4, block_size=2)
        with pytest.raises(ValueError, match="incref of free"):
            a.incref([0])

    def test_free_below_zero_is_double_free(self):
        a = BlockAllocator(4, block_size=2)
        a.reserve(1)
        ids = a.alloc(1)
        a.free(ids)
        with pytest.raises(ValueError, match="double free"):
            a.free(ids)


# ---------------------------------------------------------------------------
# the radix tree (host-only, no engine)
# ---------------------------------------------------------------------------

def _tree(num_blocks=16, bs=4):
    a = BlockAllocator(num_blocks, bs)
    return a, RadixPrefixCache(a, bs)


def _take(alloc, n):
    alloc.reserve(n)
    return alloc.alloc(n)


class TestRadixTree:
    def test_match_full_blocks_then_partial_tail(self):
        a, c = _tree()
        seq = list(range(10))               # 2 full blocks + 2-token tail
        blocks = _take(a, 3)
        assert c.insert(seq, blocks) == 3
        a.free(blocks)                      # tree holds them now
        assert a.in_use == 3 == c.cached_blocks()

        hit = c.match(seq)                  # identical prompt: full hit
        assert hit.tokens == 10 and hit.blocks == blocks and hit.partial

        hit = c.match(seq[:8])              # block-aligned prefix
        assert hit.tokens == 8 and not hit.partial
        assert hit.blocks == blocks[:2]

        hit = c.match(seq[:6] + [99, 99])   # diverges mid-block 2
        assert hit.tokens == 6 and hit.partial
        assert hit.blocks == blocks[:2]

        assert c.match([77, 78]).tokens == 0

    def test_insert_shares_existing_nodes(self):
        a, c = _tree()
        common = list(range(8))
        b1 = _take(a, 3)
        c.insert(common + [50, 51], b1)
        a.free(b1)
        # same common prefix, different tail: only the tail is adopted
        b2 = _take(a, 3)
        adopted = c.insert(common + [60, 61], b2)
        assert adopted == 1
        a.free(b2)                          # unadopted copies die here
        assert a.in_use == 4 == c.cached_blocks()
        # both tails reachable by a token-granular walk
        assert c.match(common + [60, 61]).tokens == 10
        assert c.match(common + [50, 51]).tokens == 10

    def test_lru_eviction_respects_refcounts_and_protection(self):
        a, c = _tree(num_blocks=8)
        b1 = _take(a, 2)
        c.insert(list(range(8)), b1)        # older
        b2 = _take(a, 2)
        c.insert(list(range(100, 108)), b2)  # newer
        a.free(b1)
        a.free(b2)
        a.incref([b2[1]])                   # a slot still maps this one
        # oldest unreferenced leaf goes first: b1's tail
        assert c.evict_lru(1) == 1
        assert a.refcount(b1[1]) == 0
        # b2's tail is slot-referenced -> only interior-turned-leaf
        # b1[0] is evictable; protection can pin it too
        assert c.evict_lru(5, protect=frozenset([b1[0]])) == 0
        assert c.evict_lru(5) == 1          # b1[0] once unprotected
        assert c.cached_blocks() == 2       # b2 survives (tail ref'd)

    def test_clear_releases_every_tree_reference(self):
        a, c = _tree()
        blocks = _take(a, 4)
        c.insert(list(range(16)), blocks)
        a.free(blocks)
        assert a.in_use == 4
        assert c.clear() == 4
        assert a.in_use == 0 and c.cached_blocks() == 0


# ---------------------------------------------------------------------------
# scheduler integration + refcount churn fuzz
# ---------------------------------------------------------------------------

def _prefix_sched(num_slots=2, num_blocks=16, bs=4, width=6):
    a = BlockAllocator(num_blocks, bs)
    cache = RadixPrefixCache(a, bs)
    return SlotScheduler(num_slots, allocator=a, table_width=width,
                         prefix_cache=cache), cache


class TestPrefixScheduler:
    def test_hit_maps_shared_blocks_and_cow_swaps_the_tail(self):
        s, cache = _prefix_sched()
        s.submit(_req(0, list(range(10)), 4))
        [(slot, _)] = s.admit()
        assert s.prefix_admit(slot).tokens == 0     # cold miss
        s.evict(slot)                               # donates 3 blocks
        assert cache.cached_blocks() == 3
        cached_row = list(s.block_tables[slot])     # snapshot: all -1
        assert all(b == -1 for b in cached_row)

        # 9 shared tokens then divergence mid-tail-block: full-block hit
        # of 8 plus a token-granular partial match of 1 into the tail
        s.submit(_req(1, list(range(9)) + [70, 71], 4))
        [(slot, _)] = s.admit()
        info = s.prefix_admit(slot)
        assert info.tokens == 9 and info.cow is not None
        src, dst = info.cow
        assert s.allocator.refcount(src) == 2       # tree + this slot
        assert s.block_tables[slot][2] == dst       # table swapped
        s.finish_cow(slot)
        assert s.allocator.refcount(src) == 1       # tree only
        s.evict(slot)
        assert s.allocator.in_use == cache.cached_blocks()

    # randomized CoW/refcount churn lives in test_block_fuzz.py now: the
    # property-based interpreter there checks the exact refcount identity
    # (slots + tree + pending CoW sources) after every op


# ---------------------------------------------------------------------------
# suffix prefill numerics
# ---------------------------------------------------------------------------

class TestSuffixPrefill:
    def test_suffix_kv_bit_exact_vs_cold_prefill(self, setup):
        """prefill_suffix over a cached prefix must reproduce the cold
        full-prompt prefill's suffix KV bit for bit (the equal-
        reduction-extent argument in layers.apply_attention_suffix)."""
        cfg, params, shared, tails = setup
        prompt = np.concatenate([shared, tails[0][:6]])
        P0, P = 20, len(prompt)
        _, cold = M.prefill(params, cfg, jnp.asarray(prompt)[None], P)
        _, pre = M.prefill(params, cfg, jnp.asarray(prompt[:P0])[None],
                           P0)
        pad = [(0, 0), (0, 0), (0, 32 - P0), (0, 0), (0, 0)]
        strips = {n: jnp.pad(pre[n], pad) for n in ("k", "v")}
        _, sub = M.prefill_suffix(params, cfg,
                                  jnp.asarray(prompt[P0:])[None],
                                  strips, P0)
        assert int(sub["len"][0]) == P
        for name in ("k", "v"):
            np.testing.assert_array_equal(
                np.asarray(cold[name][:, :, P0:P]),
                np.asarray(sub[name]))

    def test_unsupported_family_raises(self):
        cfg = reduced(get_config("zamba2_7b"))
        with pytest.raises(ValueError, match="cannot prefix-share"):
            M.prefill_suffix(None, cfg, None, None, 0)


# ---------------------------------------------------------------------------
# the engine: bit-exactness, CoW divergence, accounting
# ---------------------------------------------------------------------------

def _streams_equal(a, b):
    return (a.tokens == b.tokens
            and np.array_equal(np.asarray(a.H, np.float32),
                               np.asarray(b.H, np.float32))
            and np.array_equal(np.asarray(a.SE, np.float32),
                               np.asarray(b.SE, np.float32))
            and np.array_equal(np.asarray(a.MI, np.float32),
                               np.asarray(b.MI, np.float32)))


class TestPrefixEngine:
    def _shared_requests(self, shared, tails, n=6):
        # 20 shared tokens then a unique tail: with kv_block=8 the
        # divergence lands mid-block -> every hit takes the CoW path
        return [_req(i, np.concatenate([shared, tails[i][:6]]), 6)
                for i in range(n)]

    def test_cow_divergence_parity_bit_exact_vs_cold(self, setup):
        """Staggered shared-prefix traffic through prefix_cache on/off:
        identical token AND uncertainty streams, with real hits and real
        copy-on-write divergences on the cached path."""
        cfg, params, shared, tails = setup
        kw = dict(num_slots=2, max_len=32, chunk=4, kv_layout="paged",
                  kv_block=8)
        cold = ServeEngine(params, cfg, **kw)
        rc = cold.run(self._shared_requests(shared, tails))
        warm = ServeEngine(params, cfg, **kw, prefix_cache=True)
        rw = warm.run(self._shared_requests(shared, tails))
        for a, b in zip(rc["requests"], rw["requests"]):
            assert _streams_equal(a, b), f"request {a.rid} diverged"
        pc = rw["prefix_cache"]
        assert pc["hits"] > 0 and pc["cow_copies"] > 0
        assert pc["prompt_tokens_saved"] > 0
        assert rc["prefix_cache"]["enabled"] is False

    def test_full_prompt_hit_accounting_is_exact(self, setup):
        """S-sample fanout (identical prompts): the first num_slots
        admissions miss (cache fills at eviction), every later one is a
        full-prompt hit that skips prefill entirely."""
        cfg, params, shared, tails = setup
        prompt = np.concatenate([shared, tails[0][:6]])   # 26 tokens
        n, slots = 8, 2
        engine = ServeEngine(params, cfg, num_slots=slots, max_len=40,
                             chunk=4, kv_layout="paged", kv_block=8,
                             kv_blocks=20, prefix_cache=True)
        res = engine.run([_req(i, prompt.copy(), 6) for i in range(n)])
        pc = res["prefix_cache"]
        assert pc["misses"] == slots
        assert pc["hits"] == n - slots
        assert pc["hit_rate"] == (n - slots) / n
        assert pc["prompt_tokens"] == n * len(prompt)
        assert pc["prompt_tokens_saved"] == (n - slots) * len(prompt)
        assert pc["saved_frac"] == pytest.approx((n - slots) / n)
        # 26 % 8 != 0: every full hit CoWs the partial tail block
        assert pc["cow_copies"] == n - slots
        assert all(len(r.tokens) == 6 for r in res["requests"])

    def test_sched_trace_exposes_pool_occupancy_per_chunk(self, setup):
        cfg, params, shared, tails = setup
        engine = ServeEngine(params, cfg, num_slots=2, max_len=32,
                             chunk=4, kv_layout="paged", kv_block=8,
                             prefix_cache=True)
        res = engine.run(self._shared_requests(shared, tails, n=4))
        trace = res["sched_trace"]
        assert len(trace) > 0
        for snap in trace:
            for key in ("queue_depth", "active_slots", "blocks_free",
                        "blocks_reserved", "blocks_cached",
                        "blocks_in_use"):
                assert key in snap
            assert snap["blocks_in_use"] + snap["blocks_free"] == \
                engine.kv_blocks
        # leak-check invariant incl. cached refcounts held at drain
        # (ServeEngine.run raises otherwise); what remains is cache-owned
        assert res["prefix_cache"]["blocks_cached_end"] > 0

    def test_dense_layout_rejects_prefix_cache(self, setup):
        cfg, params, _, _ = setup
        with pytest.raises(ValueError, match="paged"):
            ServeEngine(params, cfg, num_slots=2, max_len=32,
                        kv_layout="dense", prefix_cache=True)

    def test_unsupported_family_serves_cold(self):
        """hybrid prompt state is not a pure function of the KV blocks
        (its SSM/conv recurrent state is not paged), so the engine
        silently serves it cold — same fallback convention as ssm's
        dense layout."""
        cfg = dataclasses.replace(reduced(get_config("zamba2_7b")),
                                  head_entropy="operand")
        params = M.init_params(jax.random.key(1), cfg)
        engine = ServeEngine(params, cfg, num_slots=2, max_len=24,
                             chunk=4, kv_layout="paged", kv_block=8,
                             prefix_cache=True)
        assert engine.prefix_cache is False
        toks = np.asarray(jax.random.randint(jax.random.key(2), (2, 8),
                                             0, cfg.vocab_size), np.int32)
        res = engine.run([_req(i, toks[i], 4) for i in range(2)])
        assert res["prefix_cache"]["enabled"] is False
        assert all(len(r.tokens) == 4 for r in res["requests"])
