"""The generator: one seed, one schedule; sizes fixed by the mix."""

import json
from collections import Counter

import numpy as np
import pytest

from _paths import ROOT
from bench import traffic as T

MIXES = {m: json.loads((ROOT / f"bench/traffic/{m}.json").read_text())
         for m in ("chat", "rag")}
SEEDS = (3, 2**31 + 11)


def _key(s):
    return [(it.prompt.tobytes(), it.max_new_tokens, it.arrival_step,
             it.doc) for it in s]


@pytest.mark.parametrize("mix", sorted(MIXES))
def test_same_seed_same_schedule(mix):
    a = T.schedule(MIXES[mix], 151936, SEEDS[1])
    b = T.schedule(MIXES[mix], 151936, SEEDS[1])
    assert _key(a) == _key(b)
    assert _key(a) != _key(T.schedule(MIXES[mix], 151936, SEEDS[0]))


@pytest.mark.parametrize("mix", sorted(MIXES))
def test_lengths_inside_their_clips(mix):
    m = MIXES[mix]
    s = T.schedule(m, 151936, SEEDS[0])
    outs = [it.max_new_tokens for it in s]
    assert m["output"]["min"] <= min(outs) and max(outs) <= m["output"]["max"]
    lens = [len(it.prompt) for it in s]
    if "prompt" in m:
        lo, hi = m["prompt"]["min"], m["prompt"]["max"]
    else:
        lo = m["document"]["min"] + m["question"]["min"]
        hi = m["document"]["max"] + m["question"]["max"]
    assert lo <= min(lens) and max(lens) <= hi
    assert set(lens) <= set(T.prompt_lengths(m))
    assert all(0 <= int(t) < 151936 for it in s[:50] for t in it.prompt)


@pytest.mark.parametrize("mix", sorted(MIXES))
def test_every_seed_gets_the_same_sizes_and_gaps(mix):
    m = MIXES[mix]
    a, b = (T.schedule(m, 151936, s) for s in SEEDS)
    assert len(a) == len(b) == T.pool_size(m)
    # every block alike: the opening one and the next hold the same
    # sizes in both
    o, k = T.opening_size(m), T.block_size(m)
    for lo, hi in ((0, o), (o, o + k)):
        assert Counter(it.max_new_tokens for it in a[lo:hi]) == \
            Counter(it.max_new_tokens for it in b[lo:hi])
    assert Counter(it.max_new_tokens for it in a) == \
        Counter(it.max_new_tokens for it in b)
    opening = T.opening_size(m)
    assert [it.arrival_step for it in a[:opening]] == [0] * opening
    gaps = [np.diff([it.arrival_step for it in s[opening - 1:]]).sum()
            for s in (a, b)]
    assert abs(gaps[0] - gaps[1]) <= 1     # the same gaps, rounded
    if "prompt" in m:
        assert Counter(len(it.prompt) for it in a) == \
            Counter(len(it.prompt) for it in b)


@pytest.mark.parametrize("mix", sorted(MIXES))
def test_every_seed_gets_the_same_order_and_its_own_tokens(mix):
    a, b = (T.schedule(MIXES[mix], 151936, s) for s in SEEDS)
    assert [(len(x.prompt), x.max_new_tokens, x.arrival_step, x.doc)
            for x in a] == [(len(x.prompt), x.max_new_tokens,
                             x.arrival_step, x.doc) for x in b]
    assert all(not np.array_equal(x.prompt, y.prompt)
               for x, y in zip(a[:20], b[:20]))


def test_documents_are_asked_within_reach():
    m = MIXES["rag"]
    s = T.schedule(m, 151936, SEEDS[0])
    where = {}
    for i, it in enumerate(s):
        where.setdefault(it.doc, []).append(i)
    counts = Counter(len(v) for v in where.values())
    assert set(counts) <= set(range(m["asks"]["min"], m["asks"]["max"] + 1))
    for d, idx in where.items():
        # keys within reach of each other, so positions within about
        # reach plus the asks of the documents in between
        assert idx[-1] - idx[0] <= 3 * m["asks"]["reach"]
        first = s[idx[0]].prompt
        for i in idx[1:]:
            assert np.array_equal(s[i].prompt[:len(first) - m["question"]
                                              ["max"]],
                                  first[:len(first) - m["question"]["max"]])


def test_requests_for_counts_arrivals_and_caps_at_the_pool():
    m = MIXES["chat"]
    rate, opening = m["arrivals"]["rate_per_step"], T.opening_size(m)
    assert T.requests_for(m, 1000) == opening + int(np.ceil(rate * 1000))
    assert T.requests_for(m, 0) == opening + 1
    assert T.requests_for(m, 1e9) == T.pool_size(m)
