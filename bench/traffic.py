"""The one traffic generator: a mix file's parameters in, requests out.

A mix is an open loop on the engine's step clock: request ``k`` joins the
queue once the engine has run ``arrival_step`` decode steps, with
exponential gaps at ``arrivals.rate_per_step``.  An opening block of
``arrivals.opening`` requests (documents, for a document mix) is due at
step 0.

Every seed gets the same schedule: the same sizes and gaps in the same
order, drawn once from ``ORDER_SEED``; the seed draws the token ids.  The
schedule is the opening block, then ``blocks`` blocks of ``block``
requests (of ``block`` documents' asks for a document mix); every block
holds the same sizes, the distribution's quantiles at
``(i + 0.5) / block``, and the same gaps in its own order, so any
stretch of a few blocks carries the same work.  A tail latency over a
window of some tens of requests depends on the order of its arrivals
(a burst of long prompts queues), so the order is not the seed's: two
seeds then differ by what two runs of one seed differ by.

A prompt is either one length distribution (``prompt``) or a document
followed by a question (``document``, ``question``, ``asks``): each
document is asked ``asks.min``..``asks.max`` times, and its asks fall
within ``asks.reach`` requests of each other, so that a prefix cache
that holds that many documents can serve the repeats.
"""

from __future__ import annotations

import dataclasses
import math
from statistics import NormalDist

import numpy as np

# draws every block's order of sizes and gaps, whatever the run's seed
ORDER_SEED = 0


@dataclasses.dataclass
class Item:
    """One request of a schedule, before it becomes an engine Request."""

    prompt: np.ndarray            # (P,) int32 token ids
    max_new_tokens: int
    arrival_step: int
    doc: int = -1                 # document id (-1: no shared prefix)


def quantiles(dist: dict, n: int) -> np.ndarray:
    """``n`` integer lengths at the quantiles ``(i + 0.5) / n`` of
    ``dist`` (``lognormal``: median, sigma; ``uniform``: min..max
    inclusive), clipped to ``[min, max]``."""
    u = (np.arange(n) + 0.5) / n
    lo, hi = int(dist["min"]), int(dist["max"])
    if dist["dist"] == "lognormal":
        z = np.array([NormalDist().inv_cdf(x) for x in u])
        v = np.rint(dist["median"] * np.exp(dist["sigma"] * z))
    elif dist["dist"] == "uniform":
        v = lo + np.floor(u * (hi - lo + 1))
    else:
        raise ValueError(f"unknown length distribution {dist['dist']!r}")
    return np.clip(v, lo, hi).astype(np.int64)


def _gaps(rate: float, n: int) -> np.ndarray:
    u = (np.arange(n) + 0.5) / n
    return -np.log1p(-u) / rate


def pool_size(mix: dict) -> int:
    """Requests in the mix's pool (an upper bound on any schedule)."""
    return block_size(mix, int(mix["arrivals"]["opening"])) \
        + int(mix["blocks"]) * block_size(mix)


def block_size(mix: dict, k: int | None = None) -> int:
    """Requests in a block of ``k`` (default ``block``) requests, or of
    ``k`` documents' asks for a document mix."""
    k = int(mix["block"]) if k is None else k
    if "document" in mix:
        return int(sum(quantiles(_asks(mix), k)))
    return k


def _asks(mix: dict) -> dict:
    return {"dist": "uniform", "min": mix["asks"]["min"],
            "max": mix["asks"]["max"]}


def _block(mix: dict, k: int, rng: np.random.Generator,
           ids: np.random.Generator, vocab: int, first_doc: int):
    """One block of ``k`` requests (documents) in arrival order: prompts
    and document ids.  ``rng`` orders the sizes, ``ids`` draws the
    tokens."""
    if "document" not in mix:
        lens = rng.permutation(quantiles(mix["prompt"], k))
        return [ids.integers(0, vocab, int(p), dtype=np.int32)
                for p in lens], [-1] * k
    reach = mix["asks"]["reach"]
    doc_lens = rng.permutation(quantiles(mix["document"], k))
    counts = rng.permutation(quantiles(_asks(mix), k))
    docs = [ids.integers(0, vocab, int(n), dtype=np.int32)
            for n in doc_lens]
    # a document's asks sit at keys start + U[0, reach): sorted, they
    # interleave with their neighbours' asks but stay within reach
    keys, owner, start = [], [], 0
    for d, c in enumerate(counts):
        keys.extend(start + rng.uniform(0, reach, int(c)))
        owner.extend([d] * int(c))
        start += int(c)
    order = np.argsort(np.asarray(keys), kind="stable")
    owner = [owner[i] for i in order]
    q_lens = rng.permutation(quantiles(mix["question"], len(owner)))
    prompts = [np.concatenate([docs[d], ids.integers(
        0, vocab, int(q), dtype=np.int32)]) for d, q in zip(owner, q_lens)]
    return prompts, [first_doc + d for d in owner]


def schedule(mix: dict, vocab: int, seed: int) -> list[Item]:
    """The whole pool of the mix in arrival order, its tokens drawn from
    ``seed``: the opening block due at step 0, then ``blocks`` blocks,
    each the same sizes and gaps in its own order."""
    rate = float(mix["arrivals"]["rate_per_step"])
    items, step = [], 0.0
    sizes = [int(mix["arrivals"]["opening"])] \
        + [int(mix["block"])] * int(mix["blocks"])
    for b, k in enumerate(sizes):
        rng = np.random.default_rng([ORDER_SEED, b])
        ids = np.random.default_rng([seed, b])
        prompts, owner = _block(mix, k, rng, ids, vocab, sum(sizes[:b]))
        n = len(prompts)
        outs = rng.permutation(quantiles(mix["output"], n))
        gaps = rng.permutation(_gaps(rate, n)) if b else np.zeros(n)
        for p, o, d, g in zip(prompts, outs, owner, gaps):
            step += g
            items.append(Item(prompt=p, max_new_tokens=int(o),
                              arrival_step=int(round(step)), doc=d))
    return items


def prompt_lengths(mix: dict) -> list[int]:
    """Every prompt length any seed's schedule can hold: a block's and
    the opening block's lengths, or each such document length with each
    question length."""
    ks = (int(mix["block"]), int(mix["arrivals"]["opening"]))
    if "document" not in mix:
        return sorted({int(n) for k in ks
                       for n in quantiles(mix["prompt"], k)})
    docs = {int(n) for k in ks for n in quantiles(mix["document"], k)}
    qs = {int(n) for k in ks for n in quantiles(
        mix["question"], int(sum(quantiles(_asks(mix), k))))}
    return sorted({d + q for d in docs for q in qs})


def longest_request(mix: dict) -> int:
    """Largest prompt + output budget of any request of the pool."""
    prompt = max(prompt_lengths(mix))
    return prompt + int(mix["output"]["max"])


def opening_size(mix: dict) -> int:
    """Requests due at step 0."""
    return block_size(mix, int(mix["arrivals"]["opening"]))


def requests_for(mix: dict, steps: float) -> int:
    """Requests that arrive within ``steps`` decode steps, opening ones
    included (at least one more than the opening)."""
    first = opening_size(mix)
    n = first + math.ceil(float(mix["arrivals"]["rate_per_step"]) * steps)
    return min(max(n, first + 1), pool_size(mix))
