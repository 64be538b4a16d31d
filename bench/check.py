"""Decides ``correct``: the served tokens and triplets against the reference.

After the window has closed and the program's state is freed, a sample
of the finished requests (drawn from the seed, the longest always in
it) is run through the configuration's plain reference
(``bench/references/<reference>.py``, handed the configuration file's
dict, from which it reads its own sizes), one request at a time over
its whole sequence: the prompt, the prompt's last token
again (the engine feeds it as the first decode input), then every
served token but the last.  At each served position the reference gives
the float32 mean logits, and the head's estimators averaged over
``REF_SETS`` sets of S draws of its own.

Readings at each served position:

* ``gap``: how far the served token's reference mean logit lies below
  the reference's best (nats).  The program picks the argmax of the
  mean of S noisy softmaxes, so a sound run reads the head's sampling
  noise here, not 0;
* ``dlogp``: the served top probability's log against the reference's
  softmax of the mean logits at the served token;
* ``dH``, ``dSE``, ``dMI``: |served estimator - the reference's
  expectation of it|.  Over a vocabulary of 10^5 entries these average
  out, so a sound run reads little more than the S-draw noise;
* ``H_bias``, ``SE_bias``, ``MI_bias``: the same differences with their
  sign, averaged over the sample: an estimator that is off in one
  direction (fewer draws, a changed formula) shows here;
* ``triplet_bad``: the served positions whose triplet breaks what the
  estimators guarantee: finite, ``0 <= SE``, ``0 <= MI <= H <= log V``
  and ``MI = max(H - SE, 0)``, each within ``NATS_SLACK``.

Each of the first five readings gives two numbers, its widest (``_max``)
and its mean (``_mean``) over the sample.  A cell's limits file names the numbers it
compares, each with its limit and the readings it was set from; the
other numbers are printed beside them as readings only.  The draws
never leave the kernel; the comparison reaches the head through the
token it picks and the estimators, whose expectations the reference
knows from the same mu, sigma and hidden state.
"""

from __future__ import annotations

import numpy as np

REF_SETS = 4          # sets of S draws behind the reference's expectation
SEQ_BUCKET = 512      # sequences are right-padded to a multiple of this
POS_BLOCK = 16        # head positions per reference call
READINGS = ("gap", "dlogp", "dH", "dSE", "dMI")
ESTIMATORS = ("H", "SE", "MI")
# float32 rounding slack on the triplet's guarantees (sums over 10^5
# terms), as the bring-up check allows
NATS_SLACK = 1e-3


def triplet_bad(h, se, mi, vocab: int) -> np.ndarray:
    """Per position: whether (H, SE, MI) breaks its guarantees."""
    h, se, mi = (np.asarray(a, np.float64) for a in (h, se, mi))
    ok = np.isfinite(h) & np.isfinite(se) & np.isfinite(mi)
    ok &= (se >= -NATS_SLACK) & (mi >= 0) & (mi <= h + NATS_SLACK)
    ok &= h <= np.log(vocab) + NATS_SLACK
    ok &= np.abs(mi - np.maximum(h - se, 0.0)) <= NATS_SLACK
    return ~ok


def fed_tokens(prompt, tokens) -> np.ndarray:
    """The sequence the engine's model sees for one served request."""
    return np.concatenate([np.asarray(prompt, np.int32),
                           np.asarray(prompt[-1:], np.int32),
                           np.asarray(tokens[:-1], np.int32)])


def sample(requests: list, seed: int, k: int) -> list:
    """``k`` of ``requests`` drawn from ``seed``, the longest included."""
    if len(requests) <= k:
        return list(requests)
    longest = max(range(len(requests)), key=lambda i: len(
        requests[i].prompt) + len(requests[i].tokens))
    rest = [i for i in range(len(requests)) if i != longest]
    pick = np.random.default_rng(seed).choice(len(rest), k - 1,
                                              replace=False)
    return [requests[longest]] + [requests[rest[i]] for i in sorted(pick)]


def _hidden(ref, w, config, seq, precision):
    import jax.numpy as jnp
    T = len(seq)
    pad = np.zeros((-(-T // SEQ_BUCKET) * SEQ_BUCKET,), np.int32)
    pad[:T] = seq
    return ref.hidden_states(w, jnp.asarray(pad), config, precision)


def _head(ref, w, x, key, samples, sets, precision):
    """head_stats over x (n, d) in fixed-size blocks (one compile)."""
    import jax
    import jax.numpy as jnp
    n = x.shape[0]
    out = []
    for i in range(0, n, POS_BLOCK):
        blk = x[i:i + POS_BLOCK]
        m = blk.shape[0]
        if m < POS_BLOCK:
            blk = jnp.pad(blk, ((0, POS_BLOCK - m), (0, 0)))
        r = ref.head_stats(w, blk, jax.random.fold_in(key, i), samples,
                           sets, precision)
        out.append({k: np.asarray(v)[:m] for k, v in r.items()})
    return {k: np.concatenate([o[k] for o in out]) for k in out[0]}


def _logits_at(ref, w, x, tokens):
    import jax.numpy as jnp
    return np.asarray(ref.logits_at(w, x, jnp.asarray(tokens, jnp.int32)))


def readings(ref, w, config: dict, served: list, seed: int,
             stand_in: str | None = None) -> dict:
    """Per-position readings of the served requests ``served``.

    ``served`` holds (prompt, tokens, H, SE, MI) records of the
    program's outputs.  With ``stand_in`` (a reference precision, the
    control) the reference at that precision takes the program's place
    at the same prompts and tokens, and its own picks are judged."""
    import jax
    samples = int(config["bayesian_head"]["mc_samples"])
    key = jax.random.key(seed)
    vocab = int(config["vocab_size"])
    rows = {n: [] for n in READINGS + ESTIMATORS + ("bad",)}
    for j, rec in enumerate(served):
        prompt, tokens = rec["prompt"], rec["tokens"]
        P, n = len(prompt), len(tokens)
        seq = fed_tokens(prompt, tokens)
        x = _hidden(ref, w, config, seq, "float32")[P:P + n]
        want = _head(ref, w, x, jax.random.fold_in(key, 2 * j), samples,
                     REF_SETS, "float32")
        if stand_in is None:
            got = {k: np.asarray(rec[k], np.float32)
                   for k in ("H", "SE", "MI", "p_max")}
            got["token"] = np.asarray(tokens, np.int32)
        else:
            xs = _hidden(ref, w, config, seq, stand_in)[P:P + n]
            got = _head(ref, w, xs, jax.random.fold_in(key, 2 * j + 1),
                        samples, 1, stand_in)
        at = _logits_at(ref, w, x, got["token"])
        rows["gap"].append(want["logit_max"] - at)
        rows["dlogp"].append(np.log(got["p_max"]) - (at - want["logz"]))
        for k in ESTIMATORS:
            rows[k].append(got[k] - want[k])
            rows["d" + k].append(np.abs(got[k] - want[k]))
        rows["bad"].append(triplet_bad(got["H"], got["SE"], got["MI"],
                                       vocab))
    return {k: np.concatenate(v) for k, v in rows.items()}


def numbers(per_position: dict) -> dict:
    """Each reading's widest and mean over the sampled positions, each
    estimator's signed mean, and the count of broken triplets."""
    out = {}
    for k in READINGS:
        out[f"{k}_max"] = float(np.max(per_position[k]))
        out[f"{k}_mean"] = float(np.mean(per_position[k]))
    for k in ESTIMATORS:
        out[f"{k}_bias"] = float(np.mean(per_position[k]))
    out["triplet_bad"] = int(np.sum(per_position["bad"]))
    return out


def judge(nums: dict, limits: dict) -> tuple[bool, list[str]]:
    """(correct, lines): each number the limits name, beside its limit."""
    ok, lines = True, []
    for k, spec in limits.items():
        fine = nums[k] <= spec["limit"]
        ok &= fine
        lines.append(f"{k} {nums[k]:.6g} limit {spec['limit']:.6g}"
                     + ("" if fine else " FAILED"))
    return ok, lines
