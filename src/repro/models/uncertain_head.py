"""The family-shared uncertain decode head (the body/head split).

Every family's ``decode_step`` is a KV-writing BODY (``decode_hidden``:
embed -> blocks -> final norm, advancing the cache by one position)
followed by this HEAD: ``cfg.mc_samples`` LRT draws from the Bayesian
output projection over the body's hidden state, reduced to the paper's
(H, SE, MI) uncertainty triplet plus the greedy next token.

The split is what speculative decoding builds on (launch/steps.py):

  * the DRAFT pass reuses the full body — its KV writes are bitwise the
    writes plain decode would do for the same fed tokens — and proposes
    with a cheap ``num_samples`` override of this head (1 draw, or 0
    for the deterministic mean head);
  * the VERIFY step re-runs ONLY this head, ``jax.vmap``-ped over the k
    stacked draft hiddens at their per-position depths.

In operand-entropy mode the head noise is a pure function of
(key, slot, depth) (``layers.decode_head_noise`` folds slot and depth,
never the global step), and the vmapped head is bitwise identical to k
sequential per-step heads at equal (slot, depth) sites — which is the
whole losslessness argument tests/test_spec_decode.py enforces.

Per-family head differences are preserved exactly: only the dense/vlm
transformer has the fused seeded-kernel path and the logits sharding
constraint; every other family keeps the plain operand tail.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.core.uncertainty import uncertainty_from_logits
from repro.models import layers as L
from repro.sharding.partition import constrain


def head_outputs(params, cfg: ArchConfig, hidden, cache_len, key,
                 num_samples: Optional[int] = None) -> dict:
    """Uncertain head over a decode hidden state.

    hidden: (B, d) pre-head hidden; ``cache_len``: () or (B,) PRE-step
    depths (the noise site — the body has already advanced its own
    ``len`` by the time the head runs).  ``num_samples`` overrides
    ``cfg.mc_samples`` for the cheap draft head (0 = mean head, greedy
    argmax of the softmax-mean, no draws at all).  Returns
    {next_token, H, SE, MI, p_max} per slot.  Named scope
    ``bayes_head``.  The head reads only ``.mu`` and ``.sigma``: the
    serving runner hands it the served form, whose sigma was made once
    (``runner.served_params``); a training tree's sigma is softplus(rho).
    """
    with jax.named_scope("bayes_head"):
        return _head_outputs(params, cfg, hidden, cache_len, key,
                             num_samples)


def _head_outputs(params, cfg, hidden, cache_len, key, num_samples):
    head = params["head"]
    S = cfg.mc_samples if num_samples is None else num_samples
    transformer = cfg.family in ("dense", "vlm")
    if cfg.family in ("dense", "vlm", "moe") and num_samples is None \
            and "q" in head \
            and not cfg.logits_softcap and cfg.head_entropy == "kernel":
        # seed-driven fused head: on TPU the xi tensor never exists (the
        # uncertainty-head kernel draws it in-register and regenerates
        # the sample logits in its second pass); off-TPU the seeded
        # oracle runs.  Softcapped heads keep the explicit-logits path.
        from repro.kernels import ops, rng
        q = head["q"]
        unc = ops.uncertainty_head_sampled(
            hidden, q.mu, q.sigma, rng.seed_from_key(key), num_samples=S)
        return {
            "next_token": unc["pred"],
            "H": unc["H"], "SE": unc["SE"], "MI": unc["MI"],
            "p_max": unc["p_max"],
        }
    if "q" in head and S > 0:
        xi = L.decode_head_noise(key, cache_len, S, cfg.vocab_size)
        logits = L.head_logits_sampled(head, hidden[None], cfg, xi)
    else:
        logits = L.head_logits_mean(head, hidden, cfg)[None]
    if transformer:
        logits = constrain(logits, None, "batch", "model")
    unc = uncertainty_from_logits(logits)
    return {
        "next_token": unc["p_mean"].argmax(-1).astype(jnp.int32),
        "H": unc["H"], "SE": unc["SE"], "MI": unc["MI"],
        "p_max": unc["p_mean"].max(-1),
    }
