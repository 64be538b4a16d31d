"""High-S escalation lane (the engine's OOD verification sidecar).

When a decoding slot's carried MI crosses ``--escalate-mi`` the engine
hands the request to an ``EscalationLane``: a single-slot dense sidecar
driven by a second ``ModelRunner`` whose config re-draws the uncertain
head with ``--escalate-s`` MC samples instead of the serving S
(``ServeEngine.escalation_runner`` keys one runner — one jit cache —
per distinct S).  More samples shrink the MC error of the MI estimate
(see docs/uncertainty.md), so the tokens that actually ship for a
flagged-OOD request carry the better uncertainty read — the serving
analogue of routing flagged blood-cell images to a bigger verify pass
in ``examples/blood_cell_ood.py``.

The lane is deliberately primitive mechanism: one request at a time,
re-prefill of ``prompt + tokens-so-far`` into its own dense cache
(S only changes head draws, never the KV, so the replayed cache is
exactly what the main engine held), then plain scan chunks to the
request's finish.  It does ONE unit of work per engine iteration — an
admission or a decode chunk — so escalations never stall the main
pool's decode cadence.  Requests whose ``prompt + max_new_tokens``
exceed the lane's dense ``max_len`` don't fit (``fits``) and keep
decoding in the main engine, counted once in ``esc_skipped``.

Tested in tests/test_policy.py::TestEscalation.
"""

from __future__ import annotations

import collections

import jax
import jax.numpy as jnp
import numpy as np


class EscalationLane:
    """One-slot high-S finish lane over a dedicated ``ModelRunner``.

    Host-side driver state only: the cache/carry live on device via the
    runner's callables, and the lane's global step counter is its own
    (operand-mode head noise is depth-keyed, so the escalated stream is
    reproducible regardless of when the engine escalated).
    """

    def __init__(self, runner, *, chunk: int, eos_id=None, pad_to=None,
                 modality=None):
        self.runner = runner
        self.chunk = chunk
        self.eos_id = eos_id
        self.pad_to = pad_to          # prompt bucket (None: exact lengths)
        self.modality = modality
        self.max_len = runner.max_len
        self.queue: collections.deque = collections.deque()
        self.current = None
        self._cache = None            # built lazily on first admission
        self._tok = None
        self._active = None
        self._flags = None
        self._step0 = 0

    def fits(self, req) -> bool:
        """Whole-lifetime bound: the dense sidecar strip must hold the
        full prompt + generation budget."""
        return len(req.prompt) + req.max_new_tokens <= self.max_len

    def has_work(self) -> bool:
        return self.current is not None or bool(self.queue)

    def step(self, stats) -> bool:
        """One unit of lane work per engine iteration: admit the next
        escalated request, or decode one chunk of the current one.
        Returns whether anything ran (the engine's stall guard)."""
        if self.current is None:
            if not self.queue:
                return False
            self._admit(self.queue.popleft())
            return True
        self._decode_chunk(stats)
        return True

    def submit(self, req) -> None:
        self.queue.append(req)

    def _admit(self, req) -> None:
        """Re-prefill ``prompt + tokens-so-far`` into the sidecar cache.

        S affects only the head's MC draws, never the body or its KV
        writes, so this replay reconstructs bit-for-bit the KV state
        the request left behind in the main engine; decode then simply
        continues from the last emitted token at the higher S.
        """
        r = self.runner
        if self._cache is None:
            self._cache = r.make_cache(1)
            self._tok = jnp.zeros((1,), jnp.int32)
            self._active = jnp.zeros((1,), bool)
            self._flags = {"epistemic": jnp.zeros((1,), jnp.int32),
                           "aleatoric": jnp.zeros((1,), jnp.int32)}
        seq = list(req.prompt) + list(req.tokens)
        n = len(seq)
        W = n
        if self.pad_to:
            W = min(-(-n // self.pad_to) * self.pad_to, self.max_len)
        toks = np.zeros((W,), np.int32)
        toks[:n] = seq
        slot0 = jnp.asarray(0, jnp.int32)
        _, sub = r._prefill(r.params, jnp.asarray(toks)[None],
                            self.modality)
        cache = r._write(self._cache, slot0, sub)
        if W > n:
            cache = r._set_len(cache, slot0, jnp.asarray(n, jnp.int32))
        self._cache = cache
        self._tok = self._tok.at[0].set(int(seq[-1]))
        self._active = self._active.at[0].set(True)
        self._flags = {k: v.at[0].set(0) for k, v in self._flags.items()}
        self.current = req

    def _decode_chunk(self, stats) -> None:
        """One scan chunk at the verify S, harvested into the request."""
        r = self.runner
        req = self.current
        with stats.span("serve.escalate_decode"):
            self._tok, self._cache, self._flags, ys = r._scan(
                r.params, self._tok, self._cache,
                jnp.asarray(self._step0, jnp.int32), self._active,
                self._flags)
            jax.copy_to_host_async(ys)
            with stats.span("serve.escalate_wait"):
                jax.block_until_ready(ys)
            ys = jax.device_get(ys)
        stats.esc_steps += self.chunk
        self._step0 += self.chunk
        for t in range(self.chunk):
            tk = int(ys["token"][t, 0])
            req.tokens.append(tk)
            for name in ("H", "SE", "MI", "p_max"):
                getattr(req, name).append(float(ys[name][t, 0]))
            req.epistemic_flags += int(ys["epistemic"][t, 0])
            req.aleatoric_flags += int(ys["aleatoric"][t, 0])
            req.last_mi = float(ys["MI"][t, 0])
            stats.esc_tokens += 1
            done_eos = self.eos_id is not None and tk == self.eos_id
            if done_eos or len(req.tokens) >= req.max_new_tokens:
                req.transition("finished",
                               reason="eos" if done_eos else "length")
                self._active = self._active.at[0].set(False)
                self.current = None
                break
