"""Drives the serving engine for one cell: build, warm up, measure.

The engine is the program's ``ServeEngine`` built as
``repro.launch.serve.build_engine`` builds it, from the configuration
file's widths and engine settings, with the weights of
``bench.weights`` in the tree of the configuration's family.
``ServeEngine.run`` is a blocking call over a list of requests whose
arrivals are counted in decode steps; the harness gives each request a
token list that stamps the host clock as the engine appends to it, and
reads every latency from those stamps.
"""

from __future__ import annotations

import time
from pathlib import Path

import numpy as np

from bench import spec, weights
from bench import traffic as T


class Window:
    """The measured window inside one ``engine.run``.

    Opens when every opening request has its first token (so the window
    starts with the slots the schedule opens with already decoding) and
    closes ``seconds`` later; ``on_open`` and ``on_mark`` (``mark``
    seconds after the opening) run on the engine's own thread, inside the
    token append that crosses each edge.

    It also groups the appends into harvests: the engine appends a
    decode chunk's tokens in one host loop, slot by slot, up to
    ``chunk`` tokens each, and waits for the device between two chunks.
    A harvest begins where the appends pause, where a request that has
    already appended in the current one appends again after another, or
    where one appends beyond ``chunk``.  It ends the run soon after the
    close: a request
    that joined the queue after the close is cut at its first token,
    and one still decoding ``grace`` seconds after the close at its next
    (``cut_late`` holds those that had joined inside the window).
    """

    # appends further apart than this belong to different harvests: a
    # harvest appends its tokens in a host loop of microseconds, and
    # the next waits for a decode chunk of the device
    HARVEST_GAP_S = 0.02

    def __init__(self, seconds: float, opening: set, on_open=None,
                 mark: float | None = None, on_mark=None,
                 grace: float = 60.0, chunk: int = 8):
        self.seconds = seconds
        self.waiting = set(opening)
        self.t_open = self.t_close = self.t_mark = None
        self.mark = mark
        self.on_open, self.on_mark = on_open, on_mark
        self.grace = grace
        self.chunk = chunk
        self.harvests: list[list] = []   # [first append, tokens, last]
        self._seen: set = set()
        self._run = (None, 0)            # (rid appending, its appends)
        self.cut_late: set = set()

    def _harvest(self, rid: int, t: float) -> None:
        cur, n = self._run
        if not self.harvests or t - self.harvests[-1][2] > self.HARVEST_GAP_S \
                or (rid != cur and rid in self._seen) \
                or (rid == cur and n == self.chunk):
            self.harvests.append([t, 0, t])
            self._seen, cur = set(), None
        if rid != cur:
            self._seen.add(rid)
            self._run = (rid, 0)
        self._run = (rid, self._run[1] + 1)
        self.harvests[-1][1] += 1
        self.harvests[-1][2] = t

    def token(self, rid: int, first: bool, t: float) -> None:
        self._harvest(rid, t)
        if self.t_open is None:
            if first:
                self.waiting.discard(rid)
            if not self.waiting:
                self.t_open = t
                if self.on_open:
                    self.on_open()
            return
        if self.mark is not None and self.t_mark is None \
                and t >= self.t_open + self.mark:
            self.t_mark = t
            if self.on_mark:
                self.on_mark()
        if self.t_close is None and t >= self.t_open + self.seconds:
            self.t_close = self.t_open + self.seconds

    def cut(self, req, t: float) -> bool:
        """Whether ``req`` should finish with the token appended at ``t``."""
        if self.t_close is None:
            return False
        if req.t_submit >= self.t_close:
            return True
        if t >= self.t_close + self.grace:
            self.cut_late.add(req.rid)
            return True
        return False

    def inside(self, t: float) -> bool:
        return self.t_open is not None and self.t_close is not None \
            and self.t_open <= t < self.t_close

    def decode_rows(self, t0: float, t1: float) -> tuple[int, int]:
        """(tokens, harvests) of the harvests that began in [t0, t1)."""
        hs = [n for t, n, _ in self.harvests if t0 <= t < t1]
        return sum(hs), len(hs)


class StampedTokens(list):
    """A request's output tokens, each stamped as the engine appends it.

    With a window, a token that the window cuts at lowers the request's
    ``max_new_tokens`` to the tokens it has, so the engine finishes it
    there (the engine checks that budget after every append)."""

    def __init__(self, rid: int, window: Window | None):
        super().__init__()
        self.rid, self.window, self.times = rid, window, []
        self.request = None

    def append(self, tok) -> None:
        t = time.perf_counter()
        super().append(tok)
        self.times.append(t)
        if self.window is not None:
            self.window.token(self.rid, len(self.times) == 1, t)
            if self.request is not None and self.window.cut(self.request, t):
                self.request.max_new_tokens = len(self)

    def clear(self) -> None:
        super().clear()
        self.times.clear()


def program(config: dict, seed: int, root: Path = spec.ROOT):
    """(ArchConfig, parameters) of a configuration for ``seed``: its
    family (``bench/families/<reference>.py``) maps the configuration
    and ``bench.weights`` into the program's types, and the tree is
    checked against the shapes and types of the program's own
    ``init_params``."""
    import jax
    from repro.models import registry as M
    family = spec.family_module(config["reference"], root)
    cfg = family.arch_config(config)
    params = family.program_params(weights.generate(config, seed, root),
                                   cfg)
    want = jax.eval_shape(lambda k: M.init_params(k, cfg),
                          jax.random.key(0))
    got = jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype),
                       params)
    if jax.tree.structure(want) != jax.tree.structure(got) or any(
            (a.shape, a.dtype) != (b.shape, b.dtype) for a, b in
            zip(jax.tree.leaves(want), jax.tree.leaves(got))):
        raise ValueError("bench weights do not match the program's "
                         "parameter tree")
    return cfg, params


def build_engine(config: dict, mix: dict, params, cfg, seed: int):
    """The ServeEngine of the configuration's engine settings, its
    tables wide enough for the mix's longest request (no table growth,
    so no retrace, inside a run), its head draws seeded by ``seed``."""
    from repro.core.entropy import KernelEntropy
    from repro.launch.engine import ServeEngine
    e = config["engine"]
    max_len = T.longest_request(mix) + e["decode_chunk"]
    return ServeEngine(
        params, cfg, num_slots=e["num_slots"], max_len=max_len,
        chunk=e["decode_chunk"],
        entropy=KernelEntropy(seed=seed % 2**32)
        if cfg.head_entropy == "kernel" else None,
        kv_layout=e["kv_layout"], kv_block=e["kv_block"],
        prefix_cache=e["prefix_cache"], decode_attn=e["decode_attn"],
        prefill_mode=e["prefill"], prefill_chunk=e["prefill_chunk"])


def make_request(rid, prompt, max_new, arrival, window=None):
    from repro.launch.engine import Request
    req = Request(rid=rid, prompt=np.asarray(prompt, np.int32),
                  max_new_tokens=int(max_new), arrival_step=int(arrival),
                  tokens=StampedTokens(rid, window))
    req.tokens.request = req
    return req


def warmup_requests(config: dict, mix: dict, vocab: int, seed: int):
    """Requests that make the engine compile every program the mix can
    ask for: one long prompt, then, once it has finished and left its
    blocks in the prefix cache, one prompt per span (prompt bucket of
    ``kv_block`` tokens) that differs from it in its last token only, so
    each costs one prefill chunk at its own span (with a copy-on-write
    of the shared tail block) and one decode chunk."""
    e = config["engine"]
    lengths = T.prompt_lengths(mix)
    rng = np.random.default_rng(seed)
    base = rng.integers(0, vocab, max(lengths) + 1, dtype=np.int32)
    one_per_span = {}
    for n in lengths:
        one_per_span[-(-n // e["kv_block"])] = n
    reqs = [make_request(0, base[:max(lengths)], 1, 0)]
    for i, n in enumerate(sorted(one_per_span.values())):
        p = base[:n].copy()
        p[-1] = (p[-1] + 1) % vocab
        reqs.append(make_request(i + 1, p, 1, e["decode_chunk"]))
    return reqs


def calibrate(engine, config: dict, mix: dict, vocab: int,
              seed: int) -> float:
    """Seconds per engine iteration (one decode chunk) on the warm
    engine: the median gap between chunk harvests of a short run of two
    of the mix's shortest prompts."""
    e = config["engine"]
    rng = np.random.default_rng(seed)
    n = min(T.prompt_lengths(mix))
    reqs = [make_request(i, rng.integers(0, vocab, n, dtype=np.int32),
                         10 * e["decode_chunk"], 0) for i in range(2)]
    engine.run(reqs)
    times = np.asarray(reqs[0].tokens.times)
    firsts = times[::e["decode_chunk"]]
    return float(np.median(np.diff(firsts[2:])))


def window_requests(items, n: int, window: Window) -> list:
    return [make_request(i, it.prompt, it.max_new_tokens, it.arrival_step,
                         window) for i, it in enumerate(items[:n])]
