"""The harness end to end on the CPU at a tiny size, and its refusals.

``run_cell`` is everything a run does after its look for a chip: these
tests call it on the tiny configuration, once sound and once for each
fault a one-chip serving cell can have, planted in the served path, and
check the verdict.  (A one-chip cell has no exchange between chips.)
The limits in ``data/tiny-limits.json`` were set from CPU readings of
this tiny cell (sound runs on six seeds: gap_mean up to 0.043, dMI_mean
up to 0.0014; each fault on two: gap_mean from 0.32 for a state left
unchanged, 1.45 for half the batch, 3.7 for an altered token, dMI_mean
from 0.009 for half the draws), not from the chip cells'.
"""

import json
import os
import shutil
import subprocess
import sys

import jax
import pytest

from _paths import DATA, ROOT
from bench import check, run, spec

E2E = [{"name": n, "unit": "u"}
       for n in ("tok_s", "ttft_p95_ms", "tpot_p95_ms", "setup_s")]


def _tiny_cell():
    return spec.Cell(
        name="tiny.chat", chips=1,
        config=json.loads((DATA / "tiny.json").read_text()),
        traffic=json.loads((DATA / "tiny-chat.json").read_text()),
        limits=json.loads((DATA / "tiny-limits.json").read_text()),
        end_to_end=E2E, per_layer=[])


def _state_unchanged(monkeypatch):
    """A decode scan that hands back the cache it was given."""
    from repro.launch import steps
    build = steps.build_scan_decode

    def broken(*a, **k):
        scan = build(*a, **k)

        def fn(params, token, cache, step0, active, flags):
            token, _, flags, ys = scan(params, token, cache, step0, active,
                                       flags)
            return token, cache, flags, ys
        return fn
    monkeypatch.setattr(steps, "build_scan_decode", broken)


def _token_altered(monkeypatch):
    """Every decoded token replaced by its neighbour id where it is made."""
    from repro.launch import steps
    build = steps.build_scan_decode

    def broken(*a, **k):
        scan = build(*a, **k)

        def fn(params, token, cache, step0, active, flags):
            token, cache, flags, ys = scan(params, token, cache, step0,
                                           active, flags)
            vocab = params["head"]["q"].mu.shape[1]
            return (token + 1) % vocab, cache, flags, dict(
                ys, token=(ys["token"] + 1) % vocab)
        return fn
    monkeypatch.setattr(steps, "build_scan_decode", broken)


def _half_the_batch(monkeypatch):
    """The lower half of the slots (where a lightly loaded engine puts
    its requests) left out of each decode chunk: their tokens stay what
    they were fed."""
    import jax.numpy as jnp
    from repro.launch import steps
    build = steps.build_scan_decode

    def broken(*a, **k):
        scan = build(*a, **k)

        def fn(params, token, cache, step0, active, flags):
            new, cache, flags, ys = scan(params, token, cache, step0,
                                         active, flags)
            keep = jnp.arange(token.shape[0]) >= token.shape[0] // 2
            return jnp.where(keep, new, token), cache, flags, dict(
                ys, token=jnp.where(keep[None], ys["token"], token[None]))
        return fn
    monkeypatch.setattr(steps, "build_scan_decode", broken)


def _half_the_draws(monkeypatch):
    """The head averages over half of its S draws."""
    from repro.kernels import ops
    head = ops.uncertainty_head_sampled

    def broken(x, mu, sigma, seed, num_samples=10, **k):
        return head(x, mu, sigma, seed, num_samples=num_samples // 2, **k)
    monkeypatch.setattr(ops, "uncertainty_head_sampled", broken)


@pytest.mark.parametrize("fault, correct", [
    (None, True),
    (_state_unchanged, False),
    (_token_altered, False),
    (_half_the_batch, False),
    (_half_the_draws, False),
], ids=["sound", "state_unchanged", "token_altered", "half_the_batch",
        "half_the_draws"])
def test_run_decides_correct(monkeypatch, fault, correct):
    if fault is not None:
        fault(monkeypatch)
    out = run.run_cell(_tiny_cell(), 2**31 + 5, 2.0, False, jax.devices(),
                       log=lambda *a: None)
    lines = out.pop("_lines")
    assert out["correct"] is correct, lines
    assert list(out)[-1] == "checks"
    assert set(out["metrics"]) == {m["name"] for m in E2E}
    assert out["device"]["platform"] == "cpu"
    assert out["attempted"] > 0 and out["failed"] == 0


def _run_script(cwd, *args):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, str(cwd / "bench" / "run.py"), "--workload",
         "qwen2-1.5b.chat", "--seed", "1", "--seconds", "1", "--trace", "0",
         *args], cwd=cwd, env=env, capture_output=True, text=True,
        timeout=120)


def test_exits_nonzero_without_a_tpu():
    r = _run_script(ROOT)
    assert r.returncode == 3, r.stderr
    assert "Nothing was run" in r.stderr
    assert "{" not in r.stdout


def test_exits_nonzero_without_the_program(tmp_path):
    for d in json.loads((ROOT / "BENCHMARK.json").read_text())["paths"]:
        shutil.copytree(ROOT / d, tmp_path / d,
                        ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    r = _run_script(tmp_path)
    assert r.returncode == 2, r.stderr
    assert "{" not in r.stdout


def test_sample_keeps_the_longest_request():
    class R:
        def __init__(self, p, t):
            self.prompt, self.tokens = [0] * p, [0] * t
    reqs = [R(10, 5), R(300, 40), R(20, 8), R(12, 3), R(9, 9)]
    got = check.sample(reqs, 7, 3)
    assert got[0] is reqs[1] and len(got) == 3
    assert got == check.sample(reqs, 7, 3)
