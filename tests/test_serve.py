"""Serving engine: scheduler, slot-indexed cache, scan-decode parity.

The acceptance contract of the continuous-batching rebuild: scan decode
replays the per-token loop bit-exactly in operand-entropy mode, slots
behave like independent sequences at independent depths, and the
host-side scheduler admits/evicts/reuses slots in FIFO order.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import make_request as _req
from repro.configs.registry import get_config, reduced
from repro.core.entropy import KernelEntropy
from repro.launch import steps as S
from repro.launch.serve import (Request, ServeEngine, SlotScheduler,
                                decode_loop_reference)
from repro.models import registry as M

# the shared (cfg, params, prompts) `setup` fixture lives in conftest.py


# ---------------------------------------------------------------------------
# host-side scheduler
# ---------------------------------------------------------------------------

class TestSlotScheduler:
    def test_fifo_admission_and_slot_order(self):
        s = SlotScheduler(2)
        for i in range(5):
            s.submit(_req(i, [0], 4))
        placed = s.admit()
        assert [(slot, r.rid) for slot, r in placed] == [(0, 0), (1, 1)]
        assert s.admit() == []               # both slots busy
        assert len(s.queue) == 3

    def test_eviction_frees_slot_for_next_in_queue(self):
        s = SlotScheduler(2)
        for i in range(4):
            s.submit(_req(i, [0], 4))
        s.admit()
        evicted = s.evict(1)
        assert evicted.rid == 1
        placed = s.admit()                   # slot 1 reused, FIFO order
        assert [(slot, r.rid) for slot, r in placed] == [(1, 2)]
        s.evict(0)
        with pytest.raises(ValueError):
            s.evict(0)                       # evict of an empty slot

    def test_has_work_lifecycle(self):
        s = SlotScheduler(1)
        assert not s.has_work()
        s.submit(_req(0, [0], 1))
        assert s.has_work()
        s.admit()
        assert s.has_work()                  # active slot counts as work
        s.evict(0)
        assert not s.has_work()


# ---------------------------------------------------------------------------
# slot-indexed cache
# ---------------------------------------------------------------------------

class TestSlotCache:
    def test_write_slot_matches_batched_prefill(self, setup):
        cfg, params, prompts = setup
        max_len = 20
        _, batched = M.prefill(params, cfg, jnp.asarray(prompts[:3]),
                               max_len)
        cache = M.make_cache(cfg, 3, max_len)
        for i in range(3):
            _, sub = M.prefill(params, cfg, jnp.asarray(prompts[i:i + 1]),
                               max_len)
            cache = M.write_slot(cfg, cache, jnp.asarray(i, jnp.int32),
                                 sub)
        for leaf_b, leaf_s in zip(jax.tree.leaves(batched),
                                  jax.tree.leaves(cache)):
            np.testing.assert_allclose(np.asarray(leaf_b),
                                       np.asarray(leaf_s), atol=1e-5)

    @pytest.mark.parametrize("arch", ["qwen2_1_5b", "mamba2_370m",
                                      "zamba2_7b"])
    def test_write_slot_generic_across_families(self, arch):
        cfg = reduced(get_config(arch))
        key = jax.random.key(1)
        params = M.init_params(key, cfg)
        toks = jax.random.randint(key, (1, 8), 0, cfg.vocab_size)
        cache = M.make_cache(cfg, 3, 16)
        _, sub = M.prefill(params, cfg, toks, 16)
        cache = M.write_slot(cfg, cache, jnp.asarray(1, jnp.int32), sub)
        np.testing.assert_array_equal(np.asarray(cache["len"]), [0, 8, 0])
        out, cache2 = M.decode_step(params, cfg,
                                    jnp.zeros((3,), jnp.int32), cache, key)
        assert np.isfinite(np.asarray(out["H"])).all()
        np.testing.assert_array_equal(np.asarray(cache2["len"]), [1, 9, 1])

    def test_staggered_slots_decode_like_isolated_sequences(self, setup):
        """Slots at different depths must behave as independent sequences
        (per-slot RoPE positions + per-slot cache offsets).  Deterministic
        head isolates cache correctness from MC noise."""
        cfg, _, prompts = setup
        cfg = dataclasses.replace(cfg, bayesian_head=False)
        params = M.init_params(jax.random.key(7), cfg)
        max_len = 24
        scan = S.build_scan_decode(cfg, chunk=3)
        flags0 = {"epistemic": jnp.zeros((2,), jnp.int32),
                  "aleatoric": jnp.zeros((2,), jnp.int32)}

        # slot 0: request A; decode 3; then admit B into slot 1; decode 3
        cache = M.make_cache(cfg, 2, max_len)
        _, sub_a = M.prefill(params, cfg, jnp.asarray(prompts[:1]), max_len)
        cache = M.write_slot(cfg, cache, jnp.asarray(0, jnp.int32), sub_a)
        tok = jnp.zeros((2,), jnp.int32).at[0].set(int(prompts[0, -1]))
        tok, cache, _, ys1 = scan(params, tok, cache,
                                  jnp.asarray(0, jnp.int32),
                                  jnp.array([True, False]), flags0)
        _, sub_b = M.prefill(params, cfg, jnp.asarray(prompts[1:2]),
                             max_len)
        cache = M.write_slot(cfg, cache, jnp.asarray(1, jnp.int32), sub_b)
        tok = tok.at[1].set(int(prompts[1, -1]))
        tok, cache, _, ys2 = scan(params, tok, cache,
                                  jnp.asarray(3, jnp.int32),
                                  jnp.array([True, True]), flags0)
        a_tokens = np.concatenate([ys1["token"][:, 0], ys2["token"][:, 0]])
        b_tokens = np.asarray(ys2["token"][:, 1])

        ref_a = decode_loop_reference(params, cfg, prompts[:1], 6,
                                      max_len=max_len)
        ref_b = decode_loop_reference(params, cfg, prompts[1:2], 3,
                                      max_len=max_len)
        np.testing.assert_array_equal(a_tokens, ref_a["token"][:, 0])
        np.testing.assert_array_equal(b_tokens, ref_b["token"][:, 0])


# ---------------------------------------------------------------------------
# scan-decode engine
# ---------------------------------------------------------------------------

class TestEngine:
    def test_scan_decode_parity_with_per_token_loop(self, setup):
        """Operand mode, one static wave: the engine's scan decode must
        replay the per-token loop's stream bit for bit."""
        cfg, params, prompts = setup
        gen = 8
        ref = decode_loop_reference(params, cfg, prompts[:3], gen)
        engine = ServeEngine(params, cfg, num_slots=3,
                             max_len=prompts.shape[1] + gen, chunk=4)
        res = engine.run([_req(i, prompts[i], gen) for i in range(3)])
        for j, req in enumerate(res["requests"]):
            np.testing.assert_array_equal(req.tokens, ref["token"][:, j])
            np.testing.assert_array_equal(
                np.asarray(req.MI, np.float32), ref["MI"][:, j])
            np.testing.assert_array_equal(
                np.asarray(req.H, np.float32), ref["H"][:, j])

    def test_continuous_batching_drains_queue(self, setup):
        cfg, params, prompts = setup
        engine = ServeEngine(params, cfg, num_slots=2, max_len=32, chunk=4)
        reqs = [_req(i, prompts[i], 6) for i in range(6)]
        res = engine.run(reqs)
        assert res["gen_tokens"] == 6 * 6
        for r in reqs:
            assert len(r.tokens) == 6 and r.finish_reason == "length"
            assert r.t_finish >= r.t_submit
        # later arrivals wait for a slot: their latency is strictly larger
        assert reqs[-1].latency_s > reqs[0].latency_s

    def test_eos_evicts_early_and_slot_is_reused(self, setup):
        cfg, params, prompts = setup
        mk = lambda: [_req(i, prompts[i], 8) for i in range(4)]
        engine = ServeEngine(params, cfg, num_slots=2, max_len=32, chunk=4)
        probe = engine.run(mk())
        eos = probe["requests"][0].tokens[2]   # deterministic stream
        engine = ServeEngine(params, cfg, num_slots=2, max_len=32, chunk=4,
                             eos_id=eos)
        res = engine.run(mk())
        req0 = res["requests"][0]
        assert req0.finish_reason == "eos"
        assert len(req0.tokens) <= 3
        assert all(len(r.tokens) > 0 for r in res["requests"])  # reuse

    def test_uncertainty_flags_survive_scan(self, setup):
        """The gating flags computed inside the scan carry must equal a
        host-side recomputation from the emitted (MI, SE) streams."""
        cfg, params, prompts = setup
        mi_thr, se_thr = 0.004, 6.0
        engine = ServeEngine(params, cfg, num_slots=2, max_len=32, chunk=4,
                             mi_threshold=mi_thr, se_threshold=se_thr)
        res = engine.run([_req(i, prompts[i], 8) for i in range(4)])
        total_epi = total_alea = 0
        for r in res["requests"]:
            mi = np.asarray(r.MI)
            se = np.asarray(r.SE)
            epi = mi > mi_thr
            alea = (se > se_thr) & ~epi
            assert r.epistemic_flags == int(epi.sum())
            assert r.aleatoric_flags == int(alea.sum())
            total_epi += int(epi.sum())
            total_alea += int(alea.sum())
        assert res["epistemic_flags"] == total_epi
        assert res["aleatoric_flags"] == total_alea
        # device-side carry counters: requests here finish exactly at
        # chunk boundaries, so each slot's counter equals its last
        # occupant's host-side count (slot i served requests i, i+2)
        reqs = res["requests"]
        for slot in range(2):
            last = reqs[slot + 2]
            dev = res["device_flag_counters"]
            assert dev["epistemic"][slot] == last.epistemic_flags
            assert dev["aleatoric"][slot] == last.aleatoric_flags

    def test_request_over_slot_capacity_is_rejected(self, setup):
        cfg, params, prompts = setup
        engine = ServeEngine(params, cfg, num_slots=2, max_len=16, chunk=4)
        with pytest.raises(ValueError, match="slot capacity"):
            engine.run([_req(0, prompts[0], 8)])   # 12 + 8 > 16
        with pytest.raises(ValueError, match="empty prompt"):
            engine.run([_req(0, [], 2)])
        with pytest.raises(ValueError, match="max_new_tokens"):
            engine.run([_req(0, prompts[0], 0)])

    def test_mixed_prompt_lengths_split_compile_from_steady(self, setup):
        """Each distinct prompt length costs one prefill compile; repeat
        lengths must be classified steady, not averaged as recompiles."""
        cfg, params, prompts = setup
        engine = ServeEngine(params, cfg, num_slots=2, max_len=32, chunk=4)
        reqs = [_req(0, prompts[0], 4), _req(1, prompts[1], 4),
                _req(2, prompts[2][:8], 4), _req(3, prompts[3][:8], 4)]
        res = engine.run(reqs)
        assert all(len(r.tokens) == 4 for r in reqs)
        assert res["prefill_steady_s"] > 0.0
        # two compiles (len 12, len 8) dwarf the steady dispatch mean
        assert res["prefill_compile_s"] > 5 * res["prefill_steady_s"]

    def test_cli_reduced_turns_off(self):
        """The CPU default serves the reduced config; --no-reduced
        reaches the published widths."""
        from repro.launch.serve import build_parser
        ap = build_parser()
        assert ap.parse_args([]).reduced is True
        assert ap.parse_args(["--no-reduced"]).reduced is False

    def test_seeded_engine_is_deterministic_per_seed(self, setup):
        cfg, params, prompts = setup
        cfg = dataclasses.replace(cfg, head_entropy="kernel")

        def run(seed):
            engine = ServeEngine(params, cfg, num_slots=2, max_len=32,
                                 chunk=4, entropy=KernelEntropy(seed=seed))
            res = engine.run([_req(i, prompts[i], 6) for i in range(2)])
            return np.asarray([r.MI for r in res["requests"]])

        a, b, c = run(3), run(3), run(4)
        np.testing.assert_array_equal(a, b)
        assert not np.allclose(a, c)


    @pytest.mark.parametrize("entropy", ["kernel", "operand"])
    def test_served_head_decodes_bitwise_as_rho(self, setup, monkeypatch,
                                                entropy):
        """The runner's served head (sigma made once) decodes the same
        tokens and (H, SE, MI) bit for bit as the head that recomputes
        softplus(rho) inside every step."""
        from repro.launch.engine import runner as runner_mod
        cfg, params, prompts = setup
        cfg = dataclasses.replace(cfg, head_entropy=entropy)

        def run():
            engine = ServeEngine(
                params, cfg, num_slots=2, max_len=32, chunk=4,
                entropy=KernelEntropy(seed=5) if entropy == "kernel"
                else None)
            res = engine.run([_req(i, prompts[i], 6) for i in range(3)])
            return engine, {k: np.asarray([getattr(r, k) for r in
                                           res["requests"]], np.float32)
                            for k in ("tokens", "H", "SE", "MI")}

        served_engine, served = run()
        assert "rho" not in vars(served_engine.params["head"]["q"])
        monkeypatch.setattr(runner_mod, "served_params", lambda p: p)
        rho_engine, from_rho = run()
        assert rho_engine.params["head"]["q"] is params["head"]["q"]
        for k in served:
            np.testing.assert_array_equal(served[k], from_rho[k], err_msg=k)


# ---------------------------------------------------------------------------
# train-step seeding (satellite bugfix)
# ---------------------------------------------------------------------------

class TestTrainSeed:
    def test_two_seeds_diverge_same_seed_replays(self):
        from repro.core.svi import SVIConfig
        from repro.optim import adamw
        cfg = reduced(get_config("qwen2_1_5b"))
        opt_cfg = adamw.AdamWConfig(lr=1e-3, warmup_steps=0,
                                    schedule="constant")
        svi = SVIConfig(num_train_examples=10_000)
        key = jax.random.key(0)
        params = M.init_params(key, cfg)
        batch = M.make_batch(key, cfg, 2, 16)

        def losses(seed):
            fn = jax.jit(S.build_train_step(cfg, opt_cfg, svi, seed=seed))
            state = {"params": params,
                     "opt": adamw.init_state(params, opt_cfg)}
            out = []
            for _ in range(2):
                state, m = fn(state, batch)
                out.append(float(m["loss"]))
            return out

        a, b, c = losses(0), losses(0), losses(1)
        assert a == b                      # same seed -> same SVI stream
        assert a != c                      # the --seed actually threads
