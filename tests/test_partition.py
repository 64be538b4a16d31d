"""Partition-rule machinery: divisibility fallbacks, registry sweep,
device-placement round-trip.

``sanitize_pspecs`` / ``spec_if`` are the reason the name-based rule
tables can stay clean while published vocab/head sizes are not always
mesh-divisible: every dim that does not divide its mesh-axis product
must silently fall back to replication, because ``jit(in_shardings=…)``
(unlike a mere constraint) requires exact divisibility.
"""

import json
import os
import subprocess
import sys
import textwrap

import jax
import pytest
from jax.sharding import AbstractMesh, PartitionSpec as P

from repro.configs.registry import ARCH_IDS, get_config
from repro.launch.mesh import make_debug_mesh, spec_if
from repro.models import registry as M
from repro.sharding.partition import (param_pspecs, sanitize_pspecs,
                                      serve_pspecs)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

MESH_1x4 = AbstractMesh((1, 4), ("data", "model"))
MESH_2x2 = AbstractMesh((2, 2), ("data", "model"))


def _axis_product(mesh, d):
    axes = (d,) if isinstance(d, str) else tuple(d)
    n = 1
    for a in axes:
        n *= mesh.shape[a]
    return n


# ---------------------------------------------------------------------------
# spec_if: per-dim divisibility fallback
# ---------------------------------------------------------------------------

class TestSpecIf:
    def test_divisible_dims_shard(self):
        assert spec_if(MESH_1x4, (3, 8), None, "model") == P(None, "model")

    def test_indivisible_dim_replicates(self):
        # 6 % 4 != 0: the model axis is dropped, not erred
        assert spec_if(MESH_1x4, (3, 6), None, "model") == P(None, None)

    def test_dim_smaller_than_axis_replicates(self):
        # a 1-head KV pool cannot shard over 4 devices
        assert spec_if(MESH_1x4, (10, 8, 1, 32),
                       None, None, "model", None) \
            == P(None, None, None, None)

    def test_batch_expands_to_dp_axes(self):
        assert spec_if(MESH_2x2, (4, 8), "batch", None) == P("data", None)


# ---------------------------------------------------------------------------
# sanitize_pspecs over every registry config's REAL param shapes
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("arch", ARCH_IDS)
@pytest.mark.parametrize("mesh,rules", [(MESH_1x4, "serve"),
                                        (MESH_2x2, "train")])
def test_sanitized_specs_divide_for(arch, mesh, rules):
    """Every surviving shard axis divides its dim — jit-placeable — at
    the PUBLISHED sizes (eval_shape: no multi-GB allocation)."""
    cfg = get_config(arch)
    shapes = jax.eval_shape(lambda k: M.init_params(k, cfg),
                            jax.random.key(0))
    specs = serve_pspecs(shapes) if rules == "serve" \
        else param_pspecs(shapes)
    clean = sanitize_pspecs(specs, shapes, mesh)
    flat_specs = jax.tree.leaves(clean, is_leaf=lambda x: isinstance(x, P))
    flat_shapes = jax.tree.leaves(shapes)
    assert len(flat_specs) == len(flat_shapes)
    sharded = 0
    for spec, shaped in zip(flat_specs, flat_shapes):
        for size, d in zip(shaped.shape, tuple(spec)):
            if d is not None:
                sharded += 1
                assert size % _axis_product(mesh, d) == 0, \
                    (arch, shaped.shape, spec)
    if rules == "train":
        # the sweep must not sanitize everything away
        assert sharded > 0, arch


def test_serve_pspecs_place_served_head_by_mu():
    """The served head (sigma made once from rho) is placed as the
    training form is: mu and sigma both take mu's spec, so mesh serving
    reads the same columns it did."""
    from repro.core.bayesian import GaussianServed
    from repro.launch.engine.runner import served_params
    cfg = get_config("qwen2_1_5b")
    shapes = jax.eval_shape(lambda k: M.init_params(k, cfg),
                            jax.random.key(0))
    served = jax.eval_shape(served_params, shapes)
    want = serve_pspecs(shapes)["head"]["q"].mu
    assert want == P(None, "model")
    got = serve_pspecs(served)["head"]["q"]
    assert isinstance(got, GaussianServed)
    assert got.mu == want and got.sigma == want
    clean = sanitize_pspecs(serve_pspecs(served), served, MESH_1x4)
    assert clean["head"]["q"].sigma == P(None, "model")


# ---------------------------------------------------------------------------
# shardings_for round-trip on a real 1x2 debug mesh (subprocess: the
# forced device count must be pinned before jax initializes)
# ---------------------------------------------------------------------------

_ROUNDTRIP = textwrap.dedent("""
    import json
    import jax, numpy as np
    from repro.configs.registry import get_config, reduced
    from repro.launch.mesh import make_debug_mesh
    from repro.models import registry as M
    from repro.sharding.partition import shardings_for

    cfg = reduced(get_config("qwen2_1_5b"))
    params = M.init_params(jax.random.key(0), cfg)
    mesh = make_debug_mesh((1, 2), ("data", "model"))
    assert mesh.shape == {"data": 1, "model": 2}, mesh
    placed = jax.device_put(params, shardings_for(params, mesh))
    same = jax.tree.all(jax.tree.map(
        lambda a, b: bool(np.array_equal(np.asarray(a), np.asarray(b))),
        params, placed))
    n_sharded = sum(
        1 for leaf in jax.tree.leaves(placed)
        if not leaf.sharding.is_fully_replicated)
    print(json.dumps({"same": bool(same), "n_sharded": n_sharded}))
""")


def test_shardings_for_roundtrip_1x2():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
    out = subprocess.run([sys.executable, "-c", _ROUNDTRIP],
                         capture_output=True, text=True, env=env,
                         timeout=300, cwd=REPO)
    assert out.returncode == 0, out.stdout + out.stderr
    rec = json.loads(out.stdout.strip().splitlines()[-1])
    assert rec["same"], "device_put round-trip changed param bytes"
    assert rec["n_sharded"] > 0, "nothing sharded on a 2-device mesh"


def test_debug_mesh_exact_tile_keeps_shape():
    # this 1-device process CAN tile (1, 1)
    mesh = make_debug_mesh((1, 1), ("data", "model"))
    assert mesh.axis_names == ("data", "model")
