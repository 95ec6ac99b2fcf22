"""Multi-device integration (subprocess with 8 virtual host devices):
sharded staged train step, checkpoint→elastic re-mesh→restore→resume —
the fault-tolerance story end to end (DESIGN.md §5)."""
from __future__ import annotations

import os
import subprocess
import sys
import textwrap

import pytest

# Each test spawns a fresh interpreter that compiles sharded train steps on
# 8 virtual devices; raise the CI per-test cap.
pytestmark = pytest.mark.timeout(300)

SCRIPT = textwrap.dedent(
    """
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax, jax.numpy as jnp, numpy as np, tempfile
    from repro.configs import reduced_config
    from repro.data import SyntheticLMDataset
    from repro.dist.sharding import use_mesh
    from repro.dist.fault import remesh_plan, FailureSimulator
    from repro.checkpoint import CheckpointManager
    from repro.launch.mesh import make_host_mesh, make_mesh
    from repro.models.config import ShapeSpec
    from repro.runtime.train import (abstract_train_state, build_train_step,
                                     init_train_state, train_state_shardings)

    cfg = reduced_config("deepseek-7b")
    shape = ShapeSpec("t", "train", 32, 8)
    ds = SyntheticLMDataset(cfg, shape, seed=0)
    ckdir = tempfile.mkdtemp()
    mgr = CheckpointManager(ckdir, keep=2, async_commit=False)
    sim = FailureSimulator({4: 4})  # lose half the chips at step 4

    mesh = make_host_mesh(model_parallel=2)
    assert dict(mesh.shape) == {"data": 4, "model": 2}
    with use_mesh(mesh):
        sh = train_state_shardings(cfg)
        state = init_train_state(jax.random.PRNGKey(0), cfg)
        # initialised straight into its shardings, not staged on one device
        placed = jax.tree.map(lambda a, s: a.sharding.is_equivalent_to(s, a.ndim), state, sh)
        assert all(jax.tree.leaves(placed)), "state not initialised under its shardings"
        art = build_train_step(cfg, n_microbatches=2, donate=False)
        losses = []
        step = 0
        while step < 4:
            batch = {k: jnp.asarray(v) for k, v in ds.batch_for_step(step).items()}
            state, m = art(state, batch)
            losses.append(float(m["loss"]))
            step += 1
            mgr.save(step, state, block=True)
    assert sim.check(4) == 4, "failure injected"

    # elastic re-mesh: 8 chips → 4 alive, model_parallel preserved at 2
    plan = remesh_plan(8, 4, model_parallel=2)
    assert plan.shape == (2, 2), plan
    mesh2 = make_mesh(plan.shape, plan.axes, devices=jax.devices()[: plan.n_chips])
    with use_mesh(mesh2):
        template = abstract_train_state(cfg)
        restored_step, state2 = mgr.restore(template)
        assert restored_step == 4
        art2 = build_train_step(cfg, n_microbatches=2, donate=False)
        # the data pipeline cursor IS the step counter: resume deterministically
        while restored_step < 8:
            batch = {k: jnp.asarray(v) for k, v in ds.batch_for_step(restored_step).items()}
            state2, m = art2(state2, batch)
            restored_step += 1
            losses.append(float(m["loss"]))
    assert int(state2.step) == 8
    assert all(np.isfinite(losses)), losses
    assert losses[-1] < losses[0] + 0.5  # still training sanely after re-mesh
    print("ELASTIC_OK", losses[0], "->", losses[-1])
    """
)


def _child_env() -> dict:
    """The child simulates its mesh on virtual host devices: pin it to the
    CPU explicitly rather than inheriting a platform (on a machine with a
    chip it would otherwise take the chip, or fail behind a parent that
    holds it)."""
    env = dict(os.environ)
    env["PYTHONPATH"] = "src"
    env["JAX_PLATFORMS"] = "cpu"
    env.pop("XLA_FLAGS", None)
    return env


def test_elastic_remesh_end_to_end():
    env = _child_env()
    r = subprocess.run(
        [sys.executable, "-c", SCRIPT], env=env, capture_output=True, text=True,
        timeout=900, cwd=os.path.join(os.path.dirname(__file__), ".."),
    )
    assert r.returncode == 0, f"stdout:\n{r.stdout[-3000:]}\nstderr:\n{r.stderr[-3000:]}"
    assert "ELASTIC_OK" in r.stdout


HIER_SCRIPT = textwrap.dedent(
    """
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax, jax.numpy as jnp, numpy as np
    from functools import partial
    from jax.sharding import Mesh, PartitionSpec as P
    from jax import shard_map
    from repro.dist.collectives import hierarchical_psum
    from repro.launch.mesh import make_mesh

    mesh = make_mesh((2, 4), ("pod", "data"))
    x = jnp.arange(8 * 16, dtype=jnp.float32).reshape(8, 16)

    @partial(shard_map, mesh=mesh, in_specs=P(("pod", "data"), None),
             out_specs=P(("pod", "data"), None))
    def hier(v):
        return hierarchical_psum(v, pod_axis="pod", inner_axis="data")

    @partial(shard_map, mesh=mesh, in_specs=P(("pod", "data"), None),
             out_specs=P(("pod", "data"), None))
    def flat(v):
        return jax.lax.psum(v, ("pod", "data"))

    a, b = hier(x), flat(x)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-6)
    # HLO of the hierarchical version must contain the 3-stage pattern
    lowered = jax.jit(hier).lower(x).compile().as_text()
    assert "reduce-scatter" in lowered and "all-gather" in lowered, "3-stage pattern"
    print("HIER_OK")
    """
)


def test_hierarchical_psum_matches_flat():
    env = _child_env()
    r = subprocess.run(
        [sys.executable, "-c", HIER_SCRIPT], env=env, capture_output=True, text=True,
        timeout=600, cwd=os.path.join(os.path.dirname(__file__), ".."),
    )
    assert r.returncode == 0, f"stdout:\n{r.stdout[-2000:]}\nstderr:\n{r.stderr[-2000:]}"
    assert "HIER_OK" in r.stdout


DECODE_SCRIPT = textwrap.dedent(
    """
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import jax, jax.numpy as jnp, numpy as np
    from repro.configs import reduced_config
    from repro.dist.sharding import use_mesh
    from repro.launch.mesh import make_host_mesh
    from repro.models import attention as attn_mod
    from repro.models import decode_step, init_cache, init_params

    mesh = make_host_mesh(model_parallel=2)
    assert dict(mesh.shape) == {"data": 2, "model": 2}
    seq_sharded = attn_mod.seq_sharded
    for name, kw, select in [("deepseek-7b", {}, True),
                             ("deepseek-7b", {"kv_shard": "heads"}, False),
                             ("deepseek-v2-lite-ep8", {}, True)]:
        cfg = reduced_config(name).replace(dtype="float32", **kw)
        params = init_params(jax.random.PRNGKey(1), cfg)
        caches = jax.tree.map(lambda x: jax.random.normal(jax.random.PRNGKey(2), x.shape, x.dtype),
                              init_cache(cfg, 4, 16))
        tok = jnp.arange(4, dtype=jnp.int32)[:, None]
        pos = jnp.asarray([0, 5, 15, 9], jnp.int32)
        want = jax.jit(lambda p, t, c, q: decode_step(p, t, c, q, cfg))(params, tok, caches, pos)
        seen = []
        attn_mod.seq_sharded = lambda axes: seen.append(seq_sharded(axes)) or seen[-1]
        with use_mesh(mesh):
            got = jax.jit(lambda p, t, c, q: decode_step(p, t, c, q, cfg))(params, tok, caches, pos)
        attn_mod.seq_sharded = seq_sharded
        assert seen and all(s == select for s in seen), (name, kw, seen)
        for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
            np.testing.assert_allclose(np.asarray(g), np.asarray(w), rtol=1e-5, atol=1e-5)
    print("DECODE_OK")
    """
)


def test_sharded_decode_selects_by_cache_layout():
    """On a (data 2, model 2) mesh a sequence-sharded cache takes the select
    and a head-sharded one the row write; both match the unsharded decode
    up to float32 reduction order."""
    env = _child_env()
    r = subprocess.run(
        [sys.executable, "-c", DECODE_SCRIPT], env=env, capture_output=True, text=True,
        timeout=600, cwd=os.path.join(os.path.dirname(__file__), ".."),
    )
    assert r.returncode == 0, f"stdout:\n{r.stdout[-2000:]}\nstderr:\n{r.stderr[-2000:]}"
    assert "DECODE_OK" in r.stdout
