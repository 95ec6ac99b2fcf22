"""Substrate tests: optimizer, schedules, data pipeline, checkpointing,
sharding rules, gradient compression, elastic re-mesh planning."""
from __future__ import annotations

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.checkpoint import CheckpointManager
from repro.data import Prefetcher, SyntheticLMDataset
from repro.dist.collectives import compress_int8, compress_tree, decompress_int8, init_residuals
from repro.dist.fault import remesh_plan
from repro.dist.sharding import safe_spec, use_mesh
from repro.launch.mesh import make_mesh
from repro.models.config import ShapeSpec
from repro.configs import reduced_config
from repro.optim import (
    adamw_init,
    adamw_update,
    adafactor_init,
    adafactor_update,
    clip_by_global_norm,
    cosine_schedule,
    global_norm,
    linear_warmup_cosine,
)


# ---------------------------------------------------------------------------
# optimizer
# ---------------------------------------------------------------------------

def test_adamw_first_step_matches_analytic():
    params = {"w": jnp.array([1.0, 2.0])}
    grads = {"w": jnp.array([0.1, -0.2])}
    state = adamw_init(params)
    new_p, _ = adamw_update(
        grads, state, params, lr=jnp.float32(0.01), step=jnp.int32(0), weight_decay=0.0
    )
    # bias-corrected first step ⇒ update ≈ lr·sign(g)
    np.testing.assert_allclose(
        np.asarray(new_p["w"]), np.array([1.0 - 0.01, 2.0 + 0.01]), rtol=1e-4
    )


def test_adamw_reduces_quadratic():
    params = {"w": jnp.array([5.0, -3.0])}
    state = adamw_init(params)
    for step in range(200):
        grads = {"w": 2 * params["w"]}
        params, state = adamw_update(
            grads, state, params, lr=jnp.float32(0.05), step=jnp.int32(step), weight_decay=0.0
        )
    assert float(jnp.abs(params["w"]).max()) < 0.5


def test_adafactor_shapes_and_descent():
    params = {"w": jnp.ones((8, 4)), "b": jnp.ones((4,))}
    state = adafactor_init(params)
    assert state["w"]["vr"].shape == (8,) and state["w"]["vc"].shape == (4,)
    assert state["b"]["v"].shape == (4,)
    loss0 = float(jnp.sum(params["w"] ** 2))
    for step in range(50):
        grads = jax.tree.map(lambda p: 2 * p, params)
        params, state = adafactor_update(
            grads, state, params, lr=jnp.float32(0.05), step=jnp.int32(step)
        )
    assert float(jnp.sum(params["w"] ** 2)) < loss0


def test_clip_by_global_norm():
    tree = {"a": jnp.array([3.0]), "b": jnp.array([4.0])}
    clipped, norm = clip_by_global_norm(tree, 1.0)
    assert abs(float(norm) - 5.0) < 1e-6
    assert abs(float(global_norm(clipped)) - 1.0) < 1e-5


def test_schedules():
    s = cosine_schedule(1.0, 100)
    assert float(s(jnp.int32(0))) == pytest.approx(1.0)
    assert float(s(jnp.int32(100))) == pytest.approx(0.1, abs=1e-3)
    w = linear_warmup_cosine(1.0, 10, 110)
    assert float(w(jnp.int32(5))) == pytest.approx(0.5)


# ---------------------------------------------------------------------------
# gradient compression
# ---------------------------------------------------------------------------

@settings(max_examples=30, deadline=None)
@given(st.lists(st.floats(-100, 100, allow_nan=False), min_size=1, max_size=32))
def test_property_int8_roundtrip_bounded(vals):
    g = jnp.asarray(vals, jnp.float32)
    q, scale = compress_int8(g)
    err = jnp.abs(decompress_int8(q, scale) - g)
    assert float(err.max()) <= float(scale) / 2 + 1e-6


def test_error_feedback_accumulates():
    grads = {"w": jnp.full((16,), 0.001, jnp.float32)}
    res = init_residuals(grads)
    total = jnp.zeros((16,))
    for _ in range(50):
        deq, res = compress_tree(grads, res)
        total = total + deq["w"]
    # with error feedback the long-run mean approaches the true gradient
    np.testing.assert_allclose(np.asarray(total / 50), 0.001, rtol=0.2)


# ---------------------------------------------------------------------------
# data pipeline
# ---------------------------------------------------------------------------

def test_data_determinism_and_rule():
    cfg = reduced_config("deepseek-7b")
    shape = ShapeSpec("t", "train", 16, 4)
    ds1 = SyntheticLMDataset(cfg, shape, seed=7)
    ds2 = SyntheticLMDataset(cfg, shape, seed=7)
    b1, b2 = ds1.batch_for_step(5), ds2.batch_for_step(5)
    np.testing.assert_array_equal(b1["tokens"], b2["tokens"])
    # labels are the next-token shift of tokens
    np.testing.assert_array_equal(b1["labels"][:, :-1], b1["tokens"][:, 1:])
    # different steps → different data
    assert not np.array_equal(b1["tokens"], ds1.batch_for_step(6)["tokens"])


def test_prefetcher_order_and_restart():
    cfg = reduced_config("deepseek-7b")
    shape = ShapeSpec("t", "train", 16, 4)
    ds = SyntheticLMDataset(cfg, shape, seed=1)
    pf = Prefetcher(ds, start_step=3, depth=2)
    try:
        s0, b0 = pf.get()
        s1, b1 = pf.get()
        assert (s0, s1) == (3, 4)
        np.testing.assert_array_equal(b0["tokens"], ds.batch_for_step(3)["tokens"])
    finally:
        pf.stop()


# ---------------------------------------------------------------------------
# checkpointing
# ---------------------------------------------------------------------------

def test_checkpoint_roundtrip_retention_corruption(tmp_path):
    mgr = CheckpointManager(str(tmp_path), keep=2, async_commit=False)
    state = {"w": jnp.arange(6, dtype=jnp.float32).reshape(2, 3), "step": jnp.int32(4)}
    for s in (1, 2, 3):
        mgr.save(s, state, block=True)
    assert mgr.all_steps() == [2, 3]  # retention
    step, restored = mgr.restore(state)
    assert step == 3
    np.testing.assert_array_equal(np.asarray(restored["w"]), np.asarray(state["w"]))
    # corruption detection
    d = os.path.join(str(tmp_path), "step_000000003")
    victim = [f for f in os.listdir(d) if f.endswith(".npy")][0]
    with open(os.path.join(d, victim), "r+b") as f:
        f.seek(-4, 2)
        f.write(b"\xde\xad\xbe\xef")
    with pytest.raises(IOError, match="corruption"):
        mgr.restore(state)


def test_checkpoint_async_and_crash_tmp_cleanup(tmp_path):
    mgr = CheckpointManager(str(tmp_path), keep=3)
    state = {"w": jnp.ones((4,))}
    mgr.save(10, state)
    mgr.wait()
    assert mgr.latest_step() == 10
    # simulate a crash leaving a tmp dir
    os.makedirs(os.path.join(str(tmp_path), "step_000000099.tmp"))
    mgr2 = CheckpointManager(str(tmp_path), keep=3)
    assert mgr2.latest_step() == 10
    assert not any(d.endswith(".tmp") for d in os.listdir(str(tmp_path)))


# ---------------------------------------------------------------------------
# sharding rules + re-mesh
# ---------------------------------------------------------------------------

def test_safe_spec_drops_indivisible_axes():
    mesh = make_mesh((1, 1), ("data", "model"))
    with use_mesh(mesh):
        spec = safe_spec((8, 40), ("batch", "heads"))
        assert spec == jax.sharding.PartitionSpec(None, None) or all(
            e is None or isinstance(e, (str, tuple)) for e in spec
        )
    # synthetic 16-way mesh check via rules math (no devices needed):
    from repro.dist.sharding import default_rules

    class FakeMesh:
        shape = {"data": 16, "model": 16}

    spec = safe_spec((40, 64), ("heads", "ff"), mesh=FakeMesh(), rules=default_rules())
    assert spec[0] is None  # 40 % 16 != 0 → replicated
    assert spec[1] == "model"


def test_remesh_plan_shrinks_data_axis():
    p = remesh_plan(256, 13, model_parallel=16)
    assert p.shape == (15, 16) and p.n_chips == 240 and p.dropped_chips == 16
    p2 = remesh_plan(512, 0, model_parallel=16, pod_size=256)
    assert p2.shape == (2, 16, 16)
    p3 = remesh_plan(512, 260, model_parallel=16, pod_size=256)  # one pod lost
    assert p3.shape == (15, 16)
    with pytest.raises(RuntimeError):
        remesh_plan(16, 8, model_parallel=16)
