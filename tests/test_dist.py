"""repro.dist unit tests: ring collectives over a multi-rank ChannelHub,
gradient compression bounds, duplicated-task cancellation, mesh context,
and failure-simulation → re-mesh planning."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (
    ChannelHub,
    SpCommGroup,
    SpComputeEngine,
    SpData,
    SpTaskGraph,
    SpWorkerTeamBuilder,
)
from repro.dist.collectives import (
    compress_int8,
    decompress_int8,
    ring_all_gather,
    ring_all_reduce,
)
from repro.dist.fault import CancelToken, FailureSimulator, remesh_plan, run_duplicated
from repro.dist.sharding import current_mesh, safe_spec, use_mesh
from repro.configs import reduced_config
from repro.launch.mesh import SINGLE_POD, SINGLE_POD_AXES, make_host_mesh, make_mesh
from repro.models import init_params, param_shardings


@pytest.fixture()
def engine():
    eng = SpComputeEngine(SpWorkerTeamBuilder.team_of_cpu_workers(4))
    yield eng
    eng.stop()


# ---------------------------------------------------------------------------
# ring collectives over the hub
# ---------------------------------------------------------------------------

def _ranks(engine, size, hub):
    groups = [SpCommGroup(r, size, hub) for r in range(size)]
    graphs = [SpTaskGraph().compute_on(engine) for _ in range(size)]
    return groups, graphs


def test_ring_all_reduce_matches_psum(engine):
    size = 4
    rng = np.random.default_rng(0)
    # 18 elements: not divisible by 4, exercises uneven chunk splits
    arrays = [rng.standard_normal(18).astype(np.float32) for _ in range(size)]
    groups, graphs = _ranks(engine, size, ChannelHub())
    cells = [SpData(arrays[r].copy(), f"g{r}") for r in range(size)]
    views = [
        ring_all_reduce(graphs[r], groups[r], cells[r]) for r in range(size)
    ]
    for g in graphs:
        g.wait_all_tasks()

    # reference: jax.lax.psum over a named axis (vmap substrate)
    expected = np.asarray(
        jax.vmap(lambda v: jax.lax.psum(v, "i"), axis_name="i")(jnp.stack(arrays))
    )[0]
    for r in range(size):
        np.testing.assert_allclose(cells[r].value, expected, rtol=1e-5, atol=1e-6)
        np.testing.assert_allclose(views[r].get_value(), expected, rtol=1e-5, atol=1e-6)


def test_ring_all_reduce_mean_and_2d(engine):
    size = 3
    arrays = [np.full((2, 5), float(r + 1), np.float32) for r in range(size)]
    groups, graphs = _ranks(engine, size, ChannelHub())
    cells = [SpData(arrays[r], f"m{r}") for r in range(size)]
    for r in range(size):
        ring_all_reduce(graphs[r], groups[r], cells[r], op="mean")
    for g in graphs:
        g.wait_all_tasks()
    for r in range(size):
        assert cells[r].value.shape == (2, 5)
        np.testing.assert_allclose(cells[r].value, 2.0, rtol=1e-6)


def test_ring_all_gather_orders_by_rank(engine):
    size = 4
    groups, graphs = _ranks(engine, size, ChannelHub())
    cells = [SpData(np.arange(3) + 10 * r, f"x{r}") for r in range(size)]
    views = [
        ring_all_gather(graphs[r], groups[r], cells[r]) for r in range(size)
    ]
    for g in graphs:
        g.wait_all_tasks()
    for r in range(size):
        got = views[r].get_value()
        assert len(got) == size
        for src in range(size):
            np.testing.assert_array_equal(got[src], np.arange(3) + 10 * src)


def test_hub_stays_bounded_over_100_step_ring_loop(engine):
    """Regression: per-step tags used to leak one deque per (src, dst, tag)
    key forever; 100 reduce steps must leave the hub's mailbox dict empty."""
    size, steps = 2, 100
    hub = ChannelHub()
    groups, graphs = _ranks(engine, size, hub)
    base = [np.full(6, float(r + 1), np.float32) for r in range(size)]
    cells = [SpData(base[r].copy(), f"h{r}") for r in range(size)]
    for step in range(steps):
        for r in range(size):
            cells[r].value = base[r].copy()
            ring_all_reduce(graphs[r], groups[r], cells[r], tag=step)
        for g in graphs:
            g.wait_all_tasks()
        for r in range(size):
            np.testing.assert_array_equal(cells[r].value, np.full(6, 3.0, np.float32))
    st = hub.stats()
    assert st["boxes"] == 0 and st["queued"] == 0
    assert len(hub._boxes) == 0  # the dict itself is pruned, not just empty
    assert st["posted"] == st["delivered"] > 0


def test_ring_single_rank_identity(engine):
    hub = ChannelHub()
    g = SpTaskGraph().compute_on(engine)
    grp = SpCommGroup(0, 1, hub)
    x = SpData(np.ones(4, np.float32), "solo")
    v = ring_all_reduce(g, grp, x)
    g.wait_all_tasks()
    np.testing.assert_array_equal(v.get_value(), np.ones(4, np.float32))


# ---------------------------------------------------------------------------
# gradient compression
# ---------------------------------------------------------------------------

def test_compress_int8_roundtrip_bound_explicit():
    g = jnp.asarray([-100.0, -0.3, 0.0, 0.7, 99.9], jnp.float32)
    q, scale = compress_int8(g)
    assert q.dtype == jnp.int8
    err = jnp.abs(decompress_int8(q, scale) - g)
    assert float(err.max()) <= float(scale) / 2 + 1e-6


def test_compress_int8_zero_tensor():
    q, scale = compress_int8(jnp.zeros((7,), jnp.float32))
    np.testing.assert_array_equal(np.asarray(q), 0)
    np.testing.assert_allclose(np.asarray(decompress_int8(q, scale)), 0.0)


# ---------------------------------------------------------------------------
# fault tolerance
# ---------------------------------------------------------------------------

def test_run_duplicated_cancels_losers():
    # one worker ⇒ copies run sequentially ⇒ the winner is copy0 and every
    # other copy is cancelled at its pre-execution token check
    eng = SpComputeEngine(SpWorkerTeamBuilder.team_of_cpu_workers(1))
    try:
        tg = SpTaskGraph().compute_on(eng)
        x = SpData(7, "x")
        out = SpData(None, "out")
        view = run_duplicated(tg, lambda v: v * 3, [x], out, n=3, name="dup")
        tg.wait_all_tasks()
        assert view.get_value() == 21 and out.value == 21
        states = sorted(t.state for t in tg.tasks if t.name.startswith("dup.copy"))
        assert states == ["cancelled", "cancelled", "finished"]
    finally:
        eng.stop()


def test_run_duplicated_masks_a_crashing_copy():
    # a replica that raises must not claim the token or fail the graph;
    # a healthy replica still produces the value (the point of replication)
    eng = SpComputeEngine(SpWorkerTeamBuilder.team_of_cpu_workers(1))
    try:
        tg = SpTaskGraph().compute_on(eng)
        x = SpData(5, "x")
        out = SpData(None, "out")
        calls = []

        def flaky(v):
            calls.append(1)
            if len(calls) == 1:
                raise RuntimeError("injected replica crash")
            return v + 1

        view = run_duplicated(tg, flaky, [x], out, n=3, name="flaky")
        tg.wait_all_tasks()  # must NOT raise: the crash was masked
        assert view.get_value() == 6 and out.value == 6
    finally:
        eng.stop()


def test_run_duplicated_raises_when_all_copies_fail():
    eng = SpComputeEngine(SpWorkerTeamBuilder.team_of_cpu_workers(1))
    try:
        tg = SpTaskGraph().compute_on(eng)
        out = SpData(None, "out")

        def always_fails():
            raise RuntimeError("boom")

        run_duplicated(tg, always_fails, [], out, n=2, name="doomed")
        with pytest.raises(RuntimeError, match="all 2 duplicated copies failed"):
            tg.wait_all_tasks()
    finally:
        eng.stop()


def test_cancel_token_claims_once():
    tok = CancelToken()
    assert not tok.is_set()
    assert tok.set("a") and tok.winner == "a"
    assert not tok.set("b") and tok.winner == "a"
    assert tok.is_set() and tok.wait(0.01)


def test_failure_then_remesh_plan():
    sim = FailureSimulator({3: 2})
    assert sim.check(0) == 0
    lost = sim.check(3)
    assert lost == 2 and sim.total_lost == 2
    plan = remesh_plan(8, lost, model_parallel=2)
    assert plan.shape == (3, 2) and plan.axes == ("data", "model")
    assert plan.n_chips == 6 and plan.dropped_chips == 2
    with pytest.raises(RuntimeError):
        remesh_plan(8, 7, model_parallel=2)


def test_remesh_plan_validates():
    with pytest.raises(ValueError):
        remesh_plan(16, 0, model_parallel=0)
    with pytest.raises(ValueError):
        remesh_plan(512, 0, model_parallel=16, pod_size=40)


# ---------------------------------------------------------------------------
# mesh context
# ---------------------------------------------------------------------------

def test_use_mesh_nests_and_restores():
    assert current_mesh() is None
    m1 = make_mesh((1, 1), ("data", "model"))
    m2 = make_mesh((1,), ("data",))
    with use_mesh(m1):
        assert current_mesh() is m1
        with use_mesh(m2):
            assert current_mesh() is m2
        assert current_mesh() is m1
    assert current_mesh() is None


@pytest.mark.parametrize(
    "build",
    [
        lambda: make_host_mesh(),
        lambda: make_host_mesh(model_parallel=1),
        lambda: make_mesh((1,), ("data",)),
    ],
    ids=["host", "host_mp1", "one_axis"],
)
def test_meshes_have_auto_axes(build):
    """The sharding model annotates logical axes and relies on propagation:
    every mesh the repo builds must have Auto axes (``jax.make_mesh``'s
    default, Explicit, rejects the embedding gather)."""
    mesh = build()
    assert mesh.axis_types == (jax.sharding.AxisType.Auto,) * len(mesh.axis_names)


def test_production_mesh_has_auto_axes(monkeypatch):
    """``make_production_mesh`` over 256 (fake) devices: right shape, Auto axes."""
    import repro.launch.mesh as mesh_mod

    seen = {}

    def fake_make_mesh(shape, axes, axis_types=None, *, devices=None):
        seen.update(shape=shape, axes=axes, axis_types=axis_types)
        return None

    monkeypatch.setattr(mesh_mod.jax, "make_mesh", fake_make_mesh)
    mesh_mod.make_production_mesh()
    assert seen["shape"] == SINGLE_POD and seen["axes"] == SINGLE_POD_AXES
    assert seen["axis_types"] == (jax.sharding.AxisType.Auto,) * 2
    mesh_mod.make_production_mesh(multi_pod=True)
    assert seen["shape"] == (2, 16, 16)
    assert seen["axis_types"] == (jax.sharding.AxisType.Auto,) * 3


def test_auto_mesh_embedding_gather_partitions():
    """The failure the Auto axes fix: an embedding gather from a
    vocab-sharded table under the mesh, which Explicit axes refuse."""
    from repro.models.layers import embed_apply

    cfg = reduced_config("deepseek-7b")
    mesh = make_host_mesh()
    with use_mesh(mesh):
        params = init_params(jax.random.PRNGKey(0), cfg)
        params = jax.device_put(params, param_shardings(cfg))
        tokens = jnp.arange(8, dtype=jnp.int32).reshape(2, 4)
        out = jax.jit(lambda p, t: embed_apply(p, t, cfg))(params, tokens)
    assert out.shape == (2, 4, cfg.d_model)


def test_safe_spec_uses_each_mesh_axis_once():
    class FakeMesh:
        shape = {"data": 4, "model": 8}

    # both "experts" and "expert_ff" want "model"; only the first gets it
    spec = safe_spec((8, 16, 32), ("experts", "embed", "expert_ff"), mesh=FakeMesh())
    assert spec[0] == "model" and spec[1] is None and spec[2] is None
