"""The cross-process wire: SocketTransport framing/rendezvous, collectives
over both transports, non-blocking poll contract, and the two-OS-process
ring-all-reduce acceptance path (spawned via multiprocessing)."""
from __future__ import annotations

import time

import numpy as np
import pytest

from repro.core import (
    ChannelHub,
    SocketTransport,
    SpCommGroup,
    SpComputeEngine,
    SpData,
    SpSerializer,
    SpTaskGraph,
    SpWorkerTeamBuilder,
    mpi_broadcast,
    mpi_recv,
    mpi_send,
)
from repro.core.comm import _RecvRequest
from repro.dist.collectives import ring_all_gather, ring_all_reduce
from repro.launch.rendezvous import run_ring_reduce


@pytest.fixture()
def engine():
    eng = SpComputeEngine(SpWorkerTeamBuilder.team_of_cpu_workers(4))
    yield eng
    eng.stop()


@pytest.fixture()
def socket_pair():
    """Two socket transports (ranks 0, 1) in one process over localhost."""
    t0 = SocketTransport(0, 2)
    t1 = SocketTransport(1, 2, port=t0.port)
    yield t0, t1
    t0.close()
    t1.close()


def _socket_ring(size: int):
    t0 = SocketTransport(0, size)
    rest = [SocketTransport(r, size, port=t0.port) for r in range(1, size)]
    return [t0, *rest]


# ---------------------------------------------------------------------------
# transport basics
# ---------------------------------------------------------------------------

def test_socket_transport_frames_keys_and_payloads(socket_pair):
    t0, t1 = socket_pair
    tag = ("rar", 3, "rs", 0)  # the collectives' structured-tuple tags
    t0.post((0, 1, tag), {"chunk": np.arange(5, dtype=np.float32), "step": 0})
    deadline = time.monotonic() + 5.0
    ok, msg = False, None
    while not ok and time.monotonic() < deadline:
        ok, msg = t1.poll((0, 1, tag))
        if not ok:
            time.sleep(0.002)
    assert ok
    np.testing.assert_array_equal(msg["chunk"], np.arange(5, dtype=np.float32))
    assert msg["step"] == 0
    # wrong tag / wrong direction never match
    assert t1.poll((0, 1, ("rar", 3, "rs", 1)))[0] is False
    assert t0.poll((0, 1, tag))[0] is False


def test_socket_transport_prunes_and_counts(socket_pair):
    t0, t1 = socket_pair
    for step in range(20):
        t0.post((0, 1, step), step)
    got = 0
    deadline = time.monotonic() + 5.0
    while got < 20 and time.monotonic() < deadline:
        ok, msg = t1.poll((0, 1, got))
        if ok:
            assert msg == got
            got += 1
        else:
            time.sleep(0.002)
    assert got == 20
    st = t1.stats()
    assert st["boxes"] == 0 and st["queued"] == 0
    assert st["received"] == 20 and st["delivered"] == 20
    assert t0.stats()["posted"] == 20


def test_socket_poll_is_nonblocking(socket_pair):
    t0, t1 = socket_pair
    t0_ = time.perf_counter()
    for _ in range(500):
        ok, _msg = t1.poll((0, 1, "never-posted"))
        assert not ok
    assert time.perf_counter() - t0_ < 1.0  # pure dict lookups, no recv()


def test_recv_request_test_only_polls():
    """CommRequest.test() must stay non-blocking: its only transport call is
    poll() — never a blocking receive — so the comm thread's test-any loop
    keeps progressing other requests."""

    class RecordingTransport:
        def __init__(self):
            self.calls = []

        def poll(self, key):
            self.calls.append(("poll", key))
            return False, None

        def __getattr__(self, name):  # any other method => contract breach
            raise AssertionError(f"request touched transport.{name}")

    tr = RecordingTransport()
    req = _RecvRequest(tr, (0, 1, "t"), ref=None)
    for _ in range(3):
        assert req.test() is False
    assert tr.calls == [("poll", (0, 1, "t"))] * 3


def test_sp_serialize_object_roundtrips_both_transports(engine, socket_pair):
    class Grid:
        def __init__(self, values):
            self.values = values

        def sp_serialize(self, s: SpSerializer) -> None:
            s.append_array(self.values)

        @classmethod
        def sp_deserialize(cls, d) -> "Grid":
            return cls(d.next_array())

    from repro.core import register_wire_type

    register_wire_type(Grid)  # local class: not importable, register by hand

    t_sock0, t_sock1 = socket_pair
    for hub0, hub1 in ((ChannelHub(),) * 2, (t_sock0, t_sock1)):
        g0, g1 = SpCommGroup(0, 2, hub0), SpCommGroup(1, 2, hub1)
        tg0 = SpTaskGraph().compute_on(engine)
        tg1 = SpTaskGraph().compute_on(engine)
        m = SpData(Grid(np.full((2, 3), 7.0)), "m")
        r = SpData(None, "r")
        mpi_recv(tg1, g1, r, src=0, tag="grid", timeout=30.0)
        mpi_send(tg0, g0, m, dest=1, tag="grid")
        tg0.wait_all_tasks()
        tg1.wait_all_tasks()
        assert isinstance(r.value, Grid)
        r.value.values += 1.0  # received arrays must be writable in place
        np.testing.assert_array_equal(r.value.values, np.full((2, 3), 8.0))


# ---------------------------------------------------------------------------
# collective numerics over both transports (threads in one process)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("op", ["sum", "mean"])
def test_ring_all_reduce_socket_threads(engine, op):
    size = 3
    transports = _socket_ring(size)
    try:
        rng = np.random.default_rng(7)
        # 17 elements: not divisible by 3 — uneven chunk splits on the wire
        arrays = [rng.standard_normal(17).astype(np.float32) for _ in range(size)]
        groups = [
            SpCommGroup(r, size, transports[r], default_timeout=60.0)
            for r in range(size)
        ]
        graphs = [SpTaskGraph().compute_on(engine) for _ in range(size)]
        cells = [SpData(arrays[r].copy(), f"s{r}") for r in range(size)]
        for r in range(size):
            ring_all_reduce(graphs[r], groups[r], cells[r], op=op)
        for g in graphs:
            g.wait_all_tasks()
        expected = np.sum(np.stack(arrays).astype(np.float64), axis=0)
        if op == "mean":
            expected = expected / size
        for r in range(size):
            np.testing.assert_allclose(cells[r].value, expected, rtol=1e-5, atol=1e-6)
        for t in transports:
            assert t.stats()["boxes"] == 0  # all mailboxes drained + pruned
    finally:
        for t in transports:
            t.close()


def test_ring_all_gather_and_broadcast_socket_threads(engine):
    size = 2
    transports = _socket_ring(size)
    try:
        groups = [
            SpCommGroup(r, size, transports[r], default_timeout=60.0)
            for r in range(size)
        ]
        graphs = [SpTaskGraph().compute_on(engine) for _ in range(size)]

        cells = [SpData(np.arange(4) + 10 * r, f"x{r}") for r in range(size)]
        views = [
            ring_all_gather(graphs[r], groups[r], cells[r]) for r in range(size)
        ]
        bcells = [
            SpData(np.linspace(0, 1, 5) if r == 0 else None, f"b{r}")
            for r in range(size)
        ]
        for r in range(size):
            mpi_broadcast(graphs[r], groups[r], bcells[r], root=0)
        for g in graphs:
            g.wait_all_tasks()

        for r in range(size):
            got = views[r].get_value()
            assert len(got) == size
            for src in range(size):
                np.testing.assert_array_equal(got[src], np.arange(4) + 10 * src)
            np.testing.assert_array_equal(bcells[r].value, np.linspace(0, 1, 5))
    finally:
        for t in transports:
            t.close()


# ---------------------------------------------------------------------------
# the acceptance path: two OS processes over real TCP
# ---------------------------------------------------------------------------

def test_two_process_ring_all_reduce_over_tcp():
    """Two spawned processes reduce float32[4099] (odd: non-divisible
    chunks) over the socket transport; the sum must match the NumPy
    reference bit-for-bit (each element is one float32 addition at size 2),
    the mean must match allclose, and both ranks must agree."""
    size, n = 2, 4099
    results = run_ring_reduce(size, n, steps=2, timeout=300.0)
    arrays = [
        np.random.default_rng(r).standard_normal(n).astype(np.float32)
        for r in range(size)
    ]
    expected_sum = arrays[0] + arrays[1]
    for rank in range(size):
        got = results[rank]["sum"]
        assert got.dtype == np.float32
        np.testing.assert_array_equal(got, expected_sum)  # bit-for-bit
        np.testing.assert_allclose(
            results[rank]["mean"], expected_sum / size, rtol=1e-6
        )
        # every per-step mailbox was drained and pruned on both ranks
        st = results[rank]["stats"]
        assert st["boxes"] == 0 and st["queued"] == 0
        assert st["received"] == st["delivered"] > 0
    np.testing.assert_array_equal(results[0]["sum"], results[1]["sum"])


def test_spawned_ranks_never_initialise_a_jax_backend(monkeypatch):
    """Rank processes import JAX but must never initialise a backend: on a
    machine with a chip, a child that did would take the chip from (or hang
    behind) the parent that holds it.  Under a platform that cannot
    initialise, any backend use in a rank would kill it."""
    monkeypatch.setenv("JAX_PLATFORMS", "no_such_platform")
    results = run_ring_reduce(2, 64, timeout=120.0)
    np.testing.assert_array_equal(results[0]["sum"], results[1]["sum"])
