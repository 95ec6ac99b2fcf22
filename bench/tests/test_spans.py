"""The readers of the program's own spans (``bench/metrics/_spans.py`` and
the metrics that use it), on span lists made by hand and on one recorded
by a small ``ServeEngine`` run under the profiler."""
import time
from types import SimpleNamespace

import jax
import numpy as np
import pytest

from bench.harness import core
from repro.core import trace

S = 1_000_000_000  # ns per second


def _read(name, run):
    return core._reader(name)(run)


def _span(name, a, b, thread="w0"):
    sp = trace.Span(name)
    sp.t0, sp.t1, sp.thread = int(a * S), int(b * S), thread
    return sp


@pytest.fixture()
def buffer(monkeypatch):
    buf = trace.SpanBuffer()
    monkeypatch.setattr(trace, "SPANS", buf)
    return buf


def _run(steps, **record):
    return SimpleNamespace(record={"traced_steps": steps, **record})


#: the traced stretch: steps from 1.0 s to 2.0 s on the harness's clock
STEPS = [[1.0, 1.1, 0, [], [], 0], [1.2, 1.3, 0, [], [], 0], [1.9, 2.0, 0, [], [], 0]]


@pytest.mark.parametrize("name", ["step_host_ms.chat", "step_host_ms.summarize"])
def test_step_host_time_leaves_out_the_union_of_device_waits(buffer, name):
    for sp in [
        _span("serve.step", 1.0, 1.1, "main"),
        _span("serve.device_wait", 1.02, 1.05), _span("serve.device_wait", 1.04, 1.06, "w1"),
        _span("serve.step", 1.2, 1.3, "main"),
        _span("serve.device_wait", 1.25, 1.35),  # runs past its step: only 50 ms inside
        _span("serve.step", 1.9, 2.0, "main"),
        _span("serve.step", 2.1, 2.2, "main"),  # after the stretch
        _span("serve.writeback", 1.91, 1.99),  # host time, not a wait
    ]:
        buffer.append(sp)
    assert _read(name, _run(STEPS)) == pytest.approx((60 + 50 + 100) / 3)


@pytest.mark.parametrize("name", ["writeback_share.chat", "writeback_share.summarize"])
def test_writeback_share_of_the_stretch(buffer, name):
    for sp in [_span("serve.writeback", 1.5, 1.6), _span("serve.writeback", 1.7, 1.75, "w1"),
               _span("serve.writeback", 0.5, 0.9), _span("serve.step", 1.0, 2.0)]:
        buffer.append(sp)
    assert _read(name, _run(STEPS)) == pytest.approx(15.0)


@pytest.mark.parametrize("name", ["step_host_ms.chat", "writeback_share.summarize"])
def test_span_readers_read_nothing_where_nothing_was_recorded(buffer, monkeypatch, name):
    assert _read(name, _run(STEPS)) is None  # empty buffer
    buffer.append(_span("serve.step", 3.0, 3.1))
    buffer.append(_span("serve.writeback", 3.0, 3.1))
    assert _read(name, _run(STEPS)) is None  # nothing inside the stretch
    assert _read(name, _run([])) is None  # no traced steps
    monkeypatch.delattr(trace, "SPANS")  # a program that keeps no buffer
    assert _read(name, _run(STEPS)) is None


def _client(due, arrival, admit):
    req = None if arrival is None else SimpleNamespace(t_arrival=arrival, t_admit=admit)
    return SimpleNamespace(due=due, req=req)


def test_queue_wait_is_the_median_over_the_window_requests():
    clients = [_client(10.0, 10.001, 10.011), _client(11.0, 11.0, 11.030),
               _client(12.0, 12.0, 12.020), _client(13.0, None, None),  # turned away
               _client(70.0, 70.0, 71.0)]  # due after the window
    run = _run(STEPS, clients=clients, window=(10.0, 61.0))
    assert _read("queue_wait_ms_p50.chat", run) == pytest.approx(20.0)
    old = [SimpleNamespace(due=10.0, req=SimpleNamespace(t_arrival=10.0))]  # no t_admit
    assert _read("queue_wait_ms_p50.chat", _run(STEPS, clients=old, window=(10.0, 61.0))) is None


def test_readers_on_a_recorded_serving_run(tmp_path):
    """The engine's own spans, stamped on the clock of the harness's step
    records, read through the same readers."""
    from repro.configs import reduced_config
    from repro.models import init_params
    from repro.serving import ServeEngine

    cfg = reduced_config("deepseek-7b")
    params = init_params(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(0)

    def wave(eng):
        clients, steps = [], []
        for i, n in enumerate((5, 9, 7, 6)):
            due = time.perf_counter()
            req = eng.submit(rng.integers(0, cfg.vocab, size=n).astype(np.int32), 6,
                             temperature=0.7 if i % 2 else 0.0, top_k=5, seed=i)
            clients.append(SimpleNamespace(due=due, req=req))
        while eng.scheduler.queue_depth or eng.n_running:
            t = time.perf_counter()
            eng.step()
            steps.append([t, time.perf_counter(), 0, [], [], 0])
        return clients, steps

    with ServeEngine(cfg, params, n_slots=4, max_seq=32, block_size=4) as eng:
        wave(eng)  # compile outside the session
        t0 = time.perf_counter()
        with jax.profiler.trace(str(tmp_path)):
            clients, steps = wave(eng)
    run = _run(steps, clients=clients, window=(t0, time.perf_counter()))
    mean_step_ms = 1e3 * sum(b - a for a, b, *_ in steps) / len(steps)
    host = _read("step_host_ms.chat", run)
    assert 0 < host < mean_step_ms
    assert 0 < _read("writeback_share.chat", run) < 100
    assert _read("queue_wait_ms_p50.chat", run) >= 0
