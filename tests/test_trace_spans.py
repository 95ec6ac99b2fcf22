"""Spans of the task runtime and the serving tier (core/trace.py): recorded
under a JAX profiler session on the profiler's clock, into the process-wide
buffer, with parents and request ids; nothing recorded and nothing
allocated at a span site outside one."""
from __future__ import annotations

import collections
import math
import tracemalloc

import jax
import numpy as np
import pytest

from repro.configs import reduced_config
from repro.core import SpComputeEngine, SpData, SpTaskGraph, SpWorkerTeamBuilder, SpWrite, trace
from repro.models import init_cache, init_params
from repro.serving import ServeEngine
from repro.serving.engine import WRITEBACK_CHUNK

pytestmark = pytest.mark.timeout(180)

BLOCK = 4
MAX_SEQ = 32


@pytest.fixture(scope="module")
def served():
    cfg = reduced_config("deepseek-7b")
    return cfg, init_params(jax.random.PRNGKey(0), cfg)


def _drive(eng, cfg, seed):
    """Three requests, greedy and sampled, run to completion."""
    rng = np.random.default_rng(seed)
    reqs = [eng.submit(rng.integers(0, cfg.vocab, size=n).astype(np.int32), 6,
                       temperature=0.7 if i % 2 else 0.0, top_k=5, seed=i)
            for i, n in enumerate((5, 9, 7))]
    eng.run_until_drained(max_iters=50)
    return reqs


def _host_event_names(directory) -> set:
    from jax.profiler import ProfileData

    files = sorted(directory.rglob("*.xplane.pb"))
    assert files, "the profiler wrote no .xplane.pb"
    data = ProfileData.from_file(str(files[-1]))
    return {ev.name for plane in data.planes if plane.name.startswith("/host:")
            for line in plane.lines for ev in line.events}


@pytest.fixture(scope="module")
def recorded(served, tmp_path_factory):
    """One traced serving run: the spans it recorded, its requests, the
    block-table length of each request when it was released, and the names
    of the profile's host events."""
    cfg, params = served
    d = tmp_path_factory.mktemp("profile")
    with ServeEngine(cfg, params, n_slots=4, max_seq=MAX_SEQ, block_size=BLOCK) as eng:
        _drive(eng, cfg, 1)  # compile outside the session
        tables = {}
        release = eng.pool.release

        def spy(req_id, **kw):
            tables[req_id] = len(eng.pool.table_of(req_id).block_ids)
            return release(req_id, **kw)

        eng.pool.release = spy
        mark = trace.Span("mark").sid  # span ids only grow
        with jax.profiler.trace(str(d)):
            reqs = _drive(eng, cfg, 2)
        spans = [s for s in trace.SPANS.snapshot() if s.sid > mark]
    return spans, reqs, tables, _host_event_names(d)


def test_served_run_records_step_spans_as_parents_of_its_tasks(recorded):
    spans, reqs, _, _ = recorded
    by = collections.defaultdict(list)
    for s in spans:
        by[s.name].append(s)
    steps = {s.sid: s for s in by["serve.step"]}
    assert steps and all(s.parent is None for s in steps.values())
    for name in ("serve.decode", "serve.collect", "serve.prefill", "serve.install",
                 "serve.plan", "serve.wait"):
        assert by[name], name
        for s in by[name]:
            assert s.parent in steps, (name, s)
            step = steps[s.parent]
            if name in ("serve.plan", "serve.wait"):
                assert step.t0 <= s.t0 <= s.t1 <= step.t1
    assert sum(s.counts["admitted"] for s in steps.values()) == len(reqs)
    assert max(s.counts["decoding"] for s in steps.values()) == len(reqs)
    prefills = {s.req: s.counts["prompt_len"] for s in by["serve.prefill"]}
    assert prefills == {r.req_id: len(r.prompt) for r in reqs}
    # the one sampled request's keys are folded in every decode step it rides
    assert all(s.counts["sampled"] == 1 for s in by["serve.sample_keys"])
    assert all(steps[s.parent].thread != s.thread for s in by["serve.decode"])


def test_one_writeback_per_finished_request_of_its_whole_block_table(served, recorded):
    spans, reqs, tables, _ = recorded
    wb = [s for s in spans if s.name == "serve.writeback"]
    assert sorted(s.req for s in wb) == sorted(r.req_id for r in reqs)
    collects = {s.sid for s in spans if s.name == "serve.collect"}
    chunk = min(WRITEBACK_CHUNK, MAX_SEQ)
    cfg = served[0]
    row_bytes = sum(x.nbytes for x in jax.tree.leaves(init_cache(cfg, 1, 1)))  # both leaves
    for s in wb:
        req = next(r for r in reqs if r.req_id == s.req)
        rows = len(req.prompt) + len(req.out_tokens) - 1  # the last token is never fed
        chunks = math.ceil(rows / chunk)  # every block is written: the spans cover them all
        assert s.counts == {"blocks": tables[s.req], "rows": rows, "chunks": chunks,
                            "bytes": chunks * chunk * row_bytes}
        assert tables[s.req] == math.ceil(rows / BLOCK)
        assert s.parent in collects


def test_device_waits_nest_inside_the_codelet_that_blocks(recorded):
    spans = recorded[0]
    by_sid = {s.sid: s for s in spans}
    waits = [s for s in spans if s.name == "serve.device_wait"]
    owners = collections.Counter(by_sid[s.parent].name for s in waits)
    assert set(owners) == {"serve.collect", "serve.sample_keys", "serve.prefill"}
    # every collect blocks once on the sampled tokens
    assert owners["serve.collect"] == sum(1 for s in spans if s.name == "serve.collect")
    for s in waits:
        p = by_sid[s.parent]
        assert p.thread == s.thread and p.t0 <= s.t0 <= s.t1 <= p.t1


def test_every_span_is_a_host_event_of_the_profile(recorded):
    spans, _, _, names = recorded
    recorded_names = {s.name for s in spans}
    assert recorded_names <= names, recorded_names - names
    assert not any(n.startswith("bench.") for n in recorded_names)


def test_admission_is_stamped_on_the_arrival_clock(recorded):
    for r in recorded[1]:
        assert r.t_arrival <= r.t_admit <= r.t_first


def test_outside_a_session_nothing_is_recorded(served):
    cfg, params = served
    assert not trace.profiling()
    before = trace.SPANS.snapshot()
    with ServeEngine(cfg, params, n_slots=4, max_seq=32, block_size=BLOCK) as eng:
        reqs = _drive(eng, cfg, 3)
        assert eng._tg.trace_events == []
    assert all(r.done for r in reqs)
    assert trace.SPANS.snapshot() == before


def test_a_span_site_off_allocates_nothing():
    def site():
        with trace.span("serve.x") as sp:
            if sp is not None:
                sp.counts["n"] = 1
        sp = trace.current()
        if sp is not None:
            sp.req = 1

    site()
    tracemalloc.start()
    try:
        snap0 = tracemalloc.take_snapshot()
        for _ in range(10_000):
            site()
        snap1 = tracemalloc.take_snapshot()
    finally:
        tracemalloc.stop()
    flt = [tracemalloc.Filter(True, trace.__file__)]
    grown = [d for d in snap1.filter_traces(flt).compare_to(snap0.filter_traces(flt), "lineno")
             if d.count_diff > 0]
    assert grown == []


def test_graph_trace_records_task_spans_with_their_inserting_parent():
    """``trace=True`` keeps the span of every task run in the graph, outside
    any profiler session, with the span open at insertion as parent."""
    eng = SpComputeEngine(SpWorkerTeamBuilder.team_of_cpu_workers(2))
    try:
        tg = SpTaskGraph(trace=True).compute_on(eng)
        x = SpData(0, "x")
        outer = trace.Span("outer")
        outer.open()
        try:
            tg.task(SpWrite(x), lambda r: None, name="inside")
        finally:
            outer.close()
        tg.task(SpWrite(x), lambda r: None, name="after")
        tg.wait_all_tasks()
        ev = {e.name: e for e in tg.trace_events}
        assert ev["inside"].parent == outer.sid and ev["after"].parent is None
        assert all(0 < e.t0 <= e.t1 for e in ev.values())
        assert {e.counts["uid"] for e in ev.values()} == {t.uid for t in tg.tasks}
    finally:
        eng.stop()


def test_buffer_keeps_the_newest_and_counts_the_dropped():
    buf = trace.SpanBuffer(capacity=3)
    for i in range(5):
        buf.append(trace.Span(f"s{i}"))
    assert [s.name for s in buf.snapshot()] == ["s2", "s3", "s4"]
    assert buf.dropped == 2 and len(buf) == 3


def test_buffer_counts_every_span_appended_from_many_threads():
    """Workers append concurrently: no append and no drop may be lost."""
    import sys
    import threading

    buf = trace.SpanBuffer(capacity=100)
    n_threads, per_thread = 16, 500
    spans = [trace.Span("s") for _ in range(per_thread)]
    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        threads = [threading.Thread(target=lambda: [buf.append(s) for s in spans])
                   for _ in range(n_threads)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
    finally:
        sys.setswitchinterval(old)
    assert not any(t.is_alive() for t in threads)
    assert len(buf) == 100 and buf.dropped == n_threads * per_thread - 100


class _ReadSpy:
    """Stands in for a program's routed-choice counts and counts the host
    reads of them."""

    def __init__(self, a):
        self.a, self.reads = a, 0

    def __array__(self, dtype=None, copy=None):
        self.reads += 1
        return np.asarray(self.a, dtype)


def _routed_engine():
    """A reduced DeepSeek-V2-Lite share (2 of 8 experts held, 2 expert
    layers) whose programs' counts pass through read spies."""
    cfg = reduced_config("deepseek-v2-lite-ep8").replace(n_layers=3)
    eng = ServeEngine(cfg, init_params(jax.random.PRNGKey(0), cfg), n_slots=4, max_seq=MAX_SEQ,
                      block_size=BLOCK)
    spies = []
    decode, prefill_prime = eng._decode, eng._prefill_prime

    def spy(fn):
        def wrapped(*a):
            *out, routed = fn(*a)
            spies.append(_ReadSpy(routed))
            return (*out, spies[-1])
        return wrapped

    eng._decode, eng._prefill_prime = spy(decode), spy(prefill_prime)
    return cfg, eng, spies


def test_collect_and_prefill_spans_carry_the_routed_choice_counts(tmp_path):
    cfg, eng, spies = _routed_engine()
    with eng:
        _drive(eng, cfg, 1)  # compile outside the session
        mark = trace.Span("mark").sid
        with jax.profiler.trace(str(tmp_path)):
            reqs = _drive(eng, cfg, 2)
        spans = [s for s in trace.SPANS.snapshot() if s.sid > mark]
    assert all(r.done for r in reqs)
    counted = {n: [s for s in spans if s.name == n] for n in ("serve.collect", "serve.prefill")}
    read = [np.asarray(s.a) for s in spies if s.reads]
    assert counted["serve.collect"] and counted["serve.prefill"]
    assert len(read) == sum(len(v) for v in counted.values())
    moe_layers, held = cfg.n_layers - cfg.moe.first_k_dense, cfg.moe.held[1]
    for name, got in counted.items():
        for s in got:
            assert s.counts["experts_held"] == held
            assert 0 <= s.counts["expert_tokens_max"] <= s.counts["expert_tokens"]
            n_tok = s.counts["prompt_len"] if name == "serve.prefill" else 4  # at most 4 slots
            assert s.counts["expert_tokens"] <= moe_layers * n_tok * min(cfg.moe.top_k, held)
    assert sorted((int(a.sum()), int(a.max())) for a in read) == sorted(
        (s.counts["expert_tokens"], s.counts["expert_tokens_max"])
        for v in counted.values() for s in v)


def test_routed_choice_counts_are_never_read_on_the_host_while_nothing_records(recorded):
    cfg, eng, spies = _routed_engine()
    assert not trace.profiling()
    with eng:
        reqs = _drive(eng, cfg, 3)
    assert all(r.done for r in reqs)
    assert spies and all(s.reads == 0 for s in spies)
    # a model without a counted expert layer attaches no counts
    assert not any("expert_tokens" in s.counts for s in recorded[0])
