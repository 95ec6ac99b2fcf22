"""What the readers of the program's own spans share.

The program (``repro.core.trace``) keeps every span it recorded while the
profiler ran in one process-wide buffer, ``SPANS``, stamped on
``time.perf_counter_ns``: the clock of the harness's step records, in
nanoseconds.  The readers keep the spans that lie inside the traced
stretch, from the first traced step's start to the last one's end.  A
program that keeps no such buffer, or recorded nothing there, gives
nothing to read."""
from __future__ import annotations

from bench.harness.stats import interval_union, overlap


def stretch_ns(run):
    """(start, end) of the traced stretch in nanoseconds, or None."""
    steps = run.record.get("traced_steps")
    if not steps:
        return None
    return steps[0][0] * 1e9, steps[-1][1] * 1e9


def spans(run, names) -> list | None:
    """The program's spans named in ``names`` that lie inside the traced
    stretch, or None when there are none."""
    st = stretch_ns(run)
    if st is None:
        return None
    try:
        from repro.core.trace import SPANS
    except ImportError:
        return None
    a, b = st
    out = [s for s in SPANS.snapshot() if s.name in names and a <= s.t0 and s.t1 <= b]
    return out or None


def step_host_ms(run):
    """Mean over ``serve.step`` spans of the step's duration less the union
    of the ``serve.device_wait`` spans (any thread) inside it, in ms."""
    got = spans(run, ("serve.step", "serve.device_wait"))
    if got is None:
        return None
    steps = [s for s in got if s.name == "serve.step"]
    if not steps:
        return None
    waits = interval_union((s.t0, s.t1) for s in got if s.name == "serve.device_wait")
    host = [s.t1 - s.t0 - overlap(waits, s.t0, s.t1) for s in steps]
    return sum(host) / len(host) / 1e6


def writeback_share(run):
    """Summed ``serve.writeback`` time over the traced stretch, in %."""
    got = spans(run, ("serve.writeback",))
    if got is None:
        return None
    a, b = stretch_ns(run)
    return 100.0 * sum(s.t1 - s.t0 for s in got) / (b - a)
