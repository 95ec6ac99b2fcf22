"""Spans of the task runtime and the serving tier, and the execution-trace
exports that read them (paper §4.8).

A span is one timed piece of host work: its name, its start and end on
``time.perf_counter_ns`` (the clock of every stamp the program takes), the
thread that ran it, its id, the id of the span that caused it, the request
it belongs to where it belongs to one, and a few integer counts.

Spans are recorded only while something watches:

- a task graph built with ``trace=True`` keeps the span of every task it
  runs in ``graph.trace_events``, which :func:`trace_to_svg` and
  :func:`trace_metrics` read;
- while a JAX profiler session is active, every span is kept in the
  process-wide, bounded :data:`SPANS` buffer (the profiler is process-wide
  too, so the spans outlive the graph or engine that made them), and is
  entered as a ``jax.profiler.TraceAnnotation`` of the same name, so that
  the profile holds it on the device's clock, on the thread that ran it.

Otherwise a span site costs one check (:func:`profiling`) and allocates
nothing: :func:`span` hands back a shared do-nothing context whose ``as``
target is ``None``.

A task's span is caused by the span open on the thread that inserted the
task (for serving, ``serve.step``); a span opened inside a running task is
caused by the task's span.  Comm tasks run no body between their start and
completion stamps, so their spans carry no profiler annotation.
"""
from __future__ import annotations

import collections
import colorsys
import itertools
import sys
import threading
import time

_ids = itertools.count(1)
_is_enabled = None  # TraceMe.is_enabled, once JAX is imported
_annotation = None  # jax.profiler.TraceAnnotation


def _never() -> bool:
    return False


def _resolve() -> bool:
    global _is_enabled, _annotation
    if "jax" not in sys.modules:  # no profiler session without JAX
        return False
    try:
        from jax._src.lib import _profiler
        from jax.profiler import TraceAnnotation

        _is_enabled, _annotation = _profiler.TraceMe.is_enabled, TraceAnnotation
    except (ImportError, AttributeError):
        _is_enabled = _never
    return _is_enabled()


def profiling() -> bool:
    """Whether a JAX profiler session is active (``False`` where this JAX
    cannot say)."""
    f = _is_enabled
    return _resolve() if f is None else f()


class _Open(threading.local):
    span = None  # the innermost span open on this thread


_open = _Open()


def current() -> "Span | None":
    """The innermost span open on the calling thread, for a site to add its
    request id or counts to; ``None`` when nothing records."""
    return _open.span


class SpanBuffer:
    """The spans recorded under the profiler: the newest ``capacity`` of
    them, with a count of the older ones dropped."""

    def __init__(self, capacity: int = 1 << 16):
        self._spans: collections.deque = collections.deque(maxlen=capacity)
        self._lock = threading.Lock()
        self.dropped = 0

    def append(self, sp: "Span") -> None:
        with self._lock:
            if len(self._spans) == self._spans.maxlen:
                self.dropped += 1
            self._spans.append(sp)

    def snapshot(self) -> list:
        with self._lock:
            return list(self._spans)

    def __len__(self) -> int:
        return len(self._spans)


SPANS = SpanBuffer()


class Span:
    """One recorded span.  ``with`` opens it on the calling thread and, for
    a span recorded under the profiler (``profiled``), keeps it in
    :data:`SPANS` when it closes; :func:`span` makes these."""

    __slots__ = ("name", "t0", "t1", "thread", "sid", "parent", "req", "counts",
                 "profiled", "_ann", "_outer")

    def __init__(self, name: str, parent: int | None = None, req: int | None = None,
                 profiled: bool = False):
        self.name = name
        self.sid = next(_ids)
        self.parent = parent
        self.req = req
        self.counts: dict = {}
        self.profiled = profiled
        self.t0 = self.t1 = 0
        self.thread = ""
        self._ann = self._outer = None

    def open(self) -> None:
        """Make this the innermost span of the calling thread (its parent,
        unless set, is the span it nests in) and enter its annotation."""
        self._outer = _open.span
        if self.parent is None and self._outer is not None:
            self.parent = self._outer.sid
        _open.span = self
        self.thread = threading.current_thread().name
        if self.profiled:
            self._ann = _annotation(self.name)
            self._ann.__enter__()

    def close(self) -> None:
        if self._ann is not None:
            self._ann.__exit__(None, None, None)
            self._ann = None
        _open.span, self._outer = self._outer, None

    def __enter__(self) -> "Span":
        self.open()
        self.t0 = time.perf_counter_ns()
        return self

    def __exit__(self, exc_type, exc, tb) -> None:
        self.t1 = time.perf_counter_ns()
        self.close()
        if self.profiled:
            SPANS.append(self)

    def __repr__(self) -> str:
        return (f"Span({self.name!r}, sid={self.sid}, parent={self.parent}, req={self.req}, "
                f"{(self.t1 - self.t0) / 1e3:.1f} us, {self.counts})")


class _Off:
    __slots__ = ()

    def __enter__(self) -> None:
        return None

    def __exit__(self, exc_type, exc, tb) -> None:
        return None


_OFF = _Off()


def span(name: str, req: int | None = None):
    """``with span(name) as sp:`` records one span while a profiler session
    is active (``sp`` is the :class:`Span`, for counts); otherwise ``sp`` is
    ``None`` and nothing is recorded."""
    if not profiling():
        return _OFF
    return Span(name, req=req, profiled=True)


def task_span(task) -> Span | None:
    """The span of ``task``'s run, unopened, or ``None`` when nothing
    records it (its graph does not trace and no profiler session is
    active)."""
    profiled = profiling()
    if not (profiled or task.graph.trace):
        return None
    return Span(task.name, task.trace_parent, profiled=profiled)


def record_task(task, thread: str, ready: int, sp: Span | None = None) -> None:
    """The one writer of task spans (compute workers and the comm thread):
    stamp ``task``'s span with its run, ``task.t_start`` to ``task.t_end``,
    and keep it in its graph's ``trace_events`` and, under the profiler, in
    :data:`SPANS`.  ``sp`` is the span opened around the run, if any."""
    if sp is None:
        sp = task_span(task)
        if sp is None:
            return
    sp.t0, sp.t1, sp.thread = task.t_start, task.t_end, thread
    sp.counts.update(uid=task.uid, ready=ready, comm=int(task.is_comm), spec=int(task.speculative))
    graph = task.graph
    if graph.trace:
        graph.trace_events.append(sp)
    if sp.profiled:
        SPANS.append(sp)


# ---------------------------------------------------------------------------
# Exports of a traced graph's task spans
# ---------------------------------------------------------------------------


def _color(uid: int) -> str:
    h = (uid * 0.6180339887) % 1.0
    r, g, b = colorsys.hsv_to_rgb(h, 0.45, 0.92)
    return f"#{int(r * 255):02x}{int(g * 255):02x}{int(b * 255):02x}"


def trace_to_svg(graph, show_dependencies: bool = True, width: int = 1200) -> str:
    """One horizontal lane per worker; each executed task is a rectangle
    scaled to its duration, hoverable (``<title>``) for name/duration; a
    polyline under the lanes shows the number of ready tasks over time — the
    paper's "number of tasks available during the execution" track."""
    events = sorted(graph.trace_events, key=lambda e: e.t0)
    if not events:
        return (
            '<svg xmlns="http://www.w3.org/2000/svg" width="400" height="40">'
            "<text x='10' y='25'>empty trace</text></svg>"
        )
    t0 = min(e.t0 for e in events)
    t1 = max(e.t1 for e in events)
    span_ns = max(t1 - t0, 1)
    workers = sorted({e.thread for e in events})
    lane_h, pad, label_w = 26, 6, 110
    plot_w = width - label_w - 2 * pad
    ready_h = 60
    height = pad * 3 + lane_h * len(workers) + ready_h + 30

    def x(t: int) -> float:
        return label_w + pad + (t - t0) / span_ns * plot_w

    out = [
        f'<svg xmlns="http://www.w3.org/2000/svg" width="{width}" height="{height}" '
        'font-family="monospace" font-size="11">',
        f'<rect width="{width}" height="{height}" fill="white"/>',
    ]
    lane_y = {w: pad + i * lane_h for i, w in enumerate(workers)}
    for w, y in lane_y.items():
        out.append(f'<text x="4" y="{y + lane_h * 0.7:.1f}">{w}</text>')
        out.append(
            f'<line x1="{label_w}" y1="{y + lane_h:.1f}" x2="{width - pad}" '
            f'y2="{y + lane_h:.1f}" stroke="#ddd"/>'
        )
    for e in events:
        y = lane_y[e.thread]
        xa, xb = x(e.t0), x(e.t1)
        wdt = max(xb - xa, 0.75)
        c = e.counts
        fill = "#9ecae1" if c["comm"] else ("#fee391" if c["spec"] else _color(c["uid"]))
        dur_us = (e.t1 - e.t0) / 1e3
        out.append(
            f'<rect x="{xa:.2f}" y="{y + 2}" width="{wdt:.2f}" height="{lane_h - 4}" '
            f'fill="{fill}" stroke="#555" stroke-width="0.4">'
            f"<title>{e.name} ({dur_us:.1f} us)</title></rect>"
        )
    # ready-tasks-over-time track
    ry = pad * 2 + lane_h * len(workers)
    max_ready = max(1, max(e.counts["ready"] for e in events))
    out.append(f'<text x="4" y="{ry + 12}">ready</text>')
    pts = []
    for e in events:
        yy = ry + ready_h - e.counts["ready"] / max_ready * (ready_h - 10)
        pts.append(f"{x(e.t0):.1f},{yy:.1f}")
    if len(pts) >= 2:
        out.append(
            f'<polyline points="{" ".join(pts)}" fill="none" stroke="#e6550d" stroke-width="1.2"/>'
        )
    out.append(
        f'<text x="{label_w}" y="{height - 8}">span={span_ns / 1e6:.3f} ms, '
        f"tasks={len(events)}, max_ready={max_ready}</text>"
    )
    out.append("</svg>")
    return "\n".join(out)


def trace_metrics(graph) -> dict:
    """Concise execution-quality metrics — the paper's §4.8 "next release"
    feature ("export metrics that will provide concise but meaningful
    numbers on execution quality, such as the idle time")."""
    events = graph.trace_events
    if not events:
        return {"n_tasks": 0}
    t0 = min(e.t0 for e in events)
    t1 = max(e.t1 for e in events)
    span_s = max(t1 - t0, 1) * 1e-9
    workers = sorted({e.thread for e in events})
    busy = {w: 0.0 for w in workers}
    for e in events:
        busy[e.thread] += (e.t1 - e.t0) * 1e-9
    idle = {w: span_s - b for w, b in busy.items()}
    total_busy = sum(busy.values())
    durations = [(e.t1 - e.t0) * 1e-9 for e in events]
    return {
        "n_tasks": len(events),
        "n_workers": len(workers),
        "span_s": span_s,
        "busy_s": total_busy,
        "utilization": total_busy / (span_s * len(workers)),
        "idle_per_worker_s": idle,
        "mean_task_us": 1e6 * sum(durations) / len(durations),
        "max_task_us": 1e6 * max(durations),
        "comm_tasks": sum(1 for e in events if e.counts["comm"]),
        "speculative_tasks": sum(1 for e in events if e.counts["spec"]),
    }
