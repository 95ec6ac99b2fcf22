"""Scheduler-impact benchmarks — the paper's §4.5 value proposition
measured twice:

1. **Staged backend**: linearization policy changes the compiled program
   order — the ``overlap`` policy hoists the gradient-reduction comm task
   ahead of independent microbatch tasks (earlier issue → more overlap room
   for XLA's async scheduler).  Metric: normalized schedule position of the
   comm task.

2. **Eager backend**: 1F1B-priority pipeline schedule vs FIFO fill-drain on
   the same task graph, 2 workers × (4 stages × 6 microbatches).  Metric:
   worker utilization from ``trace_metrics`` (bubble fraction).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core import SpComputeEngine, SpWorkerTeamBuilder, trace_metrics
from repro.configs import reduced_config
from repro.data import SyntheticLMDataset
from repro.models.config import ShapeSpec
from repro.runtime.pipeline import pipeline_value_and_grad
from repro.runtime.train import build_train_step, init_train_state


def staged_overlap() -> dict:
    """Per-microbatch reduction graph: mb_i's grads reduce as soon as mb_i
    finishes (independent of mb_j) — the structure where linearization
    policy matters.  NB: the production train step accumulates into ONE
    commutative cell, which structurally serializes its single reduction
    behind all microbatches (measured: comm position identical across
    policies) — that finding motivated this per-microbatch variant.
    """
    from repro.core import SpData, SpRead, SpTaskGraph, SpWrite, linearize

    out = {}
    for policy in ("fifo", "overlap"):
        tg = SpTaskGraph()
        # naive program order: all compute first, then all reductions —
        # exactly what a straightforward trainer emits
        gs = [SpData(None, f"g{i}") for i in range(4)]
        rs = [SpData(None, f"r{i}") for i in range(4)]
        for i in range(4):
            tg.task(SpWrite(gs[i]), lambda ref: None, name=f"mb{i}", cost=10.0)
        for i in range(4):
            tg.task(SpRead(gs[i]), SpWrite(rs[i]), lambda v, ref: None,
                    name=f"reduce{i}", comm=True, cost=3.0)
        tg.task(*[SpRead(r) for r in rs], lambda *v: None, name="optimizer")
        order = [t.name for t in linearize(tg, policy)]
        pos = [i for i, n in enumerate(order) if n.startswith("reduce")]
        out[policy] = {
            "schedule": order,
            "mean_comm_pos": sum(pos) / len(pos) / (len(order) - 1),
        }
    return out


def pipeline_schedules() -> dict:
    import numpy as np

    depth, M, B, width = 4, 6, 16, 32
    ks = jax.random.split(jax.random.PRNGKey(0), depth + 2)
    stage_params = [{"w": jax.random.normal(ks[i], (width, width)) * 0.3} for i in range(depth)]
    head_params = {"w": jax.random.normal(ks[-2], (width, 1)) * 0.3}
    xs = jax.random.normal(ks[-1], (M, B, width))
    mbs = [{"x": xs[m], "y": jnp.sin(xs[m].sum(-1, keepdims=True))} for m in range(M)]

    import time

    def stage_fn(p, x):
        # fixed-duration stage work (sleep releases the GIL → the 2 worker
        # threads genuinely overlap on this 1-core container; the math
        # keeps gradients meaningful)
        time.sleep(0.004)
        return jnp.tanh(x @ p["w"])

    def head_fn(p, x, mb):
        return jnp.mean((x @ p["w"] - mb["y"]) ** 2)

    # warm the jit caches so the first-measured schedule pays no compiles
    _ = pipeline_value_and_grad(
        [stage_fn] * depth, head_fn, stage_params, head_params, mbs,
        SpComputeEngine(SpWorkerTeamBuilder.team_of_cpu_workers(2)), schedule="fifo",
    )
    out = {}
    for schedule in ("fifo", "1f1b"):
        eng = SpComputeEngine(SpWorkerTeamBuilder.team_of_cpu_workers(2))
        try:
            loss, _, _, tg = pipeline_value_and_grad(
                [stage_fn] * depth, head_fn, stage_params, head_params, mbs, eng,
                schedule=schedule,
            )
            m = trace_metrics(tg)
            # activation residency: F[s,m]'s output lives until B[s,m] runs.
            # 1F1B's raison d'être is bounding in-flight microbatches — the
            # wall-clock integral of live activations measures exactly that.
            ev = {e.name: e for e in tg.trace_events}
            residency = 0.0
            for s_i in range(depth):
                for m_i in range(M):
                    f = ev.get(f"F[{s_i},{m_i}]")
                    b = ev.get(f"B[{s_i},{m_i}]")
                    if f and b:
                        residency += max(b.t1 - f.t0, 0) * 1e-9
            out[schedule] = {
                "loss": float(loss),
                "utilization": m["utilization"],
                "span_ms": m["span_s"] * 1e3,
                "residency_ms": residency * 1e3,
            }
        finally:
            eng.stop()
    assert abs(out["fifo"]["loss"] - out["1f1b"]["loss"]) < 1e-5
    return out


def main() -> None:
    so = staged_overlap()
    print("staged mean comm position (0=first): "
          f"fifo={so['fifo']['mean_comm_pos']:.2f} overlap={so['overlap']['mean_comm_pos']:.2f}")
    ps = pipeline_schedules()
    print(
        "pipeline 2 workers: "
        f"fifo util={ps['fifo']['utilization']:.2f} ({ps['fifo']['span_ms']:.0f}ms, "
        f"act-residency {ps['fifo']['residency_ms']:.0f}ms)  "
        f"1f1b util={ps['1f1b']['utilization']:.2f} ({ps['1f1b']['span_ms']:.0f}ms, "
        f"act-residency {ps['1f1b']['residency_ms']:.0f}ms)"
    )


if __name__ == "__main__":
    main()
