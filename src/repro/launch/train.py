"""Production train launcher.

Drives the staged train step with the full substrate: host-mesh sharding,
synthetic data with background prefetch, periodic async checkpoints,
failure simulation + elastic re-mesh, resume-from-latest.

    PYTHONPATH=src python -m repro.launch.train --arch deepseek-7b --reduced \
        --steps 50 --batch 8 --seq 64 --ckpt-dir /tmp/ck --ckpt-every 20

Elastic fault tolerance (``--fail-at STEP:RANKS``): a
:class:`~repro.dist.fault.FailureSimulator` injects a rank loss at STEP
(surfaced as :class:`~repro.core.SpRankDeadError` from the step function).
The launcher itself contains **no recovery control flow** — the training
loop is a plain ``SpRuntime(elastic=True).elastic_loop``; the runtime
catches the death, and this module's ``on_reshard`` hook only does the
domain work: compute a :func:`~repro.dist.fault.remesh_plan` over the
survivors (preserving model parallelism), rebuild the mesh, and recover
state by one of two paths (``--recovery``):

* ``live`` (default) — *live reshard*: ``jax.device_put`` the surviving
  in-memory state onto the new mesh and continue from the failed step; no
  replay, no disk.  Falls back to checkpoint restore only when there is no
  in-memory state to reshard.
* ``restore`` — full checkpoint restore (replays every step since the
  last save); requires ``--ckpt-dir``/``--ckpt-every`` (or ``--resume``).

Either way the data-pipeline cursor is the step counter, so resumption is
deterministic.  Each recovery is timed; ``--bench-out PATH`` writes the
timings as JSON (the ``BENCH_recovery.json`` series).
"""
from __future__ import annotations

import argparse
import contextlib
import json
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint import CheckpointManager
from repro.configs import get_config, reduced_config
from repro.core import SpRankDeadError, SpRuntime
from repro.data import Prefetcher, SyntheticLMDataset
from repro.dist.fault import FailureSimulator, remesh_plan
from repro.dist.sharding import use_mesh
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_host_mesh, make_mesh
from repro.models.config import ShapeSpec
from repro.optim import linear_warmup_cosine
from repro.runtime.train import (
    abstract_train_state,
    build_train_step,
    init_train_state,
    train_state_shardings,
)


def _parse_fail_at(spec: str) -> FailureSimulator:
    try:
        step_s, ranks_s = spec.split(":")
        step, ranks = int(step_s), int(ranks_s)
    except ValueError:
        raise argparse.ArgumentTypeError(f"expected STEP:RANKS integers, got {spec!r}")
    if step < 1:
        raise argparse.ArgumentTypeError("STEP must be >= 1 (checked after each step)")
    if ranks < 1:
        raise argparse.ArgumentTypeError("RANKS must be >= 1")
    return FailureSimulator({step: ranks})


def main(argv=None) -> dict:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="deepseek-7b")
    ap.add_argument("--reduced", action="store_true", help="smoke-scale config")
    ap.add_argument(
        "--layers", type=int, default=None,
        help="cut the config's depth to this many layers (widths unchanged)",
    )
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--microbatches", type=int, default=2)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=0)
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--schedule-policy", default="overlap")
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument(
        "--fail-at", default=None, metavar="STEP:RANKS", type=_parse_fail_at,
        help="simulate losing RANKS chips at STEP, then elastically re-mesh",
    )
    ap.add_argument(
        "--recovery", choices=("live", "restore"), default="live",
        help="after a re-mesh: live-reshard the in-memory state (default) "
        "or restore the latest checkpoint",
    )
    ap.add_argument(
        "--bench-out", default=None, metavar="PATH",
        help="write recovery timings as JSON to PATH",
    )
    args = ap.parse_args(argv)
    n_devices = len(jax.devices())
    if args.fail_at is not None and n_devices < 2:
        ap.error(f"--fail-at needs several devices to re-mesh over; found {n_devices}")
    enable_compile_cache()

    cfg = reduced_config(args.arch) if args.reduced else get_config(args.arch)
    if args.layers is not None:
        cfg = cfg.replace(n_layers=args.layers)
    shape = ShapeSpec("train", "train", args.seq, args.batch)
    ds = SyntheticLMDataset(cfg, shape, seed=0)
    mgr = CheckpointManager(args.ckpt_dir, keep=3) if args.ckpt_dir else None
    sim = args.fail_at

    mesh = make_host_mesh() if n_devices > 1 else None
    lr = linear_warmup_cosine(args.lr, warmup=10, total_steps=args.steps)

    losses: list[float] = []  # losses[i] is the loss of step base_step + i + 1
    recoveries: list[dict] = []  # one entry per re-mesh: mode/step/seconds
    # Mutable training-segment state shared between the step function and
    # the reshard hook.  ``restorable``: only checkpoints this process saved
    # (or explicitly opted into via --resume) may be restored after a
    # failure — a stale dir from an earlier run must not hijack the step
    # counter.
    st: dict = {
        "mesh": mesh, "art": None, "state": None, "pf": None,
        "restorable": args.resume, "failed_ranks": 0,
        "seg_t0": 0.0, "seg_steps": 0,
    }

    def _mesh_ctx():
        return use_mesh(st["mesh"]) if st["mesh"] is not None else contextlib.nullcontext()

    def _bind(start: int) -> None:
        """(Re)build the jitted step artifact under the current mesh and
        point the prefetch pipeline at ``start``."""
        with _mesh_ctx():
            st["art"] = build_train_step(
                cfg,
                n_microbatches=args.microbatches,
                schedule_policy=args.schedule_policy,
                lr_schedule=lr,
            )
        if st["pf"] is not None:
            st["pf"].stop()
        st["pf"] = Prefetcher(ds, start_step=start, depth=2)
        st["seg_t0"], st["seg_steps"] = time.perf_counter(), 0

    start_step = 0
    with _mesh_ctx():
        if mgr is not None and args.resume and mgr.latest_step() is not None:
            start_step, st["state"] = mgr.restore(abstract_train_state(cfg))
            print(f"[train] resumed from step {start_step}")
        else:
            # under the mesh this initialises straight into the shardings
            st["state"] = init_train_state(jax.random.PRNGKey(0), cfg)
    base_step = start_step
    _bind(start_step)

    def train_step(step: int) -> float:
        """One SGD step.  No failure handling anywhere: a simulated rank
        loss raises SpRankDeadError and the elastic runtime drives the
        recovery (re-mesh + reshard via ``on_reshard``) transparently."""
        with _mesh_ctx():
            _, batch = st["pf"].get()
            batch = {k: jnp.asarray(v) for k, v in batch.items()}
            st["state"], metrics = st["art"](st["state"], batch)
        loss = float(metrics["loss"])
        losses.append(loss)
        st["seg_steps"] += 1
        s = int(st["state"].step)
        if args.log_every and s % args.log_every == 0:
            dt = (time.perf_counter() - st["seg_t0"]) / st["seg_steps"]
            print(
                f"[train] step {s:5d} loss {loss:8.4f} "
                f"gnorm {float(metrics['grad_norm']):7.3f} {dt * 1e3:7.1f} ms/step",
                flush=True,
            )
        if mgr is not None and args.ckpt_every and s % args.ckpt_every == 0:
            mgr.save(s, st["state"])  # async commit
            st["restorable"] = True
        if sim is not None:
            failed = sim.check(s)
            if failed:
                st["failed_ranks"] = failed
                raise SpRankDeadError(
                    f"simulated loss of {failed} ranks after step {s}"
                )
        return loss

    def on_reshard(event) -> int:
        """Domain half of a recovery: shrink the mesh over the survivors,
        then live-reshard the in-memory state (no replay, no disk) or
        restore the latest durable checkpoint.  Returns the resume step."""
        nonlocal base_step
        t_rec = time.perf_counter()
        failed_ranks, st["failed_ranks"] = st["failed_ranks"], 0
        plan = remesh_plan(
            int(np.prod(tuple(st["mesh"].shape.values()))),
            failed_ranks,
            model_parallel=int(st["mesh"].shape["model"]),
        )
        st["mesh"] = make_mesh(plan.shape, plan.axes, devices=jax.devices()[: plan.n_chips])
        print(
            f"[train] lost {failed_ranks} ranks at step {int(st['state'].step)}; "
            f"re-meshed to {plan.shape} ({plan.dropped_chips} chips dropped)"
        )
        can_restore = (
            st["restorable"] and mgr is not None and mgr.latest_step() is not None
        )
        with _mesh_ctx():
            if args.recovery == "restore" and can_restore:
                resume, st["state"] = mgr.restore(abstract_train_state(cfg))
                jax.block_until_ready(st["state"])
                # drop losses of the steps the restore will replay
                if resume < base_step:
                    losses.clear()
                    base_step = resume
                else:
                    del losses[resume - base_step:]
                mode = "restore"
                print(f"[train] restored step {resume} onto new mesh")
            else:
                st["state"] = jax.device_put(st["state"], train_state_shardings(cfg))
                jax.block_until_ready(st["state"])
                mode = "live"
                resume = int(st["state"].step)
                prefix = "" if args.recovery == "live" else "no restorable checkpoint; "
                print(f"[train] {prefix}live-resharded step {resume} onto new mesh")
        _bind(resume)
        recoveries.append(
            {
                "mode": mode,
                "step": int(resume),
                "seconds": time.perf_counter() - t_rec,
            }
        )
        return resume

    try:
        if start_step < args.steps:
            with SpRuntime(workers=1, elastic=True, on_reshard=on_reshard) as rt:
                rt.elastic_loop(train_step, args.steps, start=start_step)
    finally:
        st["pf"].stop()
        if mgr is not None:
            mgr.wait()

    if losses:
        print(f"[train] done: loss {losses[0]:.4f} -> {losses[-1]:.4f}")
    else:
        print("[train] nothing to do: start step >= --steps")
    final_step = int(st["state"].step) if st["state"] is not None else start_step
    result = {"losses": losses, "final_step": final_step, "recoveries": recoveries}
    if args.bench_out:
        with open(args.bench_out, "w") as f:
            json.dump(
                {"recoveries": recoveries, "final_step": final_step}, f, indent=2
            )
        print(f"[train] wrote recovery timings to {args.bench_out}")
    return result


if __name__ == "__main__":
    main()
