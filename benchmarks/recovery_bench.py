"""Failure detection + recovery benchmark → ``BENCH_recovery.json``.

Four measurements (ISSUE 6 + ISSUE 8 acceptance):

* **detection** — :func:`repro.launch.rendezvous.run_elastic_ring` spawns
  real OS rank processes, SIGKILLs one mid-``ring_all_reduce``, and each
  survivor reports ``transport.death_detected_at(victim)``; detection
  latency is that stamp minus the parent's kill time (CLOCK_MONOTONIC is
  machine-wide on Linux).  The re-roll wall time (dead-set agreement +
  group shrink) rides along as ``reroll_s``.

* **recovery** — ``launch/train.py --fail-at`` run twice in a subprocess
  with 8 virtual host devices (``--xla_force_host_platform_device_count``),
  once per ``--recovery`` mode: ``live`` (``jax.device_put`` the surviving
  in-memory state onto the shrunken mesh — no replay, no disk) vs
  ``restore`` (full checkpoint restore + replay).  The per-recovery wall
  times come from the launcher's own ``--bench-out`` JSON.

* **big_state** (ISSUE 8) — the same live-reshard vs save+restore
  comparison at serious state size: a ≥64 MiB sharded param pytree is
  moved onto a shrunken mesh by ``jax.device_put`` (live) and by a full
  checkpoint round-trip (durable write + restore onto the new
  shardings), in a subprocess with 8 virtual host devices.

* **watchdog** (ISSUE 8) — task-hang detection latency: a task with an
  ``sp_task(timeout=...)`` policy blocks forever; the engine watchdog
  must fail it with ``SpTaskTimeoutError``.  Reported as the overshoot
  past the configured timeout (the watchdog sweeps every ≤50 ms).

Numbers land in ROADMAP.md's "Live elasticity" item.  Run:

    PYTHONPATH=src python benchmarks/recovery_bench.py
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
import tempfile
import textwrap
import threading
import time

OUT = os.path.join(os.path.dirname(__file__), "..", "BENCH_recovery.json")

TRAIN_SCRIPT = textwrap.dedent(
    """
    import os, sys, json
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    from repro.launch.train import main
    out = main([
        "--arch", "deepseek-7b", "--reduced", "--steps", "6",
        "--batch", "4", "--seq", "32", "--microbatches", "2",
        "--fail-at", "3:4", "--ckpt-dir", sys.argv[1], "--ckpt-every", "1",
        "--recovery", sys.argv[2], "--bench-out", sys.argv[3],
        "--log-every", "0",
    ])
    assert out["final_step"] == 6, out
    assert out["recoveries"], "no recovery happened"
    """
)


def _child_env() -> dict:
    """Environment of a child that simulates 8 chips on virtual host
    devices: pinned to the CPU, never inheriting the parent's platform."""
    env = dict(os.environ, PYTHONPATH="src", JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    return env


def measure_detection(reps: int = 3) -> dict:
    from repro.launch.rendezvous import run_elastic_ring

    detect, reroll = [], []
    for _ in range(reps):
        results, info = run_elastic_ring(size=3, n=257, steps=4, fail_at=2)
        for rank, rep in results.items():
            detect.append(rep["detect_at"] - info["t_kill"])
            reroll.append(rep["reroll_s"])
    return {
        "ranks": 3,
        "reps": reps,
        "detect_latency_s": {"min": min(detect), "max": max(detect)},
        "reroll_s": {"min": min(reroll), "max": max(reroll)},
    }


def measure_recovery() -> dict:
    root = os.path.join(os.path.dirname(__file__), "..")
    env = _child_env()
    out: dict = {}
    for mode in ("live", "restore"):
        with tempfile.TemporaryDirectory() as ckdir:
            bench = os.path.join(ckdir, "bench.json")
            r = subprocess.run(
                [sys.executable, "-c", TRAIN_SCRIPT, ckdir, mode, bench],
                env=env, capture_output=True, text=True, timeout=900, cwd=root,
            )
            if r.returncode != 0:
                raise RuntimeError(
                    f"{mode} run failed:\n{r.stdout[-2000:]}\n{r.stderr[-2000:]}"
                )
            with open(bench) as f:
                rec = json.load(f)["recoveries"]
            out[mode] = rec[0]
    return out


BIG_STATE_SCRIPT = textwrap.dedent(
    """
    import os, sys, json, time
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax
    import jax.numpy as jnp
    from repro.checkpoint import CheckpointManager
    from repro.dist.fault import remesh_plan
    from repro.launch.mesh import make_mesh

    mib = int(sys.argv[2])
    # a pytree of float32 shards totalling >= mib MiB, sharded over 'data'
    n_arrays = 8
    rows = (mib * (1 << 20)) // (4 * 1024 * n_arrays)
    def mesh_for(plan):
        return make_mesh(plan.shape, plan.axes, devices=jax.devices()[: plan.n_chips])
    def shardings(mesh):
        spec = jax.sharding.PartitionSpec("data", None)
        return {f"w{i}": jax.sharding.NamedSharding(mesh, spec)
                for i in range(n_arrays)}
    full = mesh_for(remesh_plan(8, 0, model_parallel=2))
    keys = jax.random.split(jax.random.PRNGKey(0), n_arrays)
    state = {
        f"w{i}": jax.device_put(
            jax.random.normal(keys[i], (rows, 1024), jnp.float32),
            shardings(full)[f"w{i}"],
        )
        for i in range(n_arrays)
    }
    jax.block_until_ready(state)
    nbytes = sum(x.nbytes for x in state.values())

    # half the chips die; live-reshard onto the shrunken mesh
    shrunk = mesh_for(remesh_plan(8, 4, model_parallel=2))
    t0 = time.perf_counter()
    live = jax.device_put(state, shardings(shrunk))
    jax.block_until_ready(live)
    live_s = time.perf_counter() - t0

    # the checkpoint path: durable write (blocking), restore onto the
    # NEW shardings (template carries them), replay excluded
    mgr = CheckpointManager(sys.argv[1], keep=1)
    t0 = time.perf_counter()
    mgr.save(1, state, block=True)
    save_s = time.perf_counter() - t0
    template = {
        k: jax.ShapeDtypeStruct(v.shape, v.dtype, sharding=shardings(shrunk)[k])
        for k, v in state.items()
    }
    t0 = time.perf_counter()
    _, restored = mgr.restore(template)
    jax.block_until_ready(restored)
    restore_s = time.perf_counter() - t0
    print(json.dumps({
        "state_mib": nbytes / (1 << 20),
        "live_reshard_s": live_s,
        "ckpt_save_s": save_s,
        "ckpt_restore_s": restore_s,
    }))
    """
)


def measure_big_state(mib: int = 64) -> dict:
    root = os.path.join(os.path.dirname(__file__), "..")
    env = _child_env()
    with tempfile.TemporaryDirectory() as ckdir:
        r = subprocess.run(
            [sys.executable, "-c", BIG_STATE_SCRIPT, ckdir, str(mib)],
            env=env, capture_output=True, text=True, timeout=900, cwd=root,
        )
    if r.returncode != 0:
        raise RuntimeError(
            f"big-state run failed:\n{r.stdout[-2000:]}\n{r.stderr[-2000:]}"
        )
    out = json.loads(r.stdout.strip().splitlines()[-1])
    assert out["state_mib"] >= mib, out
    return out


def measure_watchdog(reps: int = 5, timeout_s: float = 0.2) -> dict:
    """Hang a policied task; measure how far past its configured timeout
    the watchdog's SpTaskTimeoutError lands."""
    from repro.core import SpData, SpRuntime, SpTaskTimeoutError, sp_task

    @sp_task(read=("x",), timeout=timeout_s, on_failure="quarantine",
             name="bench.hang")
    def hang(x, *, release):
        release.wait(30.0)

    overshoot = []
    with SpRuntime(workers=2) as rt:
        for i in range(reps):
            release = threading.Event()
            t0 = time.perf_counter()
            view = hang(SpData(i, f"hang{i}"), release=release)
            try:
                view.result(timeout=10.0)
            except SpTaskTimeoutError:
                pass
            overshoot.append((time.perf_counter() - t0) - timeout_s)
            release.set()  # unblock the zombie body
    return {
        "reps": reps,
        "configured_timeout_s": timeout_s,
        "detect_overshoot_s": {"min": min(overshoot), "max": max(overshoot)},
    }


def main() -> None:
    report = {
        "detection": measure_detection(),
        "recovery": measure_recovery(),
        "big_state": measure_big_state(),
        "watchdog": measure_watchdog(),
    }
    with open(OUT, "w") as f:
        json.dump(report, f, indent=2)
        f.write("\n")
    print(json.dumps(report, indent=2))
    print(f"wrote {os.path.abspath(OUT)}")


if __name__ == "__main__":
    main()
