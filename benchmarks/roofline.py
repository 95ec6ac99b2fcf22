"""Roofline analysis driver (deliverable g).

Per (arch × shape) on the single-pod mesh, derives the three roofline terms
from compiled artifacts:

    compute    = HLO_FLOPs/dev   / peak_FLOP/s          (197 TF bf16, v5e)
    memory     = HLO_bytes/dev   / HBM_bw               (819 GB/s)
    collective = wire_bytes/dev  / link_bw              (~50 GB/s/link ICI)

XLA's cost model counts a ``while`` (layer-scan) body ONCE, so raw numbers
from the deployable (scanned) modules undercount by ~n_layers.  We therefore
compile two *probe* variants per cell — unrolled at depths (a, b) with
``probe_unroll=True`` so the flash-attention KV loops and CE chunks are also
visible — and extrapolate linearly in depth:

    dense/moe/ssm/enc/vlm:  total(L) = f(2) + (L−2)·(f(4)−f(2))/2
    hybrid (pattern p=3):   total(38) = f(5) + (n_super−1)·(f(8)−f(5))
                            (5 = 1 super + 2 tail, 8 = 2 supers + 2 tail)

Memory-fit numbers come from the deployable scanned module (the canonical
dry-run record); probe memory is ignored (unrolling defeats buffer reuse).

MODEL_FLOPS = 6·N(active)·tokens for train, 2·N·tokens for prefill/decode;
the ratio MODEL_FLOPS/HLO_FLOPs exposes remat/dispatch/masking waste.

Usage::

    PYTHONPATH=src python -m benchmarks.roofline --probes   # run probe compiles
    PYTHONPATH=src python -m benchmarks.roofline --report   # aggregate + table
"""
from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys

HW = {
    "peak_flops": 197e12,  # bf16 FLOP/s per chip (TPU v5e)
    "hbm_bw": 819e9,       # bytes/s per chip
    "link_bw": 50e9,       # bytes/s per ICI link
    "hbm_bytes": 16e9,     # HBM capacity per chip
}

OUTDIR = "experiments/dryrun"
REPORT = "experiments/roofline.json"

_ADVICE = {
    "compute": "compute-bound: raise MXU utilization (bigger tiles, bf16 "
    "everywhere, cut masked-out attention FLOPs via the 'tri' schedule, "
    "scatter MoE dispatch)",
    "memory": "memory-bound: fuse epilogues (Pallas rmsnorm), cut remat "
    "recompute, shrink logits/CE transients (chunked CE), bf16 accumulators",
    "collective": "collective-bound: reshard to cut all-gather volume "
    "(FSDP axis choice), hierarchical cross-pod reduction, int8 gradient "
    "compression, overlap via the 'overlap' staged schedule",
}


def cells():
    sys.path.insert(0, "src")
    from repro.configs import ARCH_NAMES, get_config
    from repro.models import applicable_shapes

    for arch in ARCH_NAMES:
        cfg = get_config(arch)
        for shape in applicable_shapes(cfg):
            yield arch, cfg, shape


def probe_depths(cfg) -> tuple[int, int]:
    if cfg.family == "hybrid":
        return 5, 8
    return 2, 4


def run_probes(only_arch=None, only_shape=None) -> None:
    for arch, cfg, shape in cells():
        if only_arch and arch != only_arch:
            continue
        if only_shape and shape.name != only_shape:
            continue
        a, b = probe_depths(cfg)
        for depth, tag in ((a, "probeA"), (b, "probeB")):
            fname = f"{OUTDIR}/{arch}__{shape.name}__pod_16x16__{tag}.json"
            if os.path.exists(fname) and json.load(open(fname)).get("ok"):
                continue
            cmd = [
                sys.executable, "-m", "repro.launch.dryrun",
                "--arch", arch, "--shape", shape.name, "--single-pod",
                "--tag", tag,
                "--set", f"n_layers={depth}",
                "--set", "scan_layers=false",
                "--set", "probe_unroll=true",
            ]
            print(f"[probe] {arch} {shape.name} depth={depth}", flush=True)
            env = dict(os.environ, PYTHONPATH="src", JAX_PLATFORMS="cpu")
            r = subprocess.run(cmd, env=env, capture_output=True, text=True)
            if r.returncode != 0:
                print(r.stdout[-2000:], r.stderr[-2000:], flush=True)


def _load(arch, shape, tag=""):
    fname = f"{OUTDIR}/{arch}__{shape}__pod_16x16" + (f"__{tag}" if tag else "") + ".json"
    with open(fname) as f:
        return json.load(f)


def _extrapolate(cfg, fa: float, fb: float) -> float:
    a, b = probe_depths(cfg)
    if cfg.family == "hybrid":
        n_super = cfg.n_layers // len(cfg.hybrid.pattern)
        return fa + (n_super - 1) * (fb - fa)
    return fa + (cfg.n_layers - a) * (fb - fa) / (b - a)


def model_flops(cfg, shape) -> float:
    n = cfg.active_param_count()
    if shape.kind == "train":
        tokens = shape.global_batch * shape.seq_len
        return 6.0 * n * tokens
    if shape.kind == "prefill":
        tokens = shape.global_batch * shape.seq_len
        return 2.0 * n * tokens
    return 2.0 * n * shape.global_batch  # decode: one token per sequence


def aggregate() -> list[dict]:
    rows = []
    for arch, cfg, shape in cells():
        try:
            canon = _load(arch, shape.name)
            pa = _load(arch, shape.name, "probeA")
            pb = _load(arch, shape.name, "probeB")
        except FileNotFoundError as e:
            rows.append({"arch": arch, "shape": shape.name, "error": str(e)})
            continue
        if not (canon.get("ok") and pa.get("ok") and pb.get("ok")):
            rows.append({"arch": arch, "shape": shape.name, "error": "probe failed"})
            continue
        ex = lambda key_fn: _extrapolate(cfg, key_fn(pa), key_fn(pb))
        flops_dev = ex(lambda r: r["cost"].get("flops", 0.0))
        bytes_dev = ex(lambda r: r["cost"].get("bytes accessed", 0.0))
        wire_dev = ex(lambda r: float(r["collectives"]["total_wire_bytes"]))
        n_chips = canon["n_chips"]

        t_compute = flops_dev / HW["peak_flops"]
        t_memory = bytes_dev / HW["hbm_bw"]
        t_coll = wire_dev / HW["link_bw"]
        terms = {"compute": t_compute, "memory": t_memory, "collective": t_coll}
        dominant = max(terms, key=terms.get)
        bound = max(terms.values())
        mf = model_flops(cfg, shape) / n_chips
        row = {
            "arch": arch,
            "shape": shape.name,
            "n_chips": n_chips,
            "flops_per_dev": flops_dev,
            "bytes_per_dev": bytes_dev,
            "wire_bytes_per_dev": wire_dev,
            "t_compute_s": t_compute,
            "t_memory_s": t_memory,
            "t_collective_s": t_coll,
            "dominant": dominant,
            "step_time_bound_s": bound,
            "model_flops_per_dev": mf,
            "useful_flops_ratio": mf / flops_dev if flops_dev else 0.0,
            "roofline_fraction": (mf / HW["peak_flops"]) / bound if bound else 0.0,
            "memory_fit_bytes": canon["memory"].get("total_per_device_bytes"),
            "fits_hbm": (canon["memory"].get("total_per_device_bytes") or 0) < HW["hbm_bytes"],
            "advice": _ADVICE[dominant],
        }
        rows.append(row)
    return rows


def report() -> None:
    rows = aggregate()
    with open(REPORT, "w") as f:
        json.dump(rows, f, indent=1)
    hdr = (
        f"{'arch':26s} {'shape':12s} {'compute_s':>10s} {'memory_s':>10s} "
        f"{'coll_s':>10s} {'dom':>6s} {'useful':>7s} {'roofl%':>7s} {'fit':>4s}"
    )
    print(hdr)
    print("-" * len(hdr))
    for r in rows:
        if "error" in r:
            print(f"{r['arch']:26s} {r['shape']:12s} ERROR {r['error']}")
            continue
        print(
            f"{r['arch']:26s} {r['shape']:12s} {r['t_compute_s']:10.4f} "
            f"{r['t_memory_s']:10.4f} {r['t_collective_s']:10.4f} "
            f"{r['dominant'][:6]:>6s} {r['useful_flops_ratio']:7.2f} "
            f"{100 * r['roofline_fraction']:6.1f}% {'ok' if r['fits_hbm'] else 'NO':>4s}"
        )


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--probes", action="store_true")
    ap.add_argument("--report", action="store_true")
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    args = ap.parse_args()
    if args.probes:
        run_probes(args.arch, args.shape)
    if args.report or not args.probes:
        report()


if __name__ == "__main__":
    main()


# ---------------------------------------------------------------------------
# Hillclimb helpers (§Perf): tagged probe pairs + term deltas
# ---------------------------------------------------------------------------

def probe_cell(arch: str, shape_name: str, overrides: dict, tag: str) -> None:
    """Run the two unrolled probe compiles for one cell with config overrides
    (plus the canonical scanned compile for memory) under ``tag``."""
    sys.path.insert(0, "src")
    from repro.configs import get_config

    cfg = get_config(arch)
    a, b = probe_depths(cfg)
    base_sets = [f"{k}={v}" for k, v in overrides.items()]
    runs = [
        ([f"n_layers={a}", "scan_layers=false", "probe_unroll=true"], f"{tag}_probeA"),
        ([f"n_layers={b}", "scan_layers=false", "probe_unroll=true"], f"{tag}_probeB"),
        ([], f"{tag}_full"),
    ]
    for extra, t in runs:
        cmd = [sys.executable, "-m", "repro.launch.dryrun", "--arch", arch,
               "--shape", shape_name, "--single-pod", "--tag", t]
        for kv in base_sets + extra:
            cmd += ["--set", kv]
        env = dict(os.environ, PYTHONPATH="src", JAX_PLATFORMS="cpu")
        r = subprocess.run(cmd, env=env, capture_output=True, text=True)
        if r.returncode != 0:
            print(r.stdout[-1500:], r.stderr[-1500:], flush=True)


def cell_terms(arch: str, shape_name: str, tag: str = "") -> dict:
    """Roofline terms for one (possibly tagged) cell."""
    sys.path.insert(0, "src")
    from repro.configs import get_config
    from repro.models.config import SHAPES

    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    pa = _load(arch, shape_name, (f"{tag}_probeA" if tag else "probeA"))
    pb = _load(arch, shape_name, (f"{tag}_probeB" if tag else "probeB"))
    full = _load(arch, shape_name, (f"{tag}_full" if tag else ""))
    ex = lambda key_fn: _extrapolate(cfg, key_fn(pa), key_fn(pb))
    flops = ex(lambda r: r["cost"].get("flops", 0.0))
    bts = ex(lambda r: r["cost"].get("bytes accessed", 0.0))
    wire = ex(lambda r: float(r["collectives"]["total_wire_bytes"]))
    terms = {
        "compute_s": flops / HW["peak_flops"],
        "memory_s": bts / HW["hbm_bw"],
        "collective_s": wire / HW["link_bw"],
    }
    bound = max(terms.values())
    mf = model_flops(cfg, shape) / 256
    return {
        **terms,
        "dominant": max(terms, key=terms.get),
        "useful_ratio": mf / flops if flops else 0.0,
        "roofline_fraction": (mf / HW["peak_flops"]) / bound if bound else 0.0,
        "mem_fit_gb": (full["memory"].get("total_per_device_bytes") or 0) / 1e9,
        "flops_per_dev": flops,
        "bytes_per_dev": bts,
        "wire_per_dev": wire,
    }


def compare(arch: str, shape_name: str, tags: list) -> None:
    print(f"--- {arch} × {shape_name} ---")
    hdr = f"{'variant':28s} {'compute_s':>9s} {'memory_s':>9s} {'coll_s':>9s} {'useful':>7s} {'roofl%':>7s} {'mem GB':>7s}"
    print(hdr)
    for t in tags:
        try:
            r = cell_terms(arch, shape_name, t)
        except FileNotFoundError:
            print(f"{t or 'baseline':28s} (missing)")
            continue
        print(
            f"{t or 'baseline':28s} {r['compute_s']:9.3f} {r['memory_s']:9.3f} "
            f"{r['collective_s']:9.3f} {r['useful_ratio']:7.2f} "
            f"{100 * r['roofline_fraction']:6.1f}% {r['mem_fit_gb']:7.1f}"
        )
