import os

# 512 placeholder chips exist only as virtual host devices: pin the CPU
# platform so this never takes (or waits for) an accelerator
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=512 "
    + os.environ.get("XLA_FLAGS", "")
)

"""Multi-pod dry-run: lower + compile every (arch × shape × mesh) cell on
512 placeholder host devices; record memory_analysis / cost_analysis /
collective-bytes for EXPERIMENTS.md §Dry-run and §Roofline.

Usage::

    PYTHONPATH=src python -m repro.launch.dryrun --arch deepseek-7b --shape train_4k
    PYTHONPATH=src python -m repro.launch.dryrun --all [--multi-pod-only|--single-pod-only]
    PYTHONPATH=src python -m repro.launch.dryrun --arch X --shape Y --set moe.dispatch=scatter

Results append to experiments/dryrun/<arch>__<shape>__<mesh>[__tag].json.
"""
import argparse
import dataclasses
import json
import re
import time
import traceback

import jax
import jax.numpy as jnp

from repro.configs import ARCH_NAMES, get_config
from repro.dist.sharding import use_mesh
from repro.launch.mesh import make_production_mesh
from repro.models import abstract_cache, abstract_inputs, applicable_shapes
from repro.models.config import SHAPES, ArchConfig, ShapeSpec

# per-arch dry-run overrides: memory-budget knobs for the ≥100B configs
DRYRUN_OVERRIDES: dict[str, dict] = {
    "qwen3-moe-235b-a22b": {"opt_state_dtype": "bfloat16"},
    "llama4-scout-17b-a16e": {"opt_state_dtype": "bfloat16"},
}

_COLLECTIVES = (
    "all-gather",
    "all-reduce",
    "reduce-scatter",
    "all-to-all",
    "collective-permute",
)

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "c128": 16,
}

_SHAPE_RE = re.compile(r"\b([a-z]+\d+|pred)\[([0-9,]*)\]")
_GROUPS_IOTA_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]")
_GROUPS_LIST_RE = re.compile(r"replica_groups=\{\{([0-9, ]+)\}")


def _shape_bytes(dtype: str, dims: str) -> int:
    n = 1
    for d in dims.split(","):
        if d:
            n *= int(d)
    return n * _DTYPE_BYTES.get(dtype, 4)


def _group_size(line: str, default: int = 1) -> int:
    m = _GROUPS_IOTA_RE.search(line)
    if m:  # iota form [num_groups, group_size]
        return int(m.group(2))
    m = _GROUPS_LIST_RE.search(line)
    if m:  # explicit form {{0,1,...},{...}} — size of the first group
        return len(m.group(1).split(","))
    return default


def collective_stats(hlo_text: str) -> dict:
    """Per-device collective traffic from the post-SPMD (per-partition) HLO.

    Post-optimization HLO prints operands without types, so sizes come from
    the *result* type(s) on the LHS.  Per instance we record:

    * ``bytes``  — the full (logical) payload: result bytes, except
      reduce-scatter where the operand = result × group_size;
    * ``wire_bytes`` — estimated per-device link traffic for ring
      implementations: AG/RS move (g−1)/g × full, AR moves 2×(g−1)/g × full,
      A2A (g−1)/g, permute 1×.

    NB: ops inside a ``while`` (layer-scan) body appear once in the text;
    benchmarks/roofline.py corrects by trip count via unrolled probes.
    """
    out = {k: {"count": 0, "bytes": 0, "wire_bytes": 0} for k in _COLLECTIVES}
    for line in hlo_text.splitlines():
        stripped = line.strip()
        m = re.match(r"(?:ROOT )?%?[\w.\-]+ = (.*)$", stripped)
        if not m:
            continue
        rhs = m.group(1)
        kind_m = re.match(r"((?:\([^)]*\))|(?:[a-z0-9]+\[[0-9,]*\]\S*))\s+([a-z0-9\-]+)\(", rhs)
        if not kind_m:
            continue
        lhs_types, opname = kind_m.group(1), kind_m.group(2)
        base = None
        for k in _COLLECTIVES:
            if opname == k or opname == k + "-start":
                base = k
                break
        if base is None:
            continue
        sizes = [_shape_bytes(dt, dims) for dt, dims in _SHAPE_RE.findall(lhs_types)]
        if not sizes:
            continue
        g = _group_size(line, default=2)
        if base == "all-gather":
            full = max(sizes)  # result (gathered) size
            wire = full * (g - 1) // max(g, 1)
        elif base == "reduce-scatter":
            full = min(sizes) * g  # operand size
            wire = full * (g - 1) // max(g, 1)
        elif base == "all-reduce":
            full = max(sizes)
            wire = 2 * full * (g - 1) // max(g, 1)
        elif base == "all-to-all":
            full = max(sizes)
            wire = full * (g - 1) // max(g, 1)
        else:  # collective-permute
            full = max(sizes)
            wire = full
        out[base]["count"] += 1
        out[base]["bytes"] += full
        out[base]["wire_bytes"] += wire
    out["total_bytes"] = sum(v["bytes"] for v in out.values() if isinstance(v, dict))
    out["total_wire_bytes"] = sum(
        v["wire_bytes"] for v in out.values() if isinstance(v, dict)
    )
    out["total_count"] = sum(v["count"] for v in out.values() if isinstance(v, dict))
    return out


def _memory_analysis_dict(compiled) -> dict:
    try:
        ma = compiled.memory_analysis()
    except Exception as e:  # pragma: no cover
        return {"error": str(e)}
    keys = [
        "argument_size_in_bytes",
        "output_size_in_bytes",
        "temp_size_in_bytes",
        "alias_size_in_bytes",
        "generated_code_size_in_bytes",
    ]
    d = {}
    for k in keys:
        v = getattr(ma, k, None)
        if v is not None:
            d[k] = int(v)
    if d:
        d["total_per_device_bytes"] = (
            d.get("argument_size_in_bytes", 0)
            + d.get("output_size_in_bytes", 0)
            + d.get("temp_size_in_bytes", 0)
            - d.get("alias_size_in_bytes", 0)
        )
    else:
        d["repr"] = str(ma)
    return d


def _cost_analysis_dict(compiled) -> dict:
    try:
        ca = compiled.cost_analysis()
    except Exception as e:  # pragma: no cover
        return {"error": str(e)}
    if isinstance(ca, (list, tuple)):
        ca = ca[0] if ca else {}
    return {str(k): float(v) for k, v in ca.items() if isinstance(v, (int, float))}


def config_for_dryrun(arch: str, overrides: dict | None = None) -> ArchConfig:
    cfg = get_config(arch)
    kw = dict(DRYRUN_OVERRIDES.get(arch, {}))
    if overrides:
        kw.update(overrides)
    # nested override support: {"moe.dispatch": "scatter"}
    flat = {k: v for k, v in kw.items() if "." not in k}
    nested = {k: v for k, v in kw.items() if "." in k}
    if flat:
        cfg = cfg.replace(**flat)
    for key, val in nested.items():
        head, field = key.split(".", 1)
        sub = getattr(cfg, head)
        cfg = cfg.replace(**{head: dataclasses.replace(sub, **{field: val})})
    return cfg


def lower_cell(cfg: ArchConfig, shape: ShapeSpec, mesh, n_microbatches: int = 1):
    """Build and lower the step for one cell.  Returns the Lowered object."""
    with use_mesh(mesh):
        if shape.kind == "train":
            from repro.runtime.train import abstract_train_state, build_train_step

            art = build_train_step(cfg, n_microbatches=n_microbatches, donate=True)
            state_abs = abstract_train_state(cfg)
            batch_abs = abstract_inputs(cfg, shape)
            return art.step_fn.lower(state_abs, batch_abs)
        if shape.kind == "prefill":
            from repro.models import abstract_params, prefill
            from repro.models.transformer import param_shardings

            p_abs = abstract_params(cfg)
            batch_abs = abstract_inputs(cfg, shape)

            def prefill_fn(params, batch):
                logits, caches = prefill(params, batch, cfg)
                return jnp.argmax(logits, axis=-1).astype(jnp.int32), caches

            fn = jax.jit(prefill_fn, in_shardings=(param_shardings(cfg), None))
            return fn.lower(p_abs, batch_abs)
        # decode
        from repro.models import abstract_params
        from repro.runtime.serve import build_serve_step

        p_abs = abstract_params(cfg)
        tok_abs = abstract_inputs(cfg, shape)["tokens"]
        cache_abs = abstract_cache(cfg, shape.global_batch, shape.seq_len)
        pos_abs = jax.ShapeDtypeStruct((), jnp.int32)
        fn = build_serve_step(cfg, shape, jit=True)
        return fn.lower(p_abs, tok_abs, cache_abs, pos_abs)


def run_cell(
    arch: str,
    shape_name: str,
    multi_pod: bool,
    overrides: dict | None = None,
    tag: str = "",
    outdir: str = "experiments/dryrun",
) -> dict:
    overrides = dict(overrides or {})
    n_microbatches = int(overrides.pop("n_microbatches", 1))
    cfg = config_for_dryrun(arch, overrides)
    shape = SHAPES[shape_name]
    mesh = make_production_mesh(multi_pod=multi_pod)
    n_chips = mesh.size
    mesh_name = "multipod_2x16x16" if multi_pod else "pod_16x16"
    rec: dict = {
        "arch": arch,
        "shape": shape_name,
        "mesh": mesh_name,
        "n_chips": n_chips,
        "tag": tag,
        "overrides": dict(overrides or {}, n_microbatches=n_microbatches),
    }
    t0 = time.time()
    try:
        lowered = lower_cell(cfg, shape, mesh, n_microbatches=n_microbatches)
        rec["lower_s"] = round(time.time() - t0, 1)
        t1 = time.time()
        compiled = lowered.compile()
        rec["compile_s"] = round(time.time() - t1, 1)
        rec["memory"] = _memory_analysis_dict(compiled)
        rec["cost"] = _cost_analysis_dict(compiled)
        hlo = compiled.as_text()
        rec["collectives"] = collective_stats(hlo)
        rec["ok"] = True
    except Exception as e:
        rec["ok"] = False
        rec["error"] = f"{type(e).__name__}: {e}"
        rec["traceback"] = traceback.format_exc()[-4000:]
    os.makedirs(outdir, exist_ok=True)
    fname = f"{arch}__{shape_name}__{mesh_name}" + (f"__{tag}" if tag else "") + ".json"
    with open(os.path.join(outdir, fname), "w") as f:
        json.dump(rec, f, indent=1)
    return rec


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--single-pod", action="store_true")
    ap.add_argument("--tag", default="")
    ap.add_argument("--outdir", default="experiments/dryrun")
    ap.add_argument(
        "--set",
        action="append",
        default=[],
        help="config override key=value (e.g. moe.dispatch=scatter)",
    )
    args = ap.parse_args()

    overrides: dict = {}
    for kv in args.set:
        k, v = kv.split("=", 1)
        if v.lower() in ("true", "false"):
            v = v.lower() == "true"
        else:
            for cast in (int, float):
                try:
                    v = cast(v)
                    break
                except ValueError:
                    continue
        overrides[k] = v

    meshes = []
    if args.multi_pod or not args.single_pod:
        pass
    if args.single_pod:
        meshes = [False]
    elif args.multi_pod:
        meshes = [True]
    else:
        meshes = [False, True]

    cells = []
    if args.all:
        for arch in ARCH_NAMES:
            cfg = get_config(arch)
            for shape in applicable_shapes(cfg):
                cells.append((arch, shape.name))
    else:
        assert args.arch and args.shape, "--arch/--shape or --all"
        cells.append((args.arch, args.shape))

    for arch, shape in cells:
        for mp in meshes:
            rec = run_cell(arch, shape, mp, overrides or None, args.tag, args.outdir)
            status = "OK " if rec["ok"] else "FAIL"
            print(
                f"[{status}] {arch:26s} {shape:12s} {rec['mesh']:16s} "
                f"lower={rec.get('lower_s', '-'):>6}s compile={rec.get('compile_s', '-'):>6}s "
                + (
                    f"flops/dev={rec['cost'].get('flops', 0):.3e} "
                    f"coll={rec['collectives']['total_bytes']:.3e}B"
                    if rec["ok"]
                    else rec.get("error", "")
                ),
                flush=True,
            )
            if rec["ok"]:
                print(json.dumps(rec["memory"], indent=None), flush=True)


if __name__ == "__main__":
    main()
