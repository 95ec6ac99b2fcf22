#!/usr/bin/env python3
"""Smoke run of the system's main paths on a TPU.

    python chip_smoke.py             # served deepseek-7b path on one chip
    python chip_smoke.py --chips 4   # sharded training path on a 4-chip host

One chip: deepseek-7b at its published widths (only ``n_layers`` is cut)
is served through ``ServeEngine`` exactly as ``repro.launch.serve`` drives
it: requests are submitted, join and leave the decode batch mid-flight and
drain.  Before that, one prompt's decode-through-the-cache logits are
checked against prefill's logits over the prompt extended by that token,
with the engine's own jitted functions.

Four chips: the launcher's sharded training step over a (data=2, model=2)
mesh against the same steps on (data=1, model=4), then the launcher's
elastic re-mesh from 4 to 2 chips with live reshard.

The script refuses to run anywhere but a TPU and never falls back to the
CPU or to interpret-mode Pallas.  Every number it prints names the device
it came from; none is a benchmark result.  The last line of its standard
output is the JSON device record, printed only when every phase passed.
"""
from __future__ import annotations

import argparse
import json
import math
import os
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

ARCH = "deepseek-7b"
# One chip (16 GB): weights + KV cache + the decode program's temporaries
# must stay under ~12 GB.  8 layers, 8 slots x 2048 positions reckon at
# 4.9 GB of weights, 2.1 GB of cache and ~2.1 GB of decode temporaries
# (the one-hot KV update rewrites the whole cache).
SERVE = dict(
    n_layers=8, n_slots=8, max_seq=2048, block_size=16,
    n_requests=16, prompt_lens=(64, 128), gen=(32, 40, 48),
)
# Four chips: bf16 weights, f32 Adam moments, f32 gradient accumulator.
# 2 layers (1.24 B parameters) compile to 10.4 GB per chip on (2, 2) and
# 9.5 GB per chip on the re-meshed (1, 2).
TRAIN = dict(n_layers=2, steps=6, batch=4, seq=1024, microbatches=2, fail_at=3)

# decode-vs-prefill logits: both programs run in bf16 and round their
# activations at different points in each layer.  bf16 keeps 8 significant
# bits (a relative step of 2**-8); over n_layers the two logit vectors may
# drift apart by a few such steps times sqrt(n_layers), i.e. 1-3% of the
# logit scale at 8 layers.  2**-4 of the largest logit leaves twice that,
# while a wrong cache row, position or mask moves logits by their own size.
LOGITS_TOL = 2.0**-4
# training losses on two meshes: the same bf16 program partitioned two ways
# sums its partial products in different orders; each loss is a mean over
# batch * seq tokens, so the difference stays far below bf16's 2**-8
# relative step.  1% of the loss still flags any sharding error, which
# shifts the loss by far more.
LOSS_RTOL = 1e-2

_COMPILES: list[tuple[str, float]] = []


def device_info() -> dict:
    import jax

    devices = jax.devices()
    return {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
    }


def require_tpu() -> dict:
    """The device record of the TPU this runs on; exits non-zero, naming
    the platform, anywhere else."""
    if os.environ.get("REPRO_FORCE_PALLAS_INTERPRET"):
        sys.exit(
            "chip_smoke: REPRO_FORCE_PALLAS_INTERPRET is set; refusing to run "
            "Pallas kernels in interpret mode"
        )
    import jax

    backend = jax.default_backend()
    if backend != "tpu":
        sys.exit(f"chip_smoke: needs a TPU, but JAX's default backend is {backend!r}")
    return device_info()


def _watch_compiles() -> None:
    """Record (function name, seconds) of every XLA backend compile."""
    import jax

    if getattr(_watch_compiles, "on", False):
        return
    _watch_compiles.on = True

    def listener(event: str, seconds: float, **kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            _COMPILES.append((kw.get("fun_name", "?"), seconds))

    jax.monitoring.register_event_duration_secs_listener(listener)


def _compile_report(since: int) -> str:
    per: dict[str, float] = {}
    for name, s in _COMPILES[since:]:
        per[name] = per.get(name, 0.0) + s
    top = sorted(per.items(), key=lambda kv: -kv[1])
    return ", ".join(f"{n} {s:.2f}s" for n, s in top) or "none"


def _nbytes(tree) -> int:
    import jax

    return sum(math.prod(x.shape) * x.dtype.itemsize for x in jax.tree.leaves(tree))


def _peak_bytes() -> str:
    import jax

    stats = jax.devices()[0].memory_stats() or {}
    peak = stats.get("peak_bytes_in_use")
    return "not reported" if peak is None else f"{peak / 1e9:.3f} GB"


# ---------------------------------------------------------------------------
# one chip: the served path
# ---------------------------------------------------------------------------

def check_decode_matches_prefill(cfg, params, *, n_slots: int, max_seq: int, prompt) -> float:
    """Logits of the engine's jitted ``decode_step`` at position L, after
    the engine's prefill → prime → install of an L-token prompt, against the
    engine's jitted ``prefill`` over the prompt extended by the decoded
    token.  Returns max |difference| / max |reference logit|."""
    import jax.numpy as jnp
    import numpy as np

    from repro.models import init_cache
    from repro.serving.engine import _jitted_serve_ops, _jitted_steps

    decode, prefill = _jitted_steps(cfg)
    prefill_prime, install = _jitted_serve_ops(cfg, max_seq)
    L = len(prompt)
    last, primed = prefill_prime(params, {"tokens": jnp.asarray(prompt[None])})
    nxt = int(jnp.argmax(last[0]))
    caches, tok = install(
        init_cache(cfg, n_slots, max_seq), primed,
        jnp.zeros((n_slots, 1), jnp.int32), jnp.int32(0), jnp.int32(nxt),
    )
    pos = np.zeros(n_slots, np.int32)
    pos[0] = L
    got, _ = decode(params, tok, caches, jnp.asarray(pos))
    extended = np.append(prompt, nxt).astype(np.int32)
    want, _ = prefill(params, {"tokens": jnp.asarray(extended[None])})
    got = np.asarray(got[0, 0], np.float32)
    want = np.asarray(want[0, 0], np.float32)
    if not (np.isfinite(got).all() and np.isfinite(want).all()):
        raise AssertionError("non-finite logits from decode or prefill")
    return float(np.max(np.abs(got - want)) / np.max(np.abs(want)))


def _serve_wave(eng, cfg, rng, *, n_requests, prompt_lens, gen, seed) -> list:
    """Submit one wave of requests (greedy, plus every fourth sampled with
    temperature and top-k) and drain it; returns the requests."""
    reqs = [
        eng.submit(
            rng.integers(0, cfg.vocab, size=prompt_lens[i % len(prompt_lens)]).astype("int32"),
            gen[i % len(gen)],
            temperature=0.8 if i % 4 == 3 else 0.0,
            top_k=40 if i % 4 == 3 else 0,
            seed=seed + i,
        )
        for i in range(n_requests)
    ]
    eng.run_until_drained()
    return reqs


def _check_wave(reqs, cfg, gen) -> None:
    for i, r in enumerate(reqs):
        if r.rejected or not r.done:
            raise AssertionError(f"request {i}: done={r.done} rejected={r.reject_reason}")
        if len(r.out_tokens) != gen[i % len(gen)]:
            raise AssertionError(f"request {i}: {len(r.out_tokens)} tokens, wanted {gen[i % len(gen)]}")
        if not all(0 <= t < cfg.vocab for t in r.out_tokens):
            raise AssertionError(f"request {i}: token outside the vocabulary")


def serve_phase(
    cfg=None, *, n_slots, max_seq, block_size, n_requests, prompt_lens, gen,
    seed: int = 0, check=require_tpu,
) -> dict:
    """Serve ``cfg`` (default: deepseek-7b cut to ``SERVE["n_layers"]``)
    through ``ServeEngine``: the logits check, a first wave that compiles
    and is checked, then a second wave whose timings are printed."""
    device = check()
    import jax
    import numpy as np

    from repro.configs import get_config
    from repro.models import abstract_cache, abstract_params, init_params
    from repro.serving import ServeEngine

    _watch_compiles()
    label = f"({device['kind']}, {device['count']} device(s))"
    full = get_config(ARCH)
    if cfg is None:
        cfg = full.replace(n_layers=SERVE["n_layers"])
    w_bytes = _nbytes(abstract_params(cfg))
    c_bytes = _nbytes(abstract_cache(cfg, n_slots, max_seq))
    print(
        f"[smoke] {cfg.name}: n_layers {full.n_layers} -> {cfg.n_layers} (only cut); "
        f"d_model {cfg.d_model}, {cfg.n_heads}x{cfg.head_dim} heads "
        f"({cfg.n_kv_heads} kv), d_ff {cfg.d_ff}, vocab {cfg.vocab}, {cfg.dtype}; "
        f"{n_slots} slots x max_seq {max_seq}, block {block_size}",
        flush=True,
    )
    print(
        f"[smoke] reckoned: weights {w_bytes / 1e9:.3f} GB + KV cache {c_bytes / 1e9:.3f} GB "
        f"+ decode temporaries ~{c_bytes / 1e9:.3f} GB (one-hot KV update) = "
        f"{(w_bytes + 2 * c_bytes) / 1e9:.3f} GB",
        flush=True,
    )
    t0 = time.perf_counter()
    params = init_params(jax.random.PRNGKey(seed), cfg)
    jax.block_until_ready(params)
    print(f"[smoke] init params {time.perf_counter() - t0:.2f}s {label}", flush=True)

    rng = np.random.default_rng(seed)
    n0 = len(_COMPILES)
    rel = check_decode_matches_prefill(
        cfg, params, n_slots=n_slots, max_seq=max_seq,
        prompt=rng.integers(0, cfg.vocab, size=prompt_lens[-1]).astype(np.int32),
    )
    print(
        f"[smoke] decode-through-cache vs prefill logits: max|diff| / max|logit| = "
        f"{rel:.6f} (tolerance {LOGITS_TOL}) {label}",
        flush=True,
    )
    if not rel <= LOGITS_TOL:
        raise AssertionError(f"decode logits differ from prefill by {rel} > {LOGITS_TOL}")

    wave = dict(n_requests=n_requests, prompt_lens=prompt_lens, gen=gen)
    with ServeEngine(cfg, params, n_slots=n_slots, max_seq=max_seq, block_size=block_size) as eng:
        t0 = time.perf_counter()
        reqs = _serve_wave(eng, cfg, rng, seed=seed, **wave)
        _check_wave(reqs, cfg, gen)
        print(
            f"[smoke] wave 1 (compiles included): {n_requests} requests served in "
            f"{time.perf_counter() - t0:.2f}s; compile seconds by function: "
            f"{_compile_report(n0)} {label}",
            flush=True,
        )
        n1, steps1 = len(_COMPILES), eng.steps
        t0 = time.perf_counter()
        reqs = _serve_wave(eng, cfg, rng, seed=seed + n_requests, **wave)
        dt = time.perf_counter() - t0
        _check_wave(reqs, cfg, gen)
        stats = eng.stats()
    n_tok = sum(len(r.out_tokens) for r in reqs)
    ttft = sorted(r.t_first - r.t_arrival for r in reqs)
    print(
        f"[smoke] wave 2: {n_tok} tokens in {dt:.3f}s = {n_tok / dt:.1f} tokens/s over "
        f"{stats['steps'] - steps1} engine steps; time to first token median "
        f"{ttft[len(ttft) // 2]:.3f}s max {ttft[-1]:.3f}s (queueing included); "
        f"{len(_COMPILES) - n1} compiles in the window; peak device memory "
        f"{_peak_bytes()} {label}",
        flush=True,
    )
    if stats["rejected"] or stats["prefills"] != 2 * n_requests:
        raise AssertionError(f"not every request was prefilled and served: {stats}")
    return {"logits_rel_diff": rel, "tokens": n_tok, "device": device}


# ---------------------------------------------------------------------------
# four chips: the sharded training path
# ---------------------------------------------------------------------------

def _mesh_losses(cfg, mesh, *, steps, batch, seq, microbatches, lr) -> list[float]:
    """The launcher's step (``repro.launch.train``'s state, schedule and
    data) on ``mesh``; returns the loss of every step."""
    import jax
    import jax.numpy as jnp

    from repro.data import SyntheticLMDataset
    from repro.dist.sharding import use_mesh
    from repro.models.config import ShapeSpec
    from repro.optim import linear_warmup_cosine
    from repro.runtime.train import build_train_step, init_train_state

    ds = SyntheticLMDataset(cfg, ShapeSpec("train", "train", seq, batch), seed=0)
    losses = []
    with use_mesh(mesh):
        state = init_train_state(jax.random.PRNGKey(0), cfg)
        art = build_train_step(
            cfg, n_microbatches=microbatches, schedule_policy="overlap",
            lr_schedule=linear_warmup_cosine(lr, warmup=10, total_steps=steps),
        )
        for step in range(steps):
            b = {k: jnp.asarray(v) for k, v in ds.batch_for_step(step).items()}
            state, metrics = art(state, b)
            losses.append(float(metrics["loss"]))
    del state
    return losses


def _agree(a: list[float], b: list[float]) -> float:
    """Largest relative difference between two loss curves."""
    return max(abs(x - y) / max(abs(y), 1.0) for x, y in zip(a, b, strict=True))


def train_phase(
    cfg=None, *, steps, batch, seq, microbatches, fail_at, lr: float = 1e-3,
    check=require_tpu, launcher_args=("--arch", ARCH),
) -> dict:
    """(2, 2) through the launcher's ``main`` with a simulated loss of two
    chips after ``fail_at`` steps (live re-mesh to (1, 2)), against the
    same steps and batches on (1, 4)."""
    device = check()
    import jax

    from repro.configs import get_config
    from repro.launch import train as launcher
    from repro.launch.mesh import make_host_mesh

    if device["count"] != 4:
        raise SystemExit(f"chip_smoke --chips 4: found {device['count']} device(s)")
    label = f"({device['kind']}, {device['count']} devices)"
    full = get_config(ARCH)
    if cfg is None:
        cfg = full.replace(n_layers=TRAIN["n_layers"])
    print(
        f"[smoke] train {cfg.name}: n_layers {full.n_layers} -> {cfg.n_layers} (only cut), "
        f"{cfg.param_count() / 1e9:.3f} B parameters; {steps} steps of batch {batch} x "
        f"seq {seq}, {microbatches} microbatches",
        flush=True,
    )
    _watch_compiles()
    t0 = time.perf_counter()
    ref = _mesh_losses(
        cfg, make_host_mesh(model_parallel=4), steps=steps, batch=batch, seq=seq,
        microbatches=microbatches, lr=lr,
    )
    print(
        f"[smoke] (data=1, model=4) losses {ref} in {time.perf_counter() - t0:.1f}s {label}",
        flush=True,
    )
    t0 = time.perf_counter()
    out = launcher.main([
        *launcher_args, "--layers", str(cfg.n_layers), "--steps", str(steps),
        "--batch", str(batch), "--seq", str(seq), "--microbatches", str(microbatches),
        "--lr", str(lr), "--fail-at", f"{fail_at}:2", "--recovery", "live",
        "--log-every", "1",
    ])
    losses, rec = out["losses"], out["recoveries"]
    print(
        f"[smoke] launcher (data=2, model=2) losses {losses[:fail_at]}, then live "
        f"re-mesh to 2 chips {rec} and (data=1, model=2) losses {losses[fail_at:]} "
        f"in {time.perf_counter() - t0:.1f}s {label}",
        flush=True,
    )
    if not (out["final_step"] == steps and len(rec) == 1 and rec[0]["mode"] == "live"):
        raise AssertionError(f"re-mesh did not complete: {out}")
    d_before, d_after = _agree(losses[:fail_at], ref[:fail_at]), _agree(losses[fail_at:], ref[fail_at:])
    print(
        f"[smoke] loss agreement vs (1, 4): (2, 2) max rel diff {d_before:.3e}, re-meshed "
        f"(1, 2) max rel diff {d_after:.3e} (tolerance {LOSS_RTOL}); peak device memory "
        f"{_peak_bytes()} {label}",
        flush=True,
    )
    if not max(d_before, d_after) <= LOSS_RTOL:
        raise AssertionError("losses disagree across meshes")
    return {"losses_22": losses[:fail_at], "losses_12": losses[fail_at:], "losses_14": ref, "device": device}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument(
        "--chips", type=int, choices=(1, 4), default=1,
        help="1: served path on one chip (default); 4: sharded training path",
    )
    args = ap.parse_args(argv)
    require_tpu()
    from repro.launch.compile_cache import enable_compile_cache

    print(f"[smoke] compile cache: {enable_compile_cache()}", flush=True)
    if args.chips == 4:
        res = train_phase(**{k: v for k, v in TRAIN.items() if k != "n_layers"})
    else:
        res = serve_phase(**{k: v for k, v in SERVE.items() if k != "n_layers"})
    print(json.dumps({"ok": True, "device": res["device"]}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
