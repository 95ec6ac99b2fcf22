"""Training step built from codelets and compiled through the staged backend
(DESIGN.md §2) — the paper's STF model driving a pod-scale SPMD step.

Task structure of one step (N microbatches), three codelets declared once::

    mb_0 ... mb_{N-1}   read(params), read(batch_i),
                        commutative(grads)             ← C1: order-free accum
    grad_finalize       comm task: mean + sharding constraint to the param
                        layout (the GSPMD reduce-scatter lands here)  ← C4
    optimizer           write(params/opt): clip + nonfinite check +
                        *speculative* update — computed unconditionally,
                        selected by the finite flag (branchless TPU analogue
                        of SpMaybeWrite+rollback, C6)

The step runs on ``SpRuntime(backend="staged")`` inside ``jax.jit``: the
scheduler policy decides the compiled program order — ``overlap`` hoists
the comm task between independent microbatch tasks; commutative accumulation
lets it reorder microbatches freely (both visible in EXPERIMENTS.md §Perf).
"""
from __future__ import annotations

from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp

from repro.core import SpData, SpRuntime, sp_task
from repro.dist.collectives import compress_tree, init_residuals
from repro.dist.sharding import current_mesh, named_sharding, shard
from repro.models import abstract_params, loss_fn, model_defs, param_shardings
from repro.models.config import ArchConfig, ShapeSpec
from repro.models.param import abstract_tree, sharding_tree
from repro.optim import TrainState, make_optimizer


# ---------------------------------------------------------------------------
# The three task shapes of a train step (codelet frontend, core/api.py).
# ---------------------------------------------------------------------------

@sp_task(read=("params", "mb"), commutative=("grads", "metrics"), name="mb", cost=10.0)
def _microbatch_codelet(params, mb, grads, metrics, *, grad_fn):
    """Forward+backward over one microbatch; order-free gradient accumulation."""
    (loss, m), g = grad_fn(params, mb)
    grads.value = jax.tree.map(
        lambda acc, gg: acc + gg.astype(acc.dtype), grads.value, g
    )
    metrics.value = {
        "loss": metrics.value["loss"] + loss.astype(jnp.float32),
        "ce_loss": metrics.value["ce_loss"] + m["ce_loss"].astype(jnp.float32),
    }
    return loss


@sp_task(write=("grads",), name="grad_allreduce", cost=3.0, comm=True)
def _grad_finalize_codelet(grads, *, n_mb, compress, p_sh):
    """Mean + (optional) int8 quantize-dequantize + reshard to the param
    layout — the GSPMD reduce-scatter lands on this comm task."""
    g = jax.tree.map(lambda t: t / n_mb, grads.value)
    if compress:
        # error-feedback residuals live across steps via state in a
        # production driver; stateless inside one compiled step we
        # quantize-dequantize only (documented in EXPERIMENTS.md)
        g, _ = compress_tree(
            g, jax.tree.map(lambda t: jnp.zeros_like(t, jnp.float32), g)
        )
    if p_sh is not None:
        g = jax.tree.map(
            lambda t, s: jax.lax.with_sharding_constraint(t, s), g, p_sh
        )
    grads.value = g


@sp_task(
    read=("grads",),
    write=("params", "opt", "new_step"),
    name="optimizer",
    cost=5.0,
)
def _optimizer_codelet(
    grads, params, opt, new_step, *, opt_update, lr_schedule, clip_norm, step
):
    """Clip + nonfinite check + branchless-speculative update (C6): the
    update is computed unconditionally; rollback = select the old state."""
    from repro.optim.optimizer import global_norm

    gnorm = global_norm(grads)
    finite = jnp.isfinite(gnorm)
    scale = jnp.minimum(1.0, clip_norm / jnp.maximum(gnorm, 1e-9))
    g_clipped = jax.tree.map(lambda t: t * scale, grads)
    lr = lr_schedule(step)
    cand_p, cand_o = opt_update(g_clipped, opt.value, params.value, lr, step)
    sel = lambda new, old: jnp.where(finite, new, old)
    params.value = jax.tree.map(sel, cand_p, params.value)
    opt.value = jax.tree.map(sel, cand_o, opt.value)
    new_step.value = step + 1
    return gnorm


class TrainStepArtifacts:
    """Holds the jitted step + shardings + schedule introspection."""

    def __init__(self, step_fn, in_shardings, out_shardings, schedule_names):
        self.step_fn = step_fn
        self.in_shardings = in_shardings
        self.out_shardings = out_shardings
        self.schedule_names = schedule_names

    def __call__(self, state, batch):
        return self.step_fn(state, batch)


def train_state_shardings(cfg: ArchConfig):
    """NamedSharding tree for TrainState (requires active mesh context)."""
    defs = model_defs(cfg)
    p_sh = sharding_tree(defs)
    opt_init, _ = make_optimizer(cfg.optimizer, cfg.opt_state_dtype)
    # optimizer state mirrors the param tree (adamw) — reuse param shardings
    if cfg.optimizer == "adamw":
        opt_sh = {"m": p_sh, "v": p_sh}
    else:  # adafactor states are small; replicate
        abs_p = abstract_tree(defs, cfg.dtype)
        opt_abs = opt_init(abs_p)
        opt_sh = jax.tree.map(lambda _: named_sharding((), ()), opt_abs)
    step_sh = named_sharding((), ())
    return TrainState(step=step_sh, params=p_sh, opt=opt_sh)


def abstract_train_state(cfg: ArchConfig) -> TrainState:
    """ShapeDtypeStruct TrainState for .lower() (no allocation)."""
    params = abstract_params(cfg)
    opt_init, _ = make_optimizer(cfg.optimizer, cfg.opt_state_dtype)
    opt = jax.eval_shape(opt_init, params)
    if current_mesh() is not None:
        sh = train_state_shardings(cfg)
        params = jax.tree.map(
            lambda s, d: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=d),
            params,
            sh.params,
        )
        opt = jax.tree.map(
            lambda s, d: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=d),
            opt,
            sh.opt,
        )
    step = jax.ShapeDtypeStruct((), jnp.int32)
    return TrainState(step=step, params=params, opt=opt)


def init_train_state(rng: jax.Array, cfg: ArchConfig) -> TrainState:
    """Fresh TrainState.  Under an active mesh it is initialised by one
    jitted program straight into :func:`train_state_shardings`, so no device
    ever holds the whole state (at published widths that alone can exhaust
    one chip)."""
    from repro.models import init_params

    opt_init, _ = make_optimizer(cfg.optimizer, cfg.opt_state_dtype)

    def init(key):
        params = init_params(key, cfg)
        return TrainState(step=jnp.int32(0), params=params, opt=opt_init(params))

    if current_mesh() is None:
        return init(rng)
    return jax.jit(init, out_shardings=train_state_shardings(cfg))(rng)


def build_train_step(
    cfg: ArchConfig,
    *,
    n_microbatches: int = 1,
    schedule_policy: str = "overlap",
    lr_schedule: Optional[Callable] = None,
    clip_norm: float = 1.0,
    grad_accum_dtype: str = "float32",
    grad_compression: bool = False,
    jit: bool = True,
    donate: bool = True,
):
    """Build the staged train step.  Returns ``TrainStepArtifacts``."""
    lr_schedule = lr_schedule or (lambda step: jnp.float32(3e-4))
    opt_init, opt_update = make_optimizer(cfg.optimizer, cfg.opt_state_dtype)
    schedule_names: list[str] = []

    def train_step(state: TrainState, batch: dict):
        params_c = SpData(state.params, "params")
        zero_g = jax.tree.map(
            lambda p: jnp.zeros(p.shape, jnp.dtype(grad_accum_dtype)), state.params
        )
        grads_c = SpData(zero_g, "grads")
        metrics_c = SpData(
            {"loss": jnp.float32(0.0), "ce_loss": jnp.float32(0.0)}, "metrics"
        )
        opt_c = SpData(state.opt, "opt")
        new_step_c = SpData(None, "new_step")

        n_mb = n_microbatches
        mb_batch = jax.tree.map(
            lambda t: t.reshape((n_mb, t.shape[0] // n_mb) + t.shape[1:]), batch
        )
        grad_fn = jax.value_and_grad(lambda p, b: loss_fn(p, b, cfg), has_aux=True)
        p_sh = param_shardings(cfg) if current_mesh() is not None else None

        with SpRuntime(backend="staged", policy=schedule_policy) as rt:
            for i in range(n_mb):
                mb_c = SpData(jax.tree.map(lambda t: t[i], mb_batch), f"mb{i}")
                _microbatch_codelet(
                    params_c, mb_c, grads_c, metrics_c,
                    grad_fn=grad_fn, name=f"mb{i}",
                )
            _grad_finalize_codelet(
                grads_c, n_mb=n_mb, compress=grad_compression, p_sh=p_sh
            )
            gnorm_view = _optimizer_codelet(
                grads_c, params_c, opt_c, new_step_c,
                opt_update=opt_update, lr_schedule=lr_schedule,
                clip_norm=clip_norm, step=state.step,
            )
            order = rt.run()
        if not schedule_names:
            schedule_names.extend(t.name for t in order)

        metrics = jax.tree.map(lambda t: t / n_mb, metrics_c.value)
        metrics["grad_norm"] = gnorm_view.result()
        new_state = TrainState(
            step=new_step_c.value, params=params_c.value, opt=opt_c.value
        )
        return new_state, metrics

    if not jit:
        return TrainStepArtifacts(train_step, None, None, schedule_names)

    in_sh = out_sh = None
    donate_argnums = (0,) if donate else ()
    if current_mesh() is not None:
        st_sh = train_state_shardings(cfg)
        in_sh = (st_sh, None)  # batch sharding inferred from input specs
        out_sh = (st_sh, None)
        step_fn = jax.jit(
            train_step,
            in_shardings=in_sh,
            out_shardings=out_sh,
            donate_argnums=donate_argnums,
        )
    else:
        step_fn = jax.jit(train_step, donate_argnums=donate_argnums)
    return TrainStepArtifacts(step_fn, in_sh, out_sh, schedule_names)
