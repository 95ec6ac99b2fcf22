"""Model assembly: block definitions per family, scan-over-layers stacks,
train/prefill forward, decode step, losses, abstract init, input specs.

One code path serves all 10 assigned architectures (DESIGN.md §4); family
differences are block *kinds*:

* ``attn`` — pre-norm attention + gated MLP (dense / encoder / vlm)
* ``mla``  — multi-head latent attention + MLP (minicpm3)
* ``moe``  — attention + mixture-of-experts (qwen3-moe, llama4-scout)
* ``mla_moe`` — latent attention + mixture-of-experts (DeepSeek-V2-Lite),
  after ``moe.first_k_dense`` leading ``mla`` layers with a dense MLP
* ``ssm``  — Mamba-2 SSD block (mamba2)
* ``rec``  — RG-LRU recurrent block + MLP (recurrentgemma, with its
  (rec, rec, attn) pattern scanned as super-blocks)

Compile hygiene: homogeneous stacks are ``lax.scan``-ed over a stacked
parameter pytree (compile one layer, not 94) with a remat policy knob.
Leading dense layers (``params["lead"]``) run before the scanned stack;
the decode caches stay one stack over every layer.
"""
from __future__ import annotations

import functools
from typing import Any, Optional

import jax
import jax.numpy as jnp

from repro.dist.sharding import shard
from repro.models import attention as attn_mod
from repro.models import mla as mla_mod
from repro.models import moe as moe_mod
from repro.models import rglru as rec_mod
from repro.models import ssm as ssm_mod
from repro.models.config import ArchConfig, ShapeSpec
from repro.models.layers import (
    chunked_softmax_xent,
    embed_apply,
    embed_defs,
    logits_apply,
    mlp_apply,
    mlp_defs,
    rmsnorm,
    rmsnorm_def,
    softmax_xent,
)
from repro.models.param import (
    ParamDef,
    abstract_tree,
    axes_tree,
    init_tree,
    sharding_tree,
    stack_defs,
)

# ---------------------------------------------------------------------------
# Block definitions
# ---------------------------------------------------------------------------

def block_kind(cfg: ArchConfig) -> str:
    if cfg.family == "ssm":
        return "ssm"
    if cfg.family == "moe":
        return "mla_moe" if cfg.mla is not None else "moe"
    if cfg.mla is not None:
        return "mla"
    return "attn"


#: the kind of the leading dense layers in front of an expert stack
_LEAD_KIND = {"moe": "attn", "mla_moe": "mla"}


def n_lead(cfg: ArchConfig) -> int:
    """Leading dense layers outside the scanned stack."""
    return cfg.moe.first_k_dense if cfg.moe is not None else 0


def block_defs(cfg: ArchConfig, kind: str) -> dict:
    D = cfg.d_model
    if kind == "ssm":
        return {"ln1": rmsnorm_def(D), "ssm": ssm_mod.ssm_defs(cfg)}
    if kind == "rec":
        return {
            "ln1": rmsnorm_def(D),
            "rec": rec_mod.rglru_defs(cfg),
            "ln2": rmsnorm_def(D),
            "mlp": mlp_defs(D, cfg.d_ff, cfg.act),
        }
    if kind in ("moe", "mla_moe"):
        return {
            "ln1": rmsnorm_def(D),
            "attn": (mla_mod.mla_defs if kind == "mla_moe" else attn_mod.attn_defs)(cfg),
            "ln2": rmsnorm_def(D),
            "moe": moe_mod.moe_defs(cfg),
        }
    if kind == "mla":
        return {
            "ln1": rmsnorm_def(D),
            "attn": mla_mod.mla_defs(cfg),
            "ln2": rmsnorm_def(D),
            "mlp": mlp_defs(D, cfg.d_ff, cfg.act),
        }
    return {
        "ln1": rmsnorm_def(D),
        "attn": attn_mod.attn_defs(cfg),
        "ln2": rmsnorm_def(D),
        "mlp": mlp_defs(D, cfg.d_ff, cfg.act),
    }


def _zero_aux() -> dict:
    return {"moe_balance": jnp.float32(0.0), "moe_zloss": jnp.float32(0.0)}


def block_apply(
    p: dict,
    x: jax.Array,
    positions: jax.Array,
    cfg: ArchConfig,
    kind: str,
    *,
    causal: bool,
    want_cache: bool,
):
    """Returns (y, cache, aux)."""
    aux = _zero_aux()
    if kind == "ssm":
        h, cache = ssm_mod.ssm_apply(p["ssm"], rmsnorm(x, p["ln1"], cfg.norm_eps), cfg, want_cache=want_cache)
        return x + h, cache, aux
    if kind == "rec":
        h, cache = rec_mod.rglru_apply(p["rec"], rmsnorm(x, p["ln1"], cfg.norm_eps), cfg, want_cache=want_cache)
        x = x + h
        x = x + mlp_apply(p["mlp"], rmsnorm(x, p["ln2"], cfg.norm_eps), cfg.act)
        return x, cache, aux
    # attn / mla, then a dense MLP / moe
    attn = mla_mod.mla_apply if kind in ("mla", "mla_moe") else attn_mod.attention_apply
    h, cache = attn(
        p["attn"], rmsnorm(x, p["ln1"], cfg.norm_eps), positions, cfg,
        causal=causal, want_cache=want_cache,
    )
    x = x + h
    if kind in ("moe", "mla_moe"):
        h, aux = moe_mod.moe_apply(p["moe"], rmsnorm(x, p["ln2"], cfg.norm_eps), cfg)
    else:
        h = mlp_apply(p["mlp"], rmsnorm(x, p["ln2"], cfg.norm_eps), cfg.act)
    return x + h, cache, aux


def block_decode(p: dict, x: jax.Array, cache, pos, cfg: ArchConfig, kind: str, layer=None):
    """Returns (y, cache, routed): ``routed`` is the choices routed to each
    expert this layer holds (ragged expert layers), else None.  With
    ``layer``, ``cache`` is the stack over every layer and this block reads
    and writes layer ``layer`` of it in place."""
    if kind in ("ssm", "rec"):
        step = ssm_mod.ssm_decode_step if kind == "ssm" else rec_mod.rglru_decode_step
        h, c = step(p[kind], rmsnorm(x, p["ln1"], cfg.norm_eps),
                    jax.tree.map(lambda t: attn_mod.layer_of(t, layer), cache), cfg)
        if layer is not None:
            c = jax.tree.map(lambda t, u: jax.lax.dynamic_update_index_in_dim(t, u, layer, 0),
                             cache, c)
        x = x + h
        if kind == "ssm":
            return x, c, None
        return x + mlp_apply(p["mlp"], rmsnorm(x, p["ln2"], cfg.norm_eps), cfg.act), c, None
    attn = mla_mod.mla_decode_step if kind in ("mla", "mla_moe") else attn_mod.attention_decode_step
    h, cache = attn(p["attn"], rmsnorm(x, p["ln1"], cfg.norm_eps), cache, pos, cfg, layer)
    x = x + h
    routed = None
    if kind in ("moe", "mla_moe"):
        h, aux = moe_mod.moe_apply(p["moe"], rmsnorm(x, p["ln2"], cfg.norm_eps), cfg)
        routed = aux.get("expert_tokens")
    else:
        h = mlp_apply(p["mlp"], rmsnorm(x, p["ln2"], cfg.norm_eps), cfg.act)
    return x + h, cache, routed


def _block_constraint(cfg: ArchConfig, kind_or_defs) -> Any:
    """Per-layer param sharding constraint applied INSIDE the scan body.

    Constraining the primal layer params makes GSPMD (a) all-gather each
    layer's FSDP-sharded weights just-in-time and (b) — via the transpose of
    ``with_sharding_constraint`` — reduce-scatter each layer's weight
    cotangents immediately, so the stacked grad accumulator stays sharded
    over the data axis instead of materializing replicated (the dominant
    memory term for ≥100B configs; EXPERIMENTS.md §Perf)."""
    from repro.dist.sharding import current_mesh

    if current_mesh() is None:
        return lambda lp: lp
    defs = kind_or_defs if isinstance(kind_or_defs, dict) else block_defs(cfg, kind_or_defs)
    sh = sharding_tree(defs)

    def apply(lp):
        return jax.tree.map(
            lambda t, s: jax.lax.with_sharding_constraint(t, s), lp, sh
        )

    return apply


# ---------------------------------------------------------------------------
# Hybrid (recurrentgemma) layer layout
# ---------------------------------------------------------------------------

def hybrid_layout(cfg: ArchConfig) -> tuple[int, tuple[str, ...]]:
    """(#scanned super-blocks, remainder kinds)."""
    pat = cfg.hybrid.pattern
    n_super = cfg.n_layers // len(pat)
    rem = cfg.n_layers - n_super * len(pat)
    return n_super, pat[:rem]


def _hybrid_window_cfg(cfg: ArchConfig) -> ArchConfig:
    """Inside a hybrid model the attention sub-blocks use the local window."""
    return cfg.replace(attn_window=cfg.hybrid.window)


# ---------------------------------------------------------------------------
# Model definitions
# ---------------------------------------------------------------------------

def model_defs(cfg: ArchConfig) -> dict:
    D = cfg.d_model
    defs: dict[str, Any] = {}
    if cfg.frontend == "audio":
        defs["frontend_proj"] = ParamDef((512, D), (None, "embed"))
        defs["mask_emb"] = ParamDef((D,), (None,))
        defs["head"] = ParamDef((D, cfg.padded_vocab), ("embed", "vocab"))
    elif cfg.frontend == "vision":
        defs["patch_proj"] = ParamDef((1024, D), (None, "embed"))
        defs.update(embed_defs(cfg))
    else:
        defs.update(embed_defs(cfg))

    if cfg.family == "hybrid":
        hcfg = _hybrid_window_cfg(cfg)
        n_super, rem = hybrid_layout(cfg)
        pat = cfg.hybrid.pattern
        super_defs = {f"{k}_{i}": block_defs(hcfg, k) for i, k in enumerate(pat)}
        defs["layers"] = stack_defs(super_defs, n_super)
        for i, k in enumerate(rem):
            defs[f"tail_{i}"] = block_defs(hcfg, k)
    else:
        kind = block_kind(cfg)
        lead = n_lead(cfg)
        if lead:
            defs["lead"] = stack_defs(block_defs(cfg, _LEAD_KIND[kind]), lead)
        defs["layers"] = stack_defs(block_defs(cfg, kind), cfg.n_layers - lead)
    defs["final_norm"] = rmsnorm_def(D)
    return defs


@functools.partial(jax.jit, static_argnums=1)
def init_params(rng: jax.Array, cfg: ArchConfig):
    """Random parameters as one compiled program: each leaf's float32 draw
    fuses into its cast, so no float32 copy is ever materialised.  Run
    eagerly, every op compiled on its own: 86.7 s for 8 layers of
    deepseek-7b on a TPU v5e, against 21.4 s jitted, compile included."""
    return init_tree(model_defs(cfg), rng, cfg.dtype)


def abstract_params(cfg: ArchConfig):
    return abstract_tree(model_defs(cfg), cfg.dtype)


def param_shardings(cfg: ArchConfig):
    return sharding_tree(model_defs(cfg))


# ---------------------------------------------------------------------------
# Embedding of inputs (with modality-frontend stubs)
# ---------------------------------------------------------------------------

def embed_inputs(params: dict, batch: dict, cfg: ArchConfig):
    """→ (x (B,L,D), positions (B,L))."""
    if cfg.frontend == "audio":
        x = jnp.einsum("blf,fd->bld", batch["embeds"].astype(jnp.dtype(cfg.dtype)), params["frontend_proj"])
        if "mask" in batch:
            m = batch["mask"][..., None]
            x = jnp.where(m, params["mask_emb"].astype(x.dtype), x)
        B, L = x.shape[:2]
    elif cfg.frontend == "vision":
        patches = jnp.einsum(
            "bpf,fd->bpd", batch["patch_embeds"].astype(jnp.dtype(cfg.dtype)), params["patch_proj"]
        )
        text = embed_apply(params, batch["tokens"], cfg)
        x = jnp.concatenate([patches, text], axis=1)
        B, L = x.shape[:2]
    else:
        x = embed_apply(params, batch["tokens"], cfg)
        B, L = x.shape[:2]
    positions = jnp.broadcast_to(jnp.arange(L, dtype=jnp.int32), (B, L))
    x = shard(x, "batch", "act_seq", None)
    return x, positions


# ---------------------------------------------------------------------------
# Forward (train / prefill)
# ---------------------------------------------------------------------------

def _remat(fn, cfg: ArchConfig):
    if cfg.remat == "none":
        return fn
    if cfg.remat == "dots_saveable":
        return jax.checkpoint(fn, policy=jax.checkpoint_policies.dots_saveable)
    return jax.checkpoint(fn)


def forward(params: dict, batch: dict, cfg: ArchConfig, *, want_cache: bool = False):
    """→ (hidden (B,L,D), caches|None, aux dict)."""
    x, positions = embed_inputs(params, batch, cfg)
    causal = not cfg.is_encoder

    if cfg.family == "hybrid":
        hcfg = _hybrid_window_cfg(cfg)
        pat = cfg.hybrid.pattern
        n_super, rem = hybrid_layout(cfg)

        super_defs = {f"{k}_{i}": block_defs(hcfg, k) for i, k in enumerate(pat)}
        constrain = _block_constraint(hcfg, super_defs)

        def super_fn(x, lp):
            lp = constrain(lp)
            caches = {}
            aux_tot = _zero_aux()
            for i, k in enumerate(pat):
                x, cache, aux = block_apply(
                    lp[f"{k}_{i}"], x, positions, hcfg, k, causal=causal, want_cache=want_cache
                )
                caches[f"{k}_{i}"] = cache
                aux_tot = jax.tree.map(lambda a, b: a + b, aux_tot, aux)
            return x, (caches, aux_tot)

        body = _remat(super_fn, cfg)
        if cfg.scan_layers:
            x, (caches, auxs) = jax.lax.scan(lambda c, lp: body(c, lp), x, params["layers"])
            aux = jax.tree.map(jnp.sum, auxs)
        else:
            caches_l, aux = [], _zero_aux()
            for si in range(n_super):
                lp = jax.tree.map(lambda t: t[si], params["layers"])
                x, (cache, a) = body(x, lp)
                caches_l.append(cache)
                aux = jax.tree.map(lambda u, v: u + v, aux, a)
            caches = (
                jax.tree.map(lambda *xs: jnp.stack(xs), *caches_l) if want_cache else None
            )
        tail_caches = []
        for i, k in enumerate(rem):
            x, cache, a = block_apply(
                params[f"tail_{i}"], x, positions, hcfg, k, causal=causal, want_cache=want_cache
            )
            tail_caches.append(cache)
            aux = jax.tree.map(lambda u, v: u + v, aux, a)
        caches_out = {"scan": caches, "tail": tail_caches} if want_cache else None
        x = rmsnorm(x, params["final_norm"], cfg.norm_eps)
        return x, caches_out, aux

    kind = block_kind(cfg)
    lead = n_lead(cfg)
    lead_caches = []
    for li in range(lead):
        lp = jax.tree.map(lambda t: t[li], params["lead"])
        x, cache, _ = block_apply(
            lp, x, positions, cfg, _LEAD_KIND[kind], causal=causal, want_cache=want_cache
        )
        lead_caches.append(cache)
    constrain = _block_constraint(cfg, kind)

    def layer_fn(x, lp):
        lp = constrain(lp)
        y, cache, aux = block_apply(lp, x, positions, cfg, kind, causal=causal, want_cache=want_cache)
        routed = aux.pop("expert_tokens", None)  # per layer, never summed
        return y, (cache, aux, routed)

    body = _remat(layer_fn, cfg)
    if cfg.scan_layers:
        x, (caches, auxs, routed) = jax.lax.scan(lambda c, lp: body(c, lp), x, params["layers"])
        aux = jax.tree.map(jnp.sum, auxs)
    else:
        caches_l, routed_l, aux = [], [], _zero_aux()
        for li in range(cfg.n_layers - lead):
            lp = jax.tree.map(lambda t: t[li], params["layers"])
            x, (cache, a, r) = body(x, lp)
            caches_l.append(cache)
            routed_l.append(r)
            aux = jax.tree.map(lambda u, v: u + v, aux, a)
        caches = (
            jax.tree.map(lambda *xs: jnp.stack(xs), *caches_l) if want_cache else None
        )
        routed = None if routed_l[0] is None else jnp.stack(routed_l)
    if routed is not None:
        aux["expert_tokens"] = routed  # (expert layers, experts held)
    if want_cache and lead:  # one stack over every layer, leading ones first
        caches = jax.tree.map(
            lambda c, *ls: jnp.concatenate([jnp.stack(ls), c]), caches, *lead_caches
        )
    x = rmsnorm(x, params["final_norm"], cfg.norm_eps)
    return x, (caches if want_cache else None), aux


# ---------------------------------------------------------------------------
# Losses / steps
# ---------------------------------------------------------------------------

AUX_WEIGHTS = {"moe_balance": 0.01, "moe_zloss": 1e-3}


def loss_fn(params: dict, batch: dict, cfg: ArchConfig):
    x, _, aux = forward(params, batch, cfg)
    labels = batch["labels"]
    mask = batch.get("mask")
    if cfg.frontend == "audio":
        logits = jnp.einsum("bld,dv->blv", x, params["head"])
        loss = softmax_xent(logits, labels, mask)
    elif cfg.logits_chunk:
        if cfg.frontend == "vision":
            x = x[:, -labels.shape[1] :]
        loss = chunked_softmax_xent(x, labels, params, cfg, mask, chunk=cfg.logits_chunk)
    else:
        if cfg.frontend == "vision":
            x = x[:, -labels.shape[1] :]
        logits = logits_apply(params, x, cfg)
        loss = softmax_xent(logits, labels, mask)
    total = loss
    metrics = {"ce_loss": loss}
    for k, w in AUX_WEIGHTS.items():
        if cfg.family == "moe":
            total = total + w * aux[k] / cfg.n_layers
            metrics[k] = aux[k] / cfg.n_layers
    return total, metrics


def prefill(params: dict, batch: dict, cfg: ArchConfig, *, counts: bool = False):
    """→ (last-token logits (B,1,V), caches).  Only the final position's
    logits are computed (memory discipline for 32k×150k-vocab prefill).
    ``counts`` adds a third output: the choices routed to each held expert
    per expert layer, (expert layers, experts held) int32, or None where no
    layer counts them."""
    x, caches, aux = forward(params, batch, cfg, want_cache=True)
    x_last = x[:, -1:]
    if cfg.frontend == "audio":
        logits = jnp.einsum("bld,dv->blv", x_last, params["head"])
    else:
        logits = logits_apply(params, x_last, cfg)
    if counts:
        return logits, caches, aux.get("expert_tokens")
    return logits, caches


def _decode_stack(params: dict, x, caches, pos, cfg: ArchConfig, kind: str):
    """Decode through the leading dense layers, then the scanned stack,
    with the caches kept as one stack over every layer that the loop
    carries: each layer writes its new rows into its own layer of the stack
    and reads that layer in place, so no copy of a layer or of the whole
    cache is made."""
    lead = n_lead(cfg)
    for li in range(lead):
        lp = jax.tree.map(lambda t: t[li], params["lead"])
        x, caches, _ = block_decode(lp, x, caches, pos, cfg, _LEAD_KIND[kind], li)
    constrain = _block_constraint(cfg, kind)

    def layer_fn(carry, inp):
        x, caches = carry
        lp, li = inp
        x, caches, routed = block_decode(constrain(lp), x, caches, pos, cfg, kind, li)
        return (x, caches), routed

    idx = jnp.arange(lead, cfg.n_layers, dtype=jnp.int32)
    if cfg.scan_layers:
        (x, caches), routed = jax.lax.scan(layer_fn, (x, caches), (params["layers"], idx))
        return x, caches, routed
    routed_l = []
    for li in range(cfg.n_layers - lead):
        lp = jax.tree.map(lambda t: t[li], params["layers"])
        (x, caches), r = layer_fn((x, caches), (lp, idx[li]))
        routed_l.append(r)
    return x, caches, None if routed_l[0] is None else jnp.stack(routed_l)


def decode_step(params: dict, tokens: jax.Array, caches, pos, cfg: ArchConfig, *,
                counts: bool = False):
    """One decode step.  tokens (B,1) int32; pos scalar int32 (current
    position).  → (logits (B,1,V), new caches); ``counts`` adds the choices
    routed to each held expert per expert layer, as :func:`prefill` does."""
    x = embed_apply(params, tokens, cfg)
    routed = None

    if cfg.family == "hybrid":
        hcfg = _hybrid_window_cfg(cfg)
        pat = cfg.hybrid.pattern
        n_super, rem = hybrid_layout(cfg)

        def super_fn(x, inp):
            lp, cache = inp
            new = {}
            for i, k in enumerate(pat):
                x, c, _ = block_decode(lp[f"{k}_{i}"], x, cache[f"{k}_{i}"], pos, hcfg, k)
                new[f"{k}_{i}"] = c
            return x, new

        if cfg.scan_layers:
            x, new_scan = jax.lax.scan(super_fn, x, (params["layers"], caches["scan"]))
        else:
            new_l = []
            for si in range(n_super):
                inp = jax.tree.map(lambda t: t[si], (params["layers"], caches["scan"]))
                x, c = super_fn(x, inp)
                new_l.append(c)
            new_scan = jax.tree.map(lambda *xs: jnp.stack(xs), *new_l)
        new_tail = []
        for i, k in enumerate(rem):
            x, c, _ = block_decode(params[f"tail_{i}"], x, caches["tail"][i], pos, hcfg, k)
            new_tail.append(c)
        new_caches = {"scan": new_scan, "tail": new_tail}
    else:
        x, new_caches, routed = _decode_stack(params, x, caches, pos, cfg, block_kind(cfg))

    x = rmsnorm(x, params["final_norm"], cfg.norm_eps)
    logits = logits_apply(params, x, cfg)
    if counts:
        return logits, new_caches, routed
    return logits, new_caches


def verify_step(params: dict, tokens: jax.Array, caches, pos, cfg: ArchConfig,
                advance=None):
    """Multi-position decode for speculative-decoding verification.

    Feeds ``tokens`` (B, T) int32 one position at a time starting at ``pos``
    (scalar or (B,) int32) and returns the logits of **every** position:
    ``(logits (B, T, V), new caches)``.  ``advance`` (optional, (B,) int32
    0/1) lets sequences opt out of advancing — a slot with ``advance == 0``
    re-feeds its token at the same position each sub-step (an idempotent KV
    row rewrite), which is how non-speculative requests ride along in a
    mixed verification batch.

    Implementation note: the loop body is *exactly* :func:`decode_step`, so
    per-position numerics (einsum reduction orders, masking, softmax) are
    identical to the plain decode path — this is what makes greedy
    speculative decoding bit-exact against the non-speculative oracle.  The
    whole loop jits into one XLA call (T is static), so the runtime sees a
    single batched verify forward per round.
    """
    T = tokens.shape[1]
    pos = jnp.asarray(pos, jnp.int32)
    adv = None if advance is None else jnp.asarray(advance, jnp.int32)
    outs = []
    for j in range(T):
        pj = pos + (j if adv is None else j * adv)
        logits_j, caches = decode_step(params, tokens[:, j:j + 1], caches, pj, cfg)
        outs.append(logits_j)
    return jnp.concatenate(outs, axis=1), caches


# ---------------------------------------------------------------------------
# Cache + input specs (ShapeDtypeStruct stand-ins for the dry-run)
# ---------------------------------------------------------------------------

def _cache_defs_for_kind(cfg: ArchConfig, kind: str, batch: int, max_seq: int) -> dict:
    if kind == "ssm":
        s, d_in, H = ssm_mod._dims(cfg)
        conv_ch = d_in + 2 * s.n_groups * s.d_state
        return {
            "state": ParamDef((batch, H, s.d_state, s.head_dim), ("batch", None, None, None), dtype="float32"),
            "conv": ParamDef((batch, 3, conv_ch), ("batch", None, "ff"), dtype=cfg.dtype),
        }
    if kind == "rec":
        W = cfg.hybrid.lru_width or cfg.d_model
        return {
            "h": ParamDef((batch, W), ("batch", "ff"), dtype="float32"),
            "conv": ParamDef((batch, cfg.hybrid.conv_width - 1, W), ("batch", None, "ff"), dtype=cfg.dtype),
        }
    if kind in ("mla", "mla_moe"):
        shapes = mla_mod.mla_cache_shapes(cfg, batch, max_seq)
        return {k: ParamDef(sh, ax, dtype=cfg.dtype) for k, (sh, ax) in shapes.items()}
    # attention (ring-buffered if windowed)
    sh = attn_mod.kv_cache_shape(cfg, batch, max_seq)
    ax = attn_mod.kv_cache_axes(cfg)
    return {"k": ParamDef(sh, ax, dtype=cfg.dtype), "v": ParamDef(sh, ax, dtype=cfg.dtype)}


def cache_defs(cfg: ArchConfig, batch: int, max_seq: int) -> dict:
    if cfg.family == "hybrid":
        hcfg = _hybrid_window_cfg(cfg)
        pat = cfg.hybrid.pattern
        n_super, rem = hybrid_layout(cfg)
        super_defs = {
            f"{k}_{i}": _cache_defs_for_kind(hcfg, k, batch, max_seq) for i, k in enumerate(pat)
        }
        return {
            "scan": stack_defs(super_defs, n_super),
            "tail": [ _cache_defs_for_kind(hcfg, k, batch, max_seq) for k in rem ],
        }
    kind = block_kind(cfg)
    return stack_defs(_cache_defs_for_kind(cfg, kind, batch, max_seq), cfg.n_layers)


def cache_layout(cfg: ArchConfig) -> Optional[dict]:
    """Per-leaf ``(batch_axis, seq_axis)`` of the *stacked* decode caches —
    the plumbing the paged serving tier needs to slice per-token KV rows
    into block tables (``serving/kvcache.py``).

    Returns None when the family's decode cache has no per-token rows to
    page: ssm/rec carry a recurrent state (one vector per sequence, not per
    token), ring-buffered windowed attention folds positions modulo the
    window, and hybrid stacks mix both.  The serving engine falls back to
    logical block accounting only (no payload save/restore) in that case.
    """
    if cfg.family == "hybrid":
        return None
    kind = block_kind(cfg)
    if kind in ("ssm", "rec"):
        return None
    if cfg.attn_window is not None:
        return None
    # stacked caches: axis 0 = layer, 1 = batch (slot), 2 = sequence
    names = ("c_kv", "k_rope") if cfg.mla is not None else ("k", "v")
    return {n: (1, 2) for n in names}


def init_cache(cfg: ArchConfig, batch: int, max_seq: int):
    defs = cache_defs(cfg, batch, max_seq)

    def mk(d):
        return jnp.zeros(d.shape, jnp.dtype(d.dtype or cfg.dtype))

    return jax.tree.map(mk, defs, is_leaf=lambda x: isinstance(x, ParamDef))


def abstract_cache(cfg: ArchConfig, batch: int, max_seq: int):
    return abstract_tree(cache_defs(cfg, batch, max_seq), cfg.dtype)


def input_defs(cfg: ArchConfig, shape: ShapeSpec) -> dict:
    """ParamDef tree for one batch of inputs under ``shape``."""
    B, L = shape.global_batch, shape.seq_len
    if shape.kind == "decode":
        return {"tokens": ParamDef((B, 1), ("batch", None), dtype="int32")}
    if cfg.frontend == "audio":
        return {
            "embeds": ParamDef((B, L, 512), ("batch", None, None), dtype=cfg.dtype),
            "mask": ParamDef((B, L), ("batch", None), dtype="bool"),
            "labels": ParamDef((B, L), ("batch", None), dtype="int32"),
        }
    if cfg.frontend == "vision":
        lt = L - cfg.n_patches
        out = {
            "tokens": ParamDef((B, lt), ("batch", None), dtype="int32"),
            "patch_embeds": ParamDef((B, cfg.n_patches, 1024), ("batch", None, None), dtype=cfg.dtype),
        }
        if shape.kind == "train":
            out["labels"] = ParamDef((B, lt), ("batch", None), dtype="int32")
        return out
    out = {"tokens": ParamDef((B, L), ("batch", None), dtype="int32")}
    if shape.kind == "train":
        out["labels"] = ParamDef((B, L), ("batch", None), dtype="int32")
    return out


def abstract_inputs(cfg: ArchConfig, shape: ShapeSpec):
    return abstract_tree(input_defs(cfg, shape), cfg.dtype)
