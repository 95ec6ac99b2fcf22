"""Mixture-of-Experts layer with expert parallelism (qwen3-moe, llama4-scout,
DeepSeek-V2-Lite).

Three dispatch strategies — the central §Perf lever for the MoE cells:

* ``einsum``  — GShard-style grouped one-hot dispatch/combine einsums with a
  per-group capacity.  Simple and numerically exact w.r.t. capacity
  semantics, but the dispatch einsums add ~2× matmul FLOPs and the
  (G, S, E, C) one-hot tensor inflates the memory term.  This is the
  paper-era baseline.
* ``scatter`` — sort-based dispatch: tokens are scatter-added into per-expert
  capacity buffers, expert GEMMs run on the packed (E, C, D) buffer, results
  gather back.  No dispatch-matmul FLOPs; HLO FLOPs ≈ useful FLOPs.
* ``ragged``  — the layer of one expert-parallel rank: it holds experts
  ``moe.held`` of the ``n_experts`` the router scores, sorts the tokens'
  choices of those experts by expert and runs one grouped matmul
  (``lax.ragged_dot``) per projection.  No capacity: every choice of a held
  expert is computed, and choices of experts held elsewhere add nothing
  here (on one chip the exchange with the other ranks is absent).

einsum and scatter shard experts over the ``model`` axis (expert
parallelism) and tokens over ``data``.  The router runs in fp32 over every
expert; its top-k weights are renormalised to sum to one when
``moe.norm_topk_prob`` (the default; qwen3-moe, llama4-scout) and otherwise
kept as the softmax gives them, scaled by ``moe.routed_scaling_factor``
(DeepSeek-V2).  Shared experts are added once, on every rank.  Aux losses
(load-balance + z-loss) are returned for the trainer; the ragged layer adds
``expert_tokens``, the choices routed to each held expert.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.dist.sharding import shard
from repro.models.config import ArchConfig
from repro.models.layers import mlp_apply, mlp_defs
from repro.models.param import ParamDef


def moe_defs(cfg: ArchConfig) -> dict:
    m = cfg.moe
    if m.held is not None and m.dispatch != "ragged":
        raise ValueError(f"moe.held needs dispatch 'ragged', not {m.dispatch!r}")
    D, F, E = cfg.d_model, m.d_ff_expert, m.n_held
    defs = {
        "router": ParamDef((D, m.n_experts), ("embed", None), dtype="float32"),
        "wi_gate": ParamDef((E, D, F), ("experts", "embed", "expert_ff")),
        "wi_up": ParamDef((E, D, F), ("experts", "embed", "expert_ff")),
        "wo": ParamDef((E, F, D), ("experts", "expert_ff", "embed")),
    }
    if m.n_shared_experts:
        defs["shared"] = mlp_defs(D, F * m.n_shared_experts, cfg.act)
    return defs


def _capacity(tokens_per_group: int, cfg: ArchConfig) -> int:
    m = cfg.moe
    c = int(m.capacity_factor * tokens_per_group * m.top_k / m.n_experts)
    return max(8, -(-c // 8) * 8)  # round up to 8, floor 8


def _router(p: dict, xt: jax.Array, cfg: ArchConfig):
    """xt (..., D) → top-k weights and experts (fp32) + aux losses.  The
    weights sum to one with ``norm_topk_prob``; otherwise they are the
    softmax's, times ``routed_scaling_factor``."""
    m = cfg.moe
    logits = jnp.einsum("...d,de->...e", xt.astype(jnp.float32), p["router"].astype(jnp.float32))
    probs = jax.nn.softmax(logits, axis=-1)
    top_p, top_i = jax.lax.top_k(probs, m.top_k)
    if m.norm_topk_prob:
        top_p = top_p / jnp.maximum(jnp.sum(top_p, axis=-1, keepdims=True), 1e-9)
    elif m.routed_scaling_factor != 1.0:
        top_p = top_p * m.routed_scaling_factor
    # aux: load-balance (Switch) + router z-loss
    E = m.n_experts
    me = jnp.mean(probs, axis=tuple(range(probs.ndim - 1)))  # mean prob / expert
    ce = jnp.mean(
        jax.nn.one_hot(top_i[..., 0], E, dtype=jnp.float32),
        axis=tuple(range(top_i.ndim - 1)),
    )
    balance = E * jnp.sum(me * ce)
    z = jnp.mean(jax.nn.logsumexp(logits, axis=-1) ** 2)
    return top_p, top_i, {"moe_balance": balance, "moe_zloss": z}


# ---------------------------------------------------------------------------
# einsum (GShard) dispatch
# ---------------------------------------------------------------------------

def _einsum_moe(p: dict, x_grp: jax.Array, cfg: ArchConfig):
    """x_grp (G, S, D): G token groups (sharded over data)."""
    m = cfg.moe
    G, S, D = x_grp.shape
    E, K = m.n_experts, m.top_k
    C = _capacity(S, cfg)

    top_p, top_i, aux = _router(p, x_grp, cfg)  # (G,S,K)
    # GShard priority: expert-choice k=0 of every token claims capacity
    # before any k=1 choice; one (G,S,E,C) accumulator, K small einsums.
    dispatch = jnp.zeros((G, S, E, C), jnp.float32)
    combine = jnp.zeros((G, S, E, C), jnp.float32)
    acc_counts = jnp.zeros((G, E), jnp.float32)
    for k in range(K):
        ohk = jax.nn.one_hot(top_i[..., k], E, dtype=jnp.float32)  # (G,S,E)
        pos = jnp.cumsum(ohk, axis=1) - ohk + acc_counts[:, None, :]
        acc_counts = acc_counts + jnp.sum(ohk, axis=1)
        pos_of = jnp.sum(pos * ohk, axis=-1)  # (G,S)
        keep = (pos_of < C).astype(jnp.float32)
        disp_k = ohk * keep[..., None]
        slot_oh = jax.nn.one_hot(pos_of, C, dtype=jnp.float32) * keep[..., None]
        d = jnp.einsum("gse,gsc->gsec", disp_k, slot_oh)
        dispatch = dispatch + d
        combine = combine + d * top_p[..., k][..., None, None]
    dispatch = shard(dispatch.astype(x_grp.dtype), "batch", None, "experts", None)
    combine = shard(combine, "batch", None, "experts", None)

    ein = jnp.einsum("gsec,gsd->gecd", dispatch, x_grp)  # (G,E,C,D)
    ein = shard(ein, "batch", "experts", None, None)
    h = _expert_ffn(p, ein, cfg)  # (G,E,C,D)
    y = jnp.einsum("gsec,gecd->gsd", combine, h.astype(jnp.float32))
    return y.astype(x_grp.dtype), aux


def _expert_ffn(p: dict, t: jax.Array, cfg: ArchConfig) -> jax.Array:
    """t (..., E, C, D) → (..., E, C, D); per-expert gated MLP."""
    g = jnp.einsum("...ecd,edf->...ecf", t, p["wi_gate"])
    u = jnp.einsum("...ecd,edf->...ecf", t, p["wi_up"])
    g = jax.nn.silu(g) if cfg.act == "swiglu" else jax.nn.gelu(g, approximate=True)
    return jnp.einsum("...ecf,efd->...ecd", g * u, p["wo"])


# ---------------------------------------------------------------------------
# scatter (sort-based) dispatch
# ---------------------------------------------------------------------------

def _scatter_moe(p: dict, xt: jax.Array, cfg: ArchConfig):
    """xt (T, D) flat tokens."""
    m = cfg.moe
    T, D = xt.shape
    E, K = m.n_experts, m.top_k
    C = _capacity(T, cfg)

    top_p, top_i, aux = _router(p, xt, cfg)  # (T,K)
    flat_e = top_i.reshape(-1)  # (T*K,)
    flat_g = top_p.reshape(-1)
    order = jnp.argsort(flat_e)
    se = flat_e[order]
    token_of = order // K
    ones = jnp.ones_like(se, jnp.int32)
    counts = jax.ops.segment_sum(ones, se, num_segments=E)
    offsets = jnp.concatenate([jnp.zeros((1,), jnp.int32), jnp.cumsum(counts)[:-1]])
    slot = jnp.arange(T * K, dtype=jnp.int32) - offsets[se]
    keep = slot < C
    dest = se * C + jnp.clip(slot, 0, C - 1)

    gathered = xt[token_of] * keep[:, None].astype(xt.dtype)
    buf = jnp.zeros((E * C, D), xt.dtype).at[dest].add(gathered)
    buf = shard(buf.reshape(E, C, D), "experts", None, None)
    h = _expert_ffn(p, buf, cfg)  # (E,C,D)
    h = h.reshape(E * C, D)
    contrib = h[dest] * (flat_g[order] * keep)[:, None].astype(h.dtype)
    y = jnp.zeros((T, D), xt.dtype).at[token_of].add(contrib.astype(xt.dtype))
    return y, aux


# ---------------------------------------------------------------------------
# ragged (held experts, grouped matmul) dispatch
# ---------------------------------------------------------------------------

def _ragged_moe(p: dict, xt: jax.Array, cfg: ArchConfig):
    """xt (T, D) flat tokens → the held experts' part of the routed output.

    Every (token, choice) pair is sorted by held expert, pairs for experts
    held elsewhere last; each projection is one ``ragged_dot`` over the
    held experts' groups, so no choice is dropped and no held expert's
    weights are read twice.  Rows past the groups are masked out."""
    m = cfg.moe
    T, D = xt.shape
    K, n = m.top_k, m.n_held
    first = 0 if m.held is None else m.held[0]

    top_p, top_i, aux = _router(p, xt, cfg)  # (T,K) over all n_experts
    local = top_i.reshape(-1) - first
    mine = (local >= 0) & (local < n)
    group = jnp.where(mine, local, n)  # n: held elsewhere
    order = jnp.argsort(group, stable=True)
    sizes = jnp.zeros((n + 1,), jnp.int32).at[group].add(1)[:n]
    xs = xt[order // K]  # (T*K, D), grouped by held expert
    g = jax.lax.ragged_dot(xs, p["wi_gate"], sizes)
    u = jax.lax.ragged_dot(xs, p["wi_up"], sizes)
    g = jax.nn.silu(g) if cfg.act == "swiglu" else jax.nn.gelu(g, approximate=True)
    h = jax.lax.ragged_dot((g * u).astype(xt.dtype), p["wo"], sizes)  # (T*K, D)
    h = h[jnp.argsort(order)].reshape(T, K, D)  # back to (token, choice)
    w = jnp.where(mine.reshape(T, K), top_p, 0.0)
    y = jnp.sum(jnp.where(mine.reshape(T, K, 1), h.astype(jnp.float32), 0.0) * w[..., None], axis=1)
    aux["expert_tokens"] = sizes
    return y.astype(xt.dtype), aux


# ---------------------------------------------------------------------------
# Public layer
# ---------------------------------------------------------------------------

def moe_apply(p: dict, x: jax.Array, cfg: ArchConfig):
    """x (B, L, D) → (y, aux).  Groups tokens for einsum dispatch so capacity
    is local (≤4096 tokens per group), flattens for scatter and ragged
    dispatch; the shared experts are added once."""
    m = cfg.moe
    B, L, D = x.shape
    T = B * L
    if m.dispatch == "einsum":
        g_tokens = min(4096, T)
        G = T // g_tokens
        x_grp = x.reshape(G, g_tokens, D)
        y, aux = _einsum_moe(p, x_grp, cfg)
        y = y.reshape(B, L, D)
    elif m.dispatch in ("scatter", "ragged"):
        fn = _scatter_moe if m.dispatch == "scatter" else _ragged_moe
        y, aux = fn(p, x.reshape(T, D), cfg)
        y = y.reshape(B, L, D)
    else:
        raise ValueError(f"unknown moe dispatch {m.dispatch!r}")
    if m.n_shared_experts:
        y = y + mlp_apply(p["shared"], x, cfg.act)
    return shard(y, "batch", "act_seq", None), aux
