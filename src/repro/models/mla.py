"""Multi-head latent attention (MLA) — minicpm3-4b / DeepSeek-V2 style.

Queries and KV are projected through low-rank latents; only the compressed
latent (c_kv) and the shared RoPE key are cached at decode time — the KV
cache is ~(r_kv + d_rope)/(2·H·Dh) the size of a GQA cache.  Decode uses the
*absorbed* formulation: W_UK folds into the query and W_UV into the output,
so attention runs directly in latent space against the compact cache.

DeepSeek-V2-Lite has no query LoRA (``q_lora_rank`` None: one direct
projection ``wq``) and YaRN rope scaling (``cfg.rope_scaling``): YaRN
frequencies for the rope part of queries and keys, and the softmax scale
multiplied by ``mscale(mscale_all_dim)²``.  The rope columns are rotated in
rotate-half form as stored; the published checkpoint interleaves them, which
a loader undoes with one fixed permutation of those weight columns.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from repro.dist.sharding import shard
from repro.models.config import ArchConfig
from repro.models.layers import apply_rope, rmsnorm, yarn_frequencies, yarn_mscale
from repro.models.param import ParamDef
from repro.models.attention import full_attention, kv_cache_update, layer_of

NEG_INF = -1e30


def mla_defs(cfg: ArchConfig) -> dict:
    m = cfg.mla
    D, H = cfg.d_model, cfg.n_heads
    qk = m.qk_nope_head_dim + m.qk_rope_head_dim
    if m.q_lora_rank is None:
        q = {"wq": ParamDef((D, H, qk), ("embed", "heads", None))}
    else:
        q = {
            "wq_a": ParamDef((D, m.q_lora_rank), ("embed", None)),
            "q_norm": ParamDef((m.q_lora_rank,), (None,), init="zeros"),
            "wq_b": ParamDef((m.q_lora_rank, H, qk), (None, "heads", None)),
        }
    return {
        **q,
        "wkv_a": ParamDef((D, m.kv_lora_rank + m.qk_rope_head_dim), ("embed", None)),
        "kv_norm": ParamDef((m.kv_lora_rank,), (None,), init="zeros"),
        "wk_b": ParamDef((m.kv_lora_rank, H, m.qk_nope_head_dim), (None, "heads", None)),
        "wv_b": ParamDef((m.kv_lora_rank, H, m.v_head_dim), (None, "heads", None)),
        "wo": ParamDef((H, m.v_head_dim, D), ("heads", None, "embed")),
    }


def _rope(x: jax.Array, positions: jax.Array, cfg: ArchConfig) -> jax.Array:
    y = cfg.rope_scaling
    if y is None:
        return apply_rope(x, positions, cfg.rope_theta)
    freqs = yarn_frequencies(x.shape[-1], cfg.rope_theta, y)
    out = apply_rope(x, positions, cfg.rope_theta, freqs=freqs)
    gain = yarn_mscale(y.factor, y.mscale) / yarn_mscale(y.factor, y.mscale_all_dim)
    return out if gain == 1.0 else (out * gain).astype(x.dtype)


def softmax_scale(cfg: ArchConfig) -> float:
    """Scale of the q·k scores: ``1/sqrt(qk head dim)``, times
    ``mscale(mscale_all_dim)²`` under YaRN."""
    m, y = cfg.mla, cfg.rope_scaling
    scale = 1.0 / math.sqrt(m.qk_nope_head_dim + m.qk_rope_head_dim)
    if y is not None and y.mscale_all_dim:
        scale *= yarn_mscale(y.factor, y.mscale_all_dim) ** 2
    return scale


def _project_q(p: dict, x: jax.Array, positions: jax.Array, cfg: ArchConfig):
    m = cfg.mla
    if m.q_lora_rank is None:
        q = jnp.einsum("bld,dhk->blhk", x, p["wq"])
    else:
        cq = rmsnorm(jnp.einsum("bld,dr->blr", x, p["wq_a"]), p["q_norm"], cfg.norm_eps)
        q = jnp.einsum("blr,rhk->blhk", cq, p["wq_b"])
    q_nope, q_rope = jnp.split(q, [m.qk_nope_head_dim], axis=-1)
    return q_nope, _rope(q_rope, positions, cfg)


def _project_kv_latent(p: dict, x: jax.Array, positions: jax.Array, cfg: ArchConfig):
    m = cfg.mla
    kv = jnp.einsum("bld,dr->blr", x, p["wkv_a"])
    c_kv, k_rope = jnp.split(kv, [m.kv_lora_rank], axis=-1)
    c_kv = rmsnorm(c_kv, p["kv_norm"], cfg.norm_eps)
    k_rope = _rope(k_rope[:, :, None, :], positions, cfg)[:, :, 0]
    return c_kv, k_rope  # (B,L,r_kv), (B,L,d_rope)


def mla_apply(
    p: dict,
    x: jax.Array,
    positions: jax.Array,
    cfg: ArchConfig,
    *,
    causal: bool = True,
    want_cache: bool = False,
):
    """Training / prefill: expand latents to per-head K/V, run blockwise attn."""
    m = cfg.mla
    q_nope, q_rope = _project_q(p, x, positions, cfg)
    c_kv, k_rope = _project_kv_latent(p, x, positions, cfg)
    k_nope = jnp.einsum("blr,rhk->blhk", c_kv, p["wk_b"])
    v = jnp.einsum("blr,rhv->blhv", c_kv, p["wv_b"])
    H = cfg.n_heads
    k_rope_h = jnp.broadcast_to(k_rope[:, :, None, :], k_nope.shape[:3] + (m.qk_rope_head_dim,))
    q = jnp.concatenate([q_nope, q_rope], axis=-1)
    k = jnp.concatenate([k_nope, k_rope_h], axis=-1)
    q = shard(q, "batch", None, "heads", None)
    k = shard(k, "batch", None, "heads", None)
    v = shard(v, "batch", None, "heads", None)
    scale = None if cfg.rope_scaling is None else softmax_scale(cfg)
    out = full_attention(q, k, v, cfg, causal=causal, window=cfg.attn_window, scale=scale)
    y = jnp.einsum("blhv,hvd->bld", out, p["wo"])
    y = shard(y, "batch", "act_seq", None)
    if want_cache:
        return y, {"c_kv": c_kv, "k_rope": k_rope}
    return y, None


#: logical axes of both latent cache leaves: sequence-sharded like GQA caches
MLA_CACHE_AXES = ("batch", "kv_seq", None)


def mla_cache_shapes(cfg: ArchConfig, batch: int, max_seq: int) -> dict:
    m = cfg.mla
    return {
        "c_kv": ((batch, max_seq, m.kv_lora_rank), MLA_CACHE_AXES),
        "k_rope": ((batch, max_seq, m.qk_rope_head_dim), MLA_CACHE_AXES),
    }


def mla_decode_step(p: dict, x: jax.Array, cache: dict, pos: jax.Array, cfg: ArchConfig,
                    layer=None):
    """Absorbed-matmul decode.  x (B,1,D); cache {'c_kv': (B,S,r), 'k_rope':
    (B,S,d_r)}, or the stacks over every layer with ``layer`` this one's."""
    m = cfg.mla
    B = x.shape[0]
    positions = jnp.broadcast_to(jnp.asarray(pos, jnp.int32).reshape(-1, 1), (B, 1))
    q_nope, q_rope = _project_q(p, x, positions, cfg)  # (B,1,H,·)
    c_kv_new, k_rope_new = _project_kv_latent(p, x, positions, cfg)

    new_cache = {
        "c_kv": kv_cache_update(cache["c_kv"], c_kv_new, pos, MLA_CACHE_AXES, layer),
        "k_rope": kv_cache_update(cache["k_rope"], k_rope_new, pos, MLA_CACHE_AXES, layer),
    }
    c_cache = layer_of(new_cache["c_kv"], layer)
    r_cache = layer_of(new_cache["k_rope"], layer)
    S = c_cache.shape[1]

    # absorb W_UK into q: q_lat (B,H,r_kv)
    q_lat = jnp.einsum("bhk,rhk->bhr", q_nope[:, 0], p["wk_b"])
    s = jnp.einsum("bhr,bsr->bhs", q_lat.astype(jnp.float32), c_cache.astype(jnp.float32))
    s = s + jnp.einsum(
        "bhk,bsk->bhs", q_rope[:, 0].astype(jnp.float32), r_cache.astype(jnp.float32)
    )
    s = s * softmax_scale(cfg)
    pos_b = jnp.broadcast_to(jnp.asarray(pos), (B,))
    valid = jnp.arange(S)[None, :] <= pos_b[:, None]
    s = jnp.where(valid[:, None, :], s, NEG_INF)
    w = jax.nn.softmax(s, axis=-1)
    lat_out = jnp.einsum("bhs,bsr->bhr", w, c_cache.astype(jnp.float32))
    v_out = jnp.einsum("bhr,rhv->bhv", lat_out.astype(x.dtype), p["wv_b"])
    y = jnp.einsum("bhv,hvd->bd", v_out, p["wo"])[:, None]
    return y, new_cache
