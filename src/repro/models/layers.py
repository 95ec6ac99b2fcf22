"""Common layers: norms, rotary embeddings, gated MLPs, embeddings, losses.

Pure functions over explicit param dicts; logical-axis sharding constraints
via :func:`repro.dist.sharding.shard` (identity off-mesh).
"""
from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.dist.sharding import shard
from repro.models.config import ArchConfig, YarnConfig
from repro.models.param import ParamDef


# ---------------------------------------------------------------------------
# Norms
# ---------------------------------------------------------------------------

def rmsnorm(x: jax.Array, scale: jax.Array, eps: float = 1e-6) -> jax.Array:
    dt = x.dtype
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    y = xf * jax.lax.rsqrt(var + eps)
    return (y * (1.0 + scale.astype(jnp.float32))).astype(dt)


def rmsnorm_def(d: int) -> ParamDef:
    # stored as offset-from-one (gemma convention); init zeros → scale 1
    return ParamDef((d,), (None,), init="zeros")


# ---------------------------------------------------------------------------
# Rotary position embeddings
# ---------------------------------------------------------------------------

def rope_frequencies(head_dim: int, theta: float) -> jax.Array:
    half = head_dim // 2
    return 1.0 / (theta ** (jnp.arange(0, half, dtype=jnp.float32) / half))


def yarn_mscale(factor: float, mscale: float) -> float:
    """YaRN's attention temperature factor, ``0.1·mscale·ln(factor) + 1``."""
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def yarn_frequencies(head_dim: int, theta: float, y: YarnConfig) -> np.ndarray:
    """YaRN inverse frequencies (DeepSeek-V2's ``DeepseekV2YarnRotaryEmbedding``):
    the rotations that turn fewer than ``beta_slow`` times over the original
    context are interpolated (divided by ``factor``), those that turn more
    than ``beta_fast`` times are kept, with a linear ramp between."""
    pos = np.arange(0, head_dim, 2, dtype=np.float32) / np.float32(head_dim)
    extra = 1.0 / np.float32(theta) ** pos
    inter = 1.0 / (np.float32(y.factor) * np.float32(theta) ** pos)

    def dim_of(rotations):
        return head_dim * math.log(
            y.original_max_position_embeddings / (rotations * 2 * math.pi)
        ) / (2 * math.log(theta))

    low = max(math.floor(dim_of(y.beta_fast)), 0)
    high = min(math.ceil(dim_of(y.beta_slow)), head_dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(head_dim // 2, dtype=np.float32) - low) / (high - low), 0, 1)
    keep = 1.0 - ramp  # 1: extrapolated (high frequency), 0: interpolated
    return (inter * (1 - keep) + extra * keep).astype(np.float32)


def apply_rope(
    x: jax.Array, positions: jax.Array, theta: float = 10000.0, *, freqs=None
) -> jax.Array:
    """x: (..., L, n, head_dim); positions: (..., L) int32.  ``freqs``
    (head_dim/2,) overrides the plain ``theta`` frequencies (YaRN)."""
    head_dim = x.shape[-1]
    if freqs is None:
        freqs = rope_frequencies(head_dim, theta)  # (half,)
    angles = positions[..., None].astype(jnp.float32) * freqs  # (..., L, half)
    sin = jnp.sin(angles)[..., None, :]
    cos = jnp.cos(angles)[..., None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# Gated MLP (SwiGLU / GeGLU / GELU)
# ---------------------------------------------------------------------------

def mlp_defs(d_model: int, d_ff: int, act: str) -> dict:
    if act in ("swiglu", "geglu"):
        return {
            "wi_gate": ParamDef((d_model, d_ff), ("embed", "ff")),
            "wi_up": ParamDef((d_model, d_ff), ("embed", "ff")),
            "wo": ParamDef((d_ff, d_model), ("ff", "embed")),
        }
    return {
        "wi": ParamDef((d_model, d_ff), ("embed", "ff")),
        "wo": ParamDef((d_ff, d_model), ("ff", "embed")),
    }


def mlp_apply(p: dict, x: jax.Array, act: str) -> jax.Array:
    if act in ("swiglu", "geglu"):
        g = jnp.einsum("...d,df->...f", x, p["wi_gate"])
        u = jnp.einsum("...d,df->...f", x, p["wi_up"])
        g = jax.nn.silu(g) if act == "swiglu" else jax.nn.gelu(g, approximate=True)
        h = g * u
    else:
        h = jax.nn.gelu(jnp.einsum("...d,df->...f", x, p["wi"]), approximate=True)
    h = shard(h, "batch", None, "ff") if h.ndim == 3 else h
    return jnp.einsum("...f,fd->...d", h, p["wo"])


# ---------------------------------------------------------------------------
# Embedding + logits
# ---------------------------------------------------------------------------

def embed_defs(cfg: ArchConfig) -> dict:
    """Embedding (+ untied head) over the PADDED vocab (sharding-friendly)."""
    v, d = cfg.padded_vocab, cfg.d_model
    out = {"embedding": ParamDef((v, d), ("vocab", "embed"), init="embed")}
    if not cfg.tie_embeddings:
        out["unembed"] = ParamDef((d, v), ("embed", "vocab"))
    return out


import functools


@functools.lru_cache(maxsize=32)
def _make_embed_lookup(shape: tuple, dtype_name: str):
    """Embedding gather with a sharded-scatter backward.

    The default gather-transpose scatter-add materializes the FULL table
    gradient replicated per device (≈5 GB fp32 for a 150k×8k table).
    Constraining the zeros operand and the result to the table's logical
    sharding keeps the scatter partitioned over (vocab, embed)."""

    @jax.custom_vjp
    def lookup(table, tokens):
        return table[tokens]

    def fwd(table, tokens):
        return table[tokens], tokens

    def bwd(tokens, dx):
        zeros = shard(jnp.zeros(shape, dx.dtype), "vocab", "embed")
        dE = zeros.at[tokens.reshape(-1)].add(dx.reshape(-1, shape[-1]))
        dE = shard(dE, "vocab", "embed")
        return dE.astype(jnp.dtype(dtype_name)), None

    lookup.defvjp(fwd, bwd)
    return lookup


def embed_apply(p: dict, tokens: jax.Array, cfg: ArchConfig) -> jax.Array:
    table = p["embedding"]
    x = _make_embed_lookup(tuple(table.shape), str(table.dtype))(table, tokens)
    if cfg.embed_scale:
        x = x * jnp.asarray(cfg.d_model**0.5, x.dtype)
    return shard(x.astype(jnp.dtype(cfg.dtype)), "batch", "act_seq", None)


def logits_apply(p: dict, x: jax.Array, cfg: ArchConfig) -> jax.Array:
    if "unembed" in p:
        logits = jnp.einsum("...d,dv->...v", x, p["unembed"])
    else:
        logits = jnp.einsum("...d,vd->...v", x, p["embedding"])
    if cfg.logit_softcap:
        c = cfg.logit_softcap
        logits = jnp.tanh(logits / c) * c
    if cfg.padded_vocab != cfg.vocab:  # mask padding classes out of softmax
        pad_mask = jnp.arange(cfg.padded_vocab) < cfg.vocab
        logits = jnp.where(pad_mask, logits, -1e30)
    return logits


# ---------------------------------------------------------------------------
# Losses
# ---------------------------------------------------------------------------

def softmax_xent(logits: jax.Array, labels: jax.Array, mask: Optional[jax.Array] = None):
    """Mean cross-entropy over (optionally masked) positions; fp32 math."""
    lg = logits.astype(jnp.float32)
    lse = jax.nn.logsumexp(lg, axis=-1)
    gold = jnp.take_along_axis(lg, labels[..., None], axis=-1)[..., 0]
    nll = lse - gold
    if mask is not None:
        m = mask.astype(jnp.float32)
        return jnp.sum(nll * m) / jnp.maximum(jnp.sum(m), 1.0)
    return jnp.mean(nll)


def chunked_softmax_xent(
    x: jax.Array,
    labels: jax.Array,
    p_embed: dict,
    cfg: ArchConfig,
    mask: Optional[jax.Array] = None,
    chunk: int = 1024,
):
    """Cross-entropy without materializing the full (T, vocab) logits tensor:
    scan over sequence chunks (memory-term lever for 150k–256k vocabs)."""
    B, L, D = x.shape
    n = L // chunk
    assert n * chunk == L, f"seq {L} not divisible by logits chunk {chunk}"
    xs = x.reshape(B, n, chunk, D).swapaxes(0, 1)  # (n, B, chunk, D)
    ls = labels.reshape(B, n, chunk).swapaxes(0, 1)
    ms = None if mask is None else mask.reshape(B, n, chunk).swapaxes(0, 1)

    @jax.checkpoint  # recompute chunk logits in bwd: never stack them
    def chunk_nll(xc, lc, mc):
        logits = logits_apply(p_embed, xc, cfg).astype(jnp.float32)
        lse = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, lc[..., None], axis=-1)[..., 0]
        return jnp.sum((lse - gold) * mc), jnp.sum(mc)

    if cfg.probe_unroll:
        # probe mode: unrolled chunks are fully visible to cost_analysis
        tot = jnp.float32(0.0)
        cnt = jnp.float32(0.0)
        for i in range(n):
            mc = jnp.ones(ls[i].shape, jnp.float32) if ms is None else ms[i].astype(jnp.float32)
            t, c = chunk_nll(xs[i], ls[i], mc)
            tot, cnt = tot + t, cnt + c
        return tot / jnp.maximum(cnt, 1.0)

    def body(carry, inp):
        if ms is None:
            xc, lc = inp
            mc = jnp.ones(lc.shape, jnp.float32)
        else:
            xc, lc, mc = inp
            mc = mc.astype(jnp.float32)
        t, c = chunk_nll(xc, lc, mc)
        return (carry[0] + t, carry[1] + c), None

    inps = (xs, ls) if ms is None else (xs, ls, ms)
    (tot, cnt), _ = jax.lax.scan(body, (jnp.float32(0.0), jnp.float32(0.0)), inps)
    return tot / jnp.maximum(cnt, 1.0)
