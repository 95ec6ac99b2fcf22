"""Attention family: GQA/MQA/MHA, sliding-window, blockwise (flash-style)
training/prefill attention, sequence-sharded decode attention, KV caches.

Memory discipline: above ``cfg.attn_blockwise_min_seq`` the O(S²) score
matrix is never materialized — a lax.scan over KV blocks carries online
softmax statistics (the FlashAttention recurrence in pure JAX).  This is the
*reference* path; ``repro/kernels/flash_attention`` is the Pallas TPU
version of the same tiling (VMEM-resident blocks), validated against it.

Two blockwise modes (a §Perf lever):

* ``masked`` — every (q-block, kv-block) pair is computed and masked: simple,
  fully vectorized, but causal masking wastes ~2× FLOPs.
* ``tri``    — per-q-block KV ranges honour causality/window structurally:
  ~half the FLOPs for causal, bounded work for sliding windows.
"""
from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp

from repro.dist.sharding import shard
from repro.models.config import ArchConfig
from repro.models.layers import apply_rope
from repro.models.param import ParamDef

NEG_INF = -1e30
# unroll threshold for the flash kv-block loops (see _make_flash docstring)
_UNROLL_MAX = 64


# ---------------------------------------------------------------------------
# Params
# ---------------------------------------------------------------------------

def attn_defs(cfg: ArchConfig) -> dict:
    D, H, KH, Dh = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    out = {
        "wq": ParamDef((D, H, Dh), ("embed", "heads", "head_dim")),
        "wk": ParamDef((D, KH, Dh), ("embed", "kv_heads", "head_dim")),
        "wv": ParamDef((D, KH, Dh), ("embed", "kv_heads", "head_dim")),
        "wo": ParamDef((H, Dh, D), ("heads", "head_dim", "embed")),
    }
    if cfg.qkv_bias:
        out["bq"] = ParamDef((H, Dh), ("heads", "head_dim"), init="zeros")
        out["bk"] = ParamDef((KH, Dh), ("kv_heads", "head_dim"), init="zeros")
        out["bv"] = ParamDef((KH, Dh), ("kv_heads", "head_dim"), init="zeros")
    if cfg.qk_norm:
        out["q_norm"] = ParamDef((Dh,), (None,), init="zeros")
        out["k_norm"] = ParamDef((Dh,), (None,), init="zeros")
    return out


def _qk_norm(x: jax.Array, scale: jax.Array, eps: float) -> jax.Array:
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    return (xf * jax.lax.rsqrt(var + eps) * (1.0 + scale.astype(jnp.float32))).astype(x.dtype)


def qkv_project(p: dict, x: jax.Array, positions: jax.Array, cfg: ArchConfig):
    """x (B, L, D) → q (B, L, H, Dh), k/v (B, L, KH, Dh), RoPE applied."""
    q = jnp.einsum("bld,dhk->blhk", x, p["wq"])
    k = jnp.einsum("bld,dhk->blhk", x, p["wk"])
    v = jnp.einsum("bld,dhk->blhk", x, p["wv"])
    if cfg.qkv_bias:
        q = q + p["bq"]
        k = k + p["bk"]
        v = v + p["bv"]
    if cfg.qk_norm:
        q = _qk_norm(q, p["q_norm"], cfg.norm_eps)
        k = _qk_norm(k, p["k_norm"], cfg.norm_eps)
    q = apply_rope(q, positions, cfg.rope_theta)
    k = apply_rope(k, positions, cfg.rope_theta)
    q = shard(q, "batch", None, "heads", None)
    k = shard(k, "batch", None, "kv_heads", None)
    v = shard(v, "batch", None, "kv_heads", None)
    return q, k, v


# ---------------------------------------------------------------------------
# Reference (materializing) attention — small sequences & test oracle
# ---------------------------------------------------------------------------

def reference_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = True,
    window: Optional[int] = None,
    q_offset: int = 0,
    scale: Optional[float] = None,
) -> jax.Array:
    """``scale`` multiplies the scores in place of ``1/sqrt(head_dim)``."""
    B, Lq, H, Dh = q.shape
    _, Lk, KH, _ = k.shape
    G = H // KH
    if G > 1:
        # expand KV to query heads: local per-shard once heads are sharded,
        # and keeps the score tensor cleanly head-sharded (no (KH, G) split
        # that defeats the SPMD partitioner when KH < mesh model size)
        k = jnp.repeat(k, G, axis=2)
        v = jnp.repeat(v, G, axis=2)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k, preferred_element_type=jnp.float32)
    s = s / math.sqrt(Dh) if scale is None else s * scale
    qpos = q_offset + jnp.arange(Lq)
    kpos = jnp.arange(Lk)
    mask = jnp.ones((Lq, Lk), bool)
    if causal:
        mask &= qpos[:, None] >= kpos[None, :]
    if window is not None:
        mask &= qpos[:, None] - kpos[None, :] < window
    s = jnp.where(mask, s, NEG_INF)
    w = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", w.astype(v.dtype), v)


# ---------------------------------------------------------------------------
# Blockwise (flash-style) attention
# ---------------------------------------------------------------------------

def _block_ranges(nq: int, bq: int, bk: int, causal: bool, window: Optional[int]):
    """Static per-q-block [lo, hi) KV-block ranges for ``tri`` mode."""
    rng = []
    for qi in range(nq):
        q_lo, q_hi = qi * bq, (qi + 1) * bq - 1
        hi_blk = (q_hi // bk) + 1 if causal else None
        lo_blk = 0
        if window is not None:
            lo_blk = max(0, (q_lo - window + 1) // bk)
        rng.append((lo_blk, hi_blk))
    return rng


import functools


@functools.lru_cache(maxsize=64)
def _make_flash(causal: bool, window: Optional[int], block_kv: int, q_offset: int, mode: str, unroll: bool = False,
                sm_scale: Optional[float] = None):
    """Factory for a custom-VJP blockwise attention with the FlashAttention-2
    backward: residuals are only (q, k, v, out, lse) — scores are recomputed
    per KV block in the backward scan, so memory back through the layer-remat
    boundary is O(L), not O(L²).  This is the pure-JAX mirror of
    ``kernels/flash_attention``."""

    def _mask(qpos, kpos):
        msk = jnp.ones((qpos.shape[0], kpos.shape[0]), bool)
        if causal:
            msk &= qpos[:, None] >= kpos[None, :]
        if window is not None:
            msk &= qpos[:, None] - kpos[None, :] < window
        return msk

    def _fwd_scan(q, k, v):
        B, Lq, H, Dh = q.shape
        _, Lk, KH, _ = k.shape
        Dv = v.shape[-1]
        G = H // KH
        if G > 1:  # expand KV to query heads (see reference_attention note)
            k = jnp.repeat(k, G, axis=2)
            v = jnp.repeat(v, G, axis=2)
        bk = min(block_kv, Lk)
        nk = Lk // bk
        scale = 1.0 / math.sqrt(Dh) if sm_scale is None else sm_scale
        kb = k.reshape(B, nk, bk, H, Dh).swapaxes(0, 1)
        vb = v.reshape(B, nk, bk, H, Dv).swapaxes(0, 1)
        qpos = q_offset + jnp.arange(Lq)

        def kv_step(carry, inp):
            m, l, acc = carry
            ki, k_blk, v_blk = inp
            s = jnp.einsum(
                "bqhd,bkhd->bhqk", q, k_blk, preferred_element_type=jnp.float32
            ) * scale
            kpos = ki * bk + jnp.arange(bk)
            s = jnp.where(_mask(qpos, kpos), s, NEG_INF)
            m_new = jnp.maximum(m, jnp.max(s, axis=-1))
            p = jnp.exp(s - m_new[..., None])
            corr = jnp.exp(m - m_new)
            l = l * corr + jnp.sum(p, axis=-1)
            pv = jnp.einsum(
                "bhqk,bkhd->bhqd", p.astype(v_blk.dtype), v_blk
            ).astype(jnp.float32)
            acc = acc * corr[..., None] + pv
            return (m_new, l, acc), None

        m0 = jnp.full((B, H, Lq), NEG_INF, jnp.float32)
        l0 = jnp.zeros((B, H, Lq), jnp.float32)
        a0 = jnp.zeros((B, H, Lq, Dv), jnp.float32)
        if unroll and nk <= _UNROLL_MAX:
            # probe mode: XLA cost_analysis sees every block (lax.scan bodies
            # are counted once); deployable configs use the scan (memory)
            carry = (m0, l0, a0)
            for ki in range(nk):
                carry, _ = kv_step(carry, (jnp.int32(ki), kb[ki], vb[ki]))
            m, l, acc = carry
        else:
            (m, l, acc), _ = jax.lax.scan(kv_step, (m0, l0, a0), (jnp.arange(nk), kb, vb))
        l_safe = jnp.maximum(l, 1e-30)
        out = (acc / l_safe[..., None]).transpose(0, 2, 1, 3)  # (B,Lq,H,Dv)
        lse = m + jnp.log(l_safe)  # (B,H,Lq)
        return out.astype(q.dtype), lse

    @jax.custom_vjp
    def flash(q, k, v):
        return _fwd_scan(q, k, v)[0]

    def fwd(q, k, v):
        out, lse = _fwd_scan(q, k, v)
        return out, (q, k, v, out, lse)

    def bwd(res, dout):
        q, k, v, out, lse = res
        B, Lq, H, Dh = q.shape
        _, Lk, KH, _ = k.shape
        Dv = v.shape[-1]
        G = H // KH
        if G > 1:
            k = jnp.repeat(k, G, axis=2)
            v = jnp.repeat(v, G, axis=2)
        bk = min(block_kv, Lk)
        nk = Lk // bk
        scale = 1.0 / math.sqrt(Dh) if sm_scale is None else sm_scale
        kb = k.reshape(B, nk, bk, H, Dh).swapaxes(0, 1)
        vb = v.reshape(B, nk, bk, H, Dv).swapaxes(0, 1)
        do = dout.transpose(0, 2, 1, 3).astype(jnp.float32)  # (B,H,Lq,Dv)
        og = out.transpose(0, 2, 1, 3).astype(jnp.float32)
        Dvec = jnp.sum(do * og, axis=-1)  # (B,H,Lq)
        qpos = q_offset + jnp.arange(Lq)

        def kv_step(dq_acc, inp):
            ki, k_blk, v_blk = inp
            s = jnp.einsum(
                "bqhd,bkhd->bhqk", q, k_blk, preferred_element_type=jnp.float32
            ) * scale
            kpos = ki * bk + jnp.arange(bk)
            s = jnp.where(_mask(qpos, kpos), s, NEG_INF)
            p = jnp.exp(s - lse[..., None])  # recomputed probabilities
            dv_b = jnp.einsum("bhqk,bhqv->bkhv", p, do)
            dp = jnp.einsum("bhqv,bkhv->bhqk", do, v_blk.astype(jnp.float32))
            ds = p * (dp - Dvec[..., None]) * scale
            dq_acc = dq_acc + jnp.einsum("bhqk,bkhd->bqhd", ds, k_blk.astype(jnp.float32))
            dk_b = jnp.einsum("bhqk,bqhd->bkhd", ds, q.astype(jnp.float32))
            return dq_acc, (dk_b, dv_b)

        dq0 = jnp.zeros((B, Lq, H, Dh), jnp.float32)
        if unroll and nk <= _UNROLL_MAX:
            dq = dq0
            dk_list, dv_list = [], []
            for ki in range(nk):
                dq, (dk_b, dv_b) = kv_step(dq, (jnp.int32(ki), kb[ki], vb[ki]))
                dk_list.append(dk_b)
                dv_list.append(dv_b)
            dks = jnp.stack(dk_list)
            dvs = jnp.stack(dv_list)
        else:
            dq, (dks, dvs) = jax.lax.scan(kv_step, dq0, (jnp.arange(nk), kb, vb))
        dq = dq.astype(q.dtype)
        dk = dks.swapaxes(0, 1).reshape(B, Lk, H, Dh)
        dv = dvs.swapaxes(0, 1).reshape(B, Lk, H, Dv)
        if G > 1:  # fold expanded-head grads back onto the KV heads
            dk = dk.reshape(B, Lk, KH, G, Dh).sum(axis=3)
            dv = dv.reshape(B, Lk, KH, G, Dv).sum(axis=3)
        return dq, dk.astype(res[1].dtype), dv.astype(res[2].dtype)

    flash.defvjp(fwd, bwd)
    return flash


def blockwise_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = True,
    window: Optional[int] = None,
    block_q: int = 512,
    block_kv: int = 1024,
    q_offset: int = 0,
    mode: str = "masked",
    unroll: bool = False,
    scale: Optional[float] = None,
) -> jax.Array:
    """Online-softmax attention; never materializes (Lq, Lk) scores."""
    if mode == "masked":
        fn = _make_flash(causal, window, block_kv, q_offset, mode, unroll, scale)
        return fn(q, k, v)
    B, Lq, H, Dh = q.shape
    _, Lk, KH, _ = k.shape
    Dv = v.shape[-1]
    if H != KH:  # expand KV to query heads (see reference_attention note)
        k = jnp.repeat(k, H // KH, axis=2)
        v = jnp.repeat(v, H // KH, axis=2)
        KH = H
    G = 1
    bq = min(block_q, Lq)
    bk = min(block_kv, Lk)
    assert Lq % bq == 0 and Lk % bk == 0, (Lq, bq, Lk, bk)
    nq, nk = Lq // bq, Lk // bk
    scale = 1.0 / math.sqrt(Dh) if scale is None else scale

    qb = q.reshape(B, nq, bq, KH, G, Dh)
    kb = k.reshape(B, nk, bk, KH, Dh)
    vb = v.reshape(B, nk, bk, KH, Dv)

    def step(carry, inp, qi_base, q_blk):
        m, l, acc = carry
        ki, k_blk, v_blk = inp
        s = jnp.einsum("bqhgd,bkhd->bhgqk", q_blk, k_blk, preferred_element_type=jnp.float32) * scale
        qpos = qi_base + jnp.arange(bq)
        kpos = ki * bk + jnp.arange(bk)
        msk = jnp.ones((bq, bk), bool)
        if causal:
            msk &= qpos[:, None] >= kpos[None, :]
        if window is not None:
            msk &= qpos[:, None] - kpos[None, :] < window
        s = jnp.where(msk, s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[..., None])
        corr = jnp.exp(m - m_new)
        l = l * corr + jnp.sum(p, axis=-1)
        pv = jnp.einsum("bhgqk,bkhd->bhgqd", p.astype(v_blk.dtype), v_blk).astype(jnp.float32)
        acc = acc * corr[..., None] + pv
        return (m_new, l, acc), None

    def run_qblock(qi_idx, q_blk, k_sel, v_sel, n_sel, k_idx0):
        qi_base = q_offset + qi_idx * bq
        m0 = jnp.full((B, KH, G, bq), NEG_INF, jnp.float32)
        l0 = jnp.zeros((B, KH, G, bq), jnp.float32)
        a0 = jnp.zeros((B, KH, G, bq, Dv), jnp.float32)
        idxs = k_idx0 + jnp.arange(n_sel)
        (m, l, acc), _ = jax.lax.scan(
            lambda c, i: step(c, i, qi_base, q_blk),
            (m0, l0, a0),
            (idxs, k_sel.swapaxes(0, 1), v_sel.swapaxes(0, 1)),
        )
        out = acc / jnp.maximum(l, 1e-30)[..., None]
        return out  # (B, KH, G, bq, Dh)

    # 'tri': python loop over q blocks with static KV ranges
    ranges = _block_ranges(nq, bq, bk, causal, window)
    blocks = []
    for qi in range(nq):
        lo, hi = ranges[qi]
        hi = nk if hi is None else min(hi, nk)
        k_sel = kb[:, lo:hi]
        v_sel = vb[:, lo:hi]
        o = run_qblock(qi, qb[:, qi], k_sel, v_sel, hi - lo, lo)
        blocks.append(o)
    out = jnp.stack(blocks, axis=0)
    out = out.transpose(1, 0, 4, 2, 3, 5).reshape(B, Lq, H, Dv)
    return out.astype(q.dtype)


def full_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    cfg: ArchConfig,
    *,
    causal: bool,
    window: Optional[int] = None,
    q_offset: int = 0,
    mode: Optional[str] = None,
    scale: Optional[float] = None,
) -> jax.Array:
    """Dispatch: reference below the blockwise threshold, blockwise above;
    Pallas kernel when enabled on TPU (kernels/flash_attention/ops.py) and
    the scores take the default ``1/sqrt(head_dim)`` scale."""
    Lq = q.shape[1]
    if cfg.use_pallas and scale is None:
        from repro.kernels.flash_attention import ops as fa_ops

        if fa_ops.available() and Lq >= cfg.attn_blockwise_min_seq:
            return fa_ops.flash_attention(
                q, k, v, causal=causal, window=window, q_offset=q_offset
            )
    if Lq < cfg.attn_blockwise_min_seq:
        return reference_attention(
            q, k, v, causal=causal, window=window, q_offset=q_offset, scale=scale
        )
    mode = mode or cfg.attn_mode
    if mode == "auto":
        # tri needs head-sharded attention (EXPERIMENTS.md §Perf bonus round:
        # replicated heads make the per-q-block buffers explode); eligible
        # when the query heads divide the mesh model axis (or no mesh)
        from repro.dist.sharding import current_mesh

        mesh = current_mesh()
        model_size = mesh.shape.get("model", 1) if mesh is not None else 1
        mode = "tri" if (causal and q.shape[2] % max(model_size, 1) == 0) else "masked"
    return blockwise_attention(
        q,
        k,
        v,
        causal=causal,
        window=window,
        block_q=cfg.attn_block_q,
        block_kv=cfg.attn_block_kv,
        q_offset=q_offset,
        mode=mode,
        unroll=cfg.probe_unroll,
        scale=scale,
    )


# ---------------------------------------------------------------------------
# KV cache
# ---------------------------------------------------------------------------

def kv_cache_shape(cfg: ArchConfig, batch: int, max_seq: int) -> tuple[int, ...]:
    W = cfg.attn_window
    S = min(max_seq, W) if W is not None else max_seq
    return (batch, S, cfg.n_kv_heads, cfg.head_dim)


def kv_cache_axes(cfg: ArchConfig = None) -> tuple:
    if cfg is not None and cfg.kv_shard == "heads":
        return ("batch", None, "kv_heads", None)
    return ("batch", "kv_seq", "kv_heads", None)


def seq_sharded(axes: tuple) -> bool:
    """Whether a decode cache laid out as ``axes`` splits its sequence
    dimension over more than one device of the active mesh."""
    from repro.dist.sharding import current_mesh

    mesh = current_mesh()
    return axes[1] == "kv_seq" and mesh is not None and mesh.shape.get("model", 1) > 1


def layer_of(cache: jax.Array, layer) -> jax.Array:
    """Layer ``layer`` of a cache stack, or the cache itself for None."""
    if layer is None:
        return cache
    return jax.lax.dynamic_index_in_dim(cache, layer, keepdims=False)


def kv_cache_update(
    cache: jax.Array, new: jax.Array, slot: jax.Array, axes: tuple, layer=None
) -> jax.Array:
    """Write each sequence's new row ``new[b, 0]`` at position ``slot[b]``
    (``slot`` a scalar or a (B,) vector) of a cache (B, S, ...) laid out as
    ``axes``, or of layer ``layer`` of a stack (L, B, S, ...), in place.

    The B rows are one scatter into the cache, so a step reads and writes
    only its own rows; a slot outside [0, S) writes nothing.  A cache whose
    sequence dimension is split over devices takes the select
    cache·(1−δ) + new·δ over its layer instead, which every shard computes
    locally.
    """
    B = new.shape[0]
    slot = jnp.broadcast_to(jnp.asarray(slot, jnp.int32), (B,))
    rows = new[:, 0].astype(cache.dtype)
    if not seq_sharded(axes):
        idx = (jnp.arange(B), slot) if layer is None else (layer, jnp.arange(B), slot)
        return cache.at[idx].set(rows, mode="drop", indices_are_sorted=True,
                                 unique_indices=True, wrap_negative_indices=False)
    c = layer_of(cache, layer)
    oh = (jnp.arange(c.shape[1])[None, :] == slot[:, None]).astype(c.dtype)
    oh = oh.reshape(oh.shape + (1,) * (c.ndim - 2))
    c = c * (1 - oh) + rows[:, None] * oh
    return c if layer is None else jax.lax.dynamic_update_index_in_dim(cache, c, layer, 0)


def decode_attention(
    q: jax.Array,
    k_cache: jax.Array,
    v_cache: jax.Array,
    pos: jax.Array,
    *,
    window: Optional[int] = None,
) -> jax.Array:
    """One-token attention against an (optionally ring-buffered) cache.

    q: (B, 1, H, Dh); caches (B, S, KH, Dh) — S is sequence-sharded on the
    ``model`` axis, so the softmax/weighted-sum reductions over S become
    cross-shard collectives (flash-decoding-style combine, inserted by SPMD).
    pos: scalar int32 — tokens processed so far (the new token's position).
    """
    B, _, H, Dh = q.shape
    S, KH = k_cache.shape[1], k_cache.shape[2]
    G = H // KH
    qg = q.reshape(B, KH, G, Dh)
    s = jnp.einsum("bhgd,bshd->bhgs", qg, k_cache, preferred_element_type=jnp.float32)
    s = s / math.sqrt(Dh)
    slots = jnp.arange(S)
    pos_b = jnp.broadcast_to(jnp.asarray(pos), (B,))  # scalar or (B,)
    if window is None:
        valid = slots[None, :] <= pos_b[:, None]
    else:
        # ring buffer: slot i holds absolute position p ≡ i (mod S) with
        # p in (pos−S, pos]; everything stored is within the window by
        # construction once S == window
        valid = slots[None, :] < jnp.minimum(pos_b + 1, S)[:, None]
    s = jnp.where(valid[:, None, None, :], s, NEG_INF)
    w = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhgs,bshd->bhgd", w.astype(v_cache.dtype), v_cache)
    return out.reshape(B, 1, H, Dh)


def attention_decode_step(
    p: dict,
    x: jax.Array,
    cache: dict,
    pos: jax.Array,
    cfg: ArchConfig,
    layer=None,
):
    """x: (B, 1, D) new-token activations; cache: {'k','v'} ring or full,
    or the stacks over every layer with ``layer`` the index of this one.

    Returns (out (B,1,D), new_cache).
    """
    positions = jnp.broadcast_to(
        jnp.asarray(pos, jnp.int32).reshape(-1, 1), (x.shape[0], 1)
    )
    q, k, v = qkv_project(p, x, positions, cfg)
    S = cache["k"].shape[-3]
    slot = pos % S if cfg.attn_window is not None else pos
    axes = kv_cache_axes(cfg)
    k_cache = kv_cache_update(cache["k"], k, slot, axes, layer)
    v_cache = kv_cache_update(cache["v"], v, slot, axes, layer)
    out = decode_attention(q, layer_of(k_cache, layer), layer_of(v_cache, layer), pos,
                           window=cfg.attn_window)
    out = jnp.einsum("blhk,hkd->bld", out, p["wo"])
    return out, {"k": k_cache, "v": v_cache}


def attention_apply(
    p: dict,
    x: jax.Array,
    positions: jax.Array,
    cfg: ArchConfig,
    *,
    causal: bool = True,
    want_cache: bool = False,
):
    """Training / prefill attention over the full sequence."""
    q, k, v = qkv_project(p, x, positions, cfg)
    out = full_attention(q, k, v, cfg, causal=causal, window=cfg.attn_window)
    y = jnp.einsum("blhk,hkd->bld", out, p["wo"])
    y = shard(y, "batch", "act_seq", None)
    if want_cache:
        return y, {"k": k, "v": v}
    return y, None
