"""Decode attention (flash-decoding) — Pallas TPU kernel.

One new token per sequence attends to a long KV cache.  Grid:
(B·KH, n_splits) — the cache is split along the sequence into ``bs``-slot
blocks; each iteration accumulates masked partial (m, l, acc) into VMEM
scratch (the split-K structure of FlashDecoding; on the sequential TPU grid
the combine is the same online-softmax update, and fully-invalid blocks
beyond ``pos`` are skipped with ``pl.when``).

The current position arrives via scalar prefetch (SMEM) so block validity
is known before the tile is touched.

VMEM per program (bs=512, Dh=128, G≤8): k/v tiles 2×512×128×2 = 256 KiB,
scores G×512×4 ≤ 16 KiB, acc G×128×4 = 4 KiB — trivially resident.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _decode_kernel(
    pos_ref,  # scalar prefetch (SMEM): (1,) int32
    q_ref, k_ref, v_ref,
    o_ref,
    m_scr, l_scr, acc_scr,
    *,
    scale: float,
    bs: int,
    ns: int,
):
    si = pl.program_id(1)
    pos = pos_ref[0]
    s_start = si * bs

    @pl.when(si == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    @pl.when(s_start <= pos)
    def _compute():
        q = q_ref[0].astype(jnp.float32)  # (G, Dh)
        k = k_ref[0].astype(jnp.float32)  # (bs, Dh)
        v = v_ref[0]  # (bs, Dv)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale  # (G, bs)
        slot = s_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(slot <= pos, s, NEG_INF)
        m_prev = m_scr[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_scr[...] = l_scr[...] * corr + jnp.sum(p, axis=-1, keepdims=True)
        m_scr[...] = m_new
        pv = jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        acc_scr[...] = acc_scr[...] * corr + pv

    @pl.when(si == ns - 1)
    def _finalize():
        l = jnp.maximum(l_scr[...], 1e-30)
        o_ref[0] = (acc_scr[...] / l).astype(o_ref.dtype)


def decode_attention_pallas(
    q: jax.Array,        # (B, H, Dh)
    k_cache: jax.Array,  # (B, KH, S, Dh)
    v_cache: jax.Array,  # (B, KH, S, Dv)
    pos: jax.Array,      # scalar int32
    *,
    block_s: int = 512,
    interpret: bool = False,
) -> jax.Array:
    B, H, Dh = q.shape
    KH, S = k_cache.shape[1], k_cache.shape[2]
    Dv = v_cache.shape[-1]
    G = H // KH
    bs = min(block_s, S)
    assert S % bs == 0
    ns = S // bs
    scale = 1.0 / math.sqrt(Dh)

    qr = q.reshape(B * KH, G, Dh)
    kr = k_cache.reshape(B * KH, S, Dh)
    vr = v_cache.reshape(B * KH, S, Dv)
    pos_arr = jnp.asarray(pos, jnp.int32).reshape(1)

    kernel = functools.partial(_decode_kernel, scale=scale, bs=bs, ns=ns)
    scratch_shapes = [
        pltpu.VMEM((G, 1), jnp.float32),
        pltpu.VMEM((G, 1), jnp.float32),
        pltpu.VMEM((G, Dv), jnp.float32),
    ]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(B * KH, ns),
        in_specs=[
            pl.BlockSpec((1, G, Dh), lambda bh, si, pos_ref: (bh, 0, 0)),
            pl.BlockSpec((1, bs, Dh), lambda bh, si, pos_ref: (bh, si, 0)),
            pl.BlockSpec((1, bs, Dv), lambda bh, si, pos_ref: (bh, si, 0)),
        ],
        out_specs=pl.BlockSpec((1, G, Dv), lambda bh, si, pos_ref: (bh, 0, 0)),
        scratch_shapes=scratch_shapes,
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B * KH, G, Dv), q.dtype),
        interpret=interpret,
    )(pos_arr, qr, kr, vr)
    return out.reshape(B, H, Dv)
