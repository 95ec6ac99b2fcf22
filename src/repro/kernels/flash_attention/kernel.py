"""Flash attention — Pallas TPU kernel.

Grid: (B·H, nq, nk) with the KV dimension innermost (sequential on TPU);
online-softmax statistics (m, l) and the output accumulator live in VMEM
scratch and persist across the nk iterations of one (head, q-block).

VMEM working set per program (bq=512, bk=512, Dh=128, bf16 in / f32 acc):
    q tile  512×128×2   =  128 KiB
    k tile  512×128×2   =  128 KiB
    v tile  512×128×2   =  128 KiB
    scores  512×512×4   = 1024 KiB
    acc     512×128×4   =  256 KiB
    m, l    2×512×4     =    4 KiB        → ≈ 1.7 MiB  (≪ 16 MiB VMEM)

MXU alignment: all matmul dims are multiples of 128 (bq, bk, Dh).
Fully-masked (q-block, kv-block) pairs are skipped with ``pl.when`` —
the causal structural skip the pure-jnp ``tri`` mode approximates.

GQA: query head h reads KV head h // (H // KH) via the k/v index_maps.
"""
from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _flash_kernel(
    q_ref, k_ref, v_ref,  # VMEM tiles
    o_ref,  # output tile
    m_scr, l_scr, acc_scr,  # scratch
    *,
    causal: bool,
    window: Optional[int],
    scale: float,
    bq: int,
    bk: int,
    nk: int,
    q_offset: int,
):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    q_start = q_offset + qi * bq
    k_start = ki * bk

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    # structural skip: block fully above the diagonal / outside the window
    live = True
    if causal:
        live = jnp.asarray(k_start <= q_start + bq - 1)
    if window is not None:
        live = jnp.logical_and(
            live, jnp.asarray(k_start + bk - 1 > q_start - window)
        ) if causal else jnp.asarray(True)

    @pl.when(live)
    def _compute():
        q = q_ref[0].astype(jnp.float32)  # (bq, Dh)
        k = k_ref[0].astype(jnp.float32)  # (bk, Dh)
        v = v_ref[0]  # (bk, Dv)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale  # (bq, bk)
        qpos = q_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        kpos = k_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        mask = jnp.ones((bq, bk), jnp.bool_)
        if causal:
            mask &= qpos >= kpos
        if window is not None:
            mask &= qpos - kpos < window
        s = jnp.where(mask, s, NEG_INF)

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

    @pl.when(ki == nk - 1)
    def _finalize():
        l = jnp.maximum(l_scr[...], 1e-30)
        o_ref[0] = (acc_scr[...] / l).astype(o_ref.dtype)


def flash_attention_pallas(
    q: jax.Array,  # (B, H, Lq, Dh)
    k: jax.Array,  # (B, KH, Lk, Dh)
    v: jax.Array,  # (B, KH, Lk, Dv)
    *,
    causal: bool = True,
    window: Optional[int] = None,
    q_offset: int = 0,
    block_q: int = 512,
    block_kv: int = 512,
    interpret: bool = False,
) -> jax.Array:
    B, H, Lq, Dh = q.shape
    KH, Lk = k.shape[1], k.shape[2]
    Dv = v.shape[-1]
    G = H // KH
    bq = min(block_q, Lq)
    bk = min(block_kv, Lk)
    assert Lq % bq == 0 and Lk % bk == 0
    nq, nk = Lq // bq, Lk // bk
    scale = 1.0 / math.sqrt(Dh)

    # fold (B, H) into one grid dim; kv head = (bh % H) // G
    qr = q.reshape(B * H, Lq, Dh)
    kr = k.reshape(B * KH, Lk, Dh)
    vr = v.reshape(B * KH, Lk, Dv)

    def q_index(bh, qi, ki):
        return (bh, qi, 0)

    def kv_index(bh, qi, ki):
        return ((bh // H) * KH + (bh % H) // G, ki, 0)

    kernel = functools.partial(
        _flash_kernel,
        causal=causal,
        window=window,
        scale=scale,
        bq=bq,
        bk=bk,
        nk=nk,
        q_offset=q_offset,
    )
    scratch_shapes = [
        pltpu.VMEM((bq, 1), jnp.float32),
        pltpu.VMEM((bq, 1), jnp.float32),
        pltpu.VMEM((bq, Dv), jnp.float32),
    ]

    out = pl.pallas_call(
        kernel,
        grid=(B * H, nq, nk),
        in_specs=[
            pl.BlockSpec((1, bq, Dh), q_index),
            pl.BlockSpec((1, bk, Dh), kv_index),
            pl.BlockSpec((1, bk, Dv), kv_index),
        ],
        out_specs=pl.BlockSpec((1, bq, Dv), q_index),
        out_shape=jax.ShapeDtypeStruct((B * H, Lq, Dv), q.dtype),
        scratch_shapes=scratch_shapes,
        interpret=interpret,
    )(qr, kr, vr)
    return out.reshape(B, H, Lq, Dv)
