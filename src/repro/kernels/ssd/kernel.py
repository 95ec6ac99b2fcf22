"""Mamba-2 SSD intra-chunk kernel — Pallas TPU.

Grid: (B·H, n_chunks).  Each program loads one chunk's (x, dt, cum, B, C)
tile into VMEM and produces the intra-chunk output and the end-of-chunk
state with three MXU matmuls:

    scores = (C Bᵀ) ⊙ Lmask,   y = scores·(x),   state = (B·w)ᵀ x

where Lmask[i,j] = exp(cum_i − cum_j)·dt_j for i ≥ j and w = exp(cum_end −
cum)·dt.  The O(n_chunks) inter-chunk recurrence (tiny: (N, P) per head)
stays in jnp — the kernel covers the quadratic-in-chunk-size hot spot.

VMEM per program (cs=256, P=64, N=128, f32):
    x 256×64, B/C 2×256×128, scores 256×256, y 256×64, state 128×64
    ≈ 0.6 MiB — comfortably resident; cs and N are multiples of 128 for
    the MXU (P=64 rides the free dimension).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _ssd_kernel(x_ref, dt_ref, cum_ref, b_ref, c_ref, y_ref, state_ref, *, cs: int):
    x = x_ref[0, 0].astype(jnp.float32)    # (cs, P)
    dt = dt_ref[0].astype(jnp.float32)    # (cs, 1)
    cum = cum_ref[0].astype(jnp.float32)  # (cs, 1)
    B = b_ref[0, 0].astype(jnp.float32)    # (cs, N)
    C = c_ref[0, 0].astype(jnp.float32)    # (cs, N)

    scores = jax.lax.dot_general(
        C, B, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )  # (cs, cs)
    ii = jax.lax.broadcasted_iota(jnp.int32, (cs, cs), 0)
    jj = jax.lax.broadcasted_iota(jnp.int32, (cs, cs), 1)
    decay = jnp.exp(cum - cum.T)  # cum_i - cum_j
    L = jnp.where(ii >= jj, decay, 0.0)
    w = scores * L * dt.T
    y_ref[0, 0] = jax.lax.dot_general(
        w, x, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
    ).astype(y_ref.dtype)

    cum_end = cum[cs - 1, 0]
    wts = jnp.exp(cum_end - cum) * dt  # (cs, 1)
    state_ref[0, 0] = jax.lax.dot_general(
        B * wts, x, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
    ).astype(state_ref.dtype)


def ssd_intra_chunk_pallas(
    x: jax.Array,    # (BH, nc, cs, P)
    dt: jax.Array,   # (BH, nc, cs)
    cum: jax.Array,  # (BH, nc, cs)
    B: jax.Array,    # (BH, nc, cs, N)
    C: jax.Array,    # (BH, nc, cs, N)
    *,
    interpret: bool = False,
):
    BH, nc, cs, P = x.shape
    N = B.shape[-1]
    kernel = functools.partial(_ssd_kernel, cs=cs)
    grid = (BH, nc)

    def idx(b, c):
        return (b, c, 0, 0)

    def idx3(b, c):
        return (b, c, 0)

    y, state = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, cs, P), lambda b, c: (b, c, 0, 0)),
            pl.BlockSpec((1, cs, 1), lambda b, c: (b, c, 0)),
            pl.BlockSpec((1, cs, 1), lambda b, c: (b, c, 0)),
            pl.BlockSpec((1, 1, cs, N), lambda b, c: (b, c, 0, 0)),
            pl.BlockSpec((1, 1, cs, N), lambda b, c: (b, c, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, cs, P), lambda b, c: (b, c, 0, 0)),
            pl.BlockSpec((1, 1, N, P), lambda b, c: (b, c, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((BH, nc, cs, P), jnp.float32),
            jax.ShapeDtypeStruct((BH, nc, N, P), jnp.float32),
        ],
        interpret=interpret,
    )(
        x,
        dt.reshape(BH, nc * cs, 1),
        cum.reshape(BH, nc * cs, 1),
        B,
        C,
    )
    return y, state
