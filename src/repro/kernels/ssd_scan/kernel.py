"""Mamba2 SSD (state-space dual) chunked scan for TPU.

TPU adaptation of the Mamba2 GPU kernel (arXiv:2405.21060 §7): the GPU
version splits intra-chunk work across warps with shared-memory staging;
on TPU the same math becomes three MXU matmuls per (batch, head, chunk)
tile, and the inter-chunk linear recurrence rides VMEM scratch across the
sequentially-executed chunk axis of the grid (no cross-core shuffle
needed):

    intra-chunk (dual "attention" form):
        W = (C B^T) ∘ L ∘ dt      (q,q) masked-decay Gram matrix
        y_diag = W @ x            MXU matmul
    inter-chunk (recurrence over the grid's chunk axis):
        y_off  = (C ∘ exp(csum)) @ state
        state  = exp(dA_chunk) * state + (decay·B)^T @ x

Grid: (B, H, n_chunks); chunk axis iterates sequentially, so the (P, N)
f32 state persists in scratch between chunk steps. All statistics f32.
B/C are per-group (GVA): index_map folds h -> h // (H//G), no repeat.

TPU tiling: dt and dA ride twice, as (chunk, 1) columns and (1, chunk)
rows, so each block's last two dims span a unit dim or the chunk, and the
chunk's prefix sums come out in both orientations from masked sums (no
in-kernel transpose or cumsum).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import matmul_nt, matmul_tn, resolve_interpret


def _ssd_kernel(x_ref, dtc_ref, dtr_ref, dAc_ref, dAr_ref, b_ref, c_ref,
                y_ref, state_out_ref, state_ref, *, nc):
    ci = pl.program_id(2)
    q = x_ref.shape[3]

    @pl.when(ci == 0)
    def _init():
        state_ref[...] = jnp.zeros_like(state_ref)

    x = x_ref[0, 0, 0].astype(jnp.float32)                 # (q, p)
    dt_row = dtr_ref[0, 0, 0].astype(jnp.float32)          # (1, q)
    dt_col = dtc_ref[0, 0, 0].astype(jnp.float32)          # (q, 1)
    dA_row = dAr_ref[0, 0, 0].astype(jnp.float32)          # (1, q) = dt*A_h
    dA_col = dAc_ref[0, 0, 0].astype(jnp.float32)          # (q, 1)
    Bm = b_ref[0, 0, 0].astype(jnp.float32)                # (q, n)
    Cm = c_ref[0, 0, 0].astype(jnp.float32)                # (q, n)

    ii = jax.lax.broadcasted_iota(jnp.int32, (q, q), 0)
    jj = jax.lax.broadcasted_iota(jnp.int32, (q, q), 1)
    lower = jj <= ii
    # inclusive prefix sums of dA as a column (cs_i) and a row (cs_j)
    cs_col = jnp.sum(jnp.where(lower, dA_row, 0.0), axis=1, keepdims=True)
    cs_row = jnp.sum(jnp.where(ii <= jj, dA_col, 0.0), axis=0,
                     keepdims=True)
    total = jnp.sum(dA_row, axis=1, keepdims=True)         # (1, 1) = cs[-1]
    # intra-chunk decay Gram: L[i,j] = exp(cs_i - cs_j) for j <= i
    Lmat = jnp.where(lower, jnp.exp(cs_col - cs_row), 0.0)
    W = matmul_nt(Cm, Bm) * Lmat * dt_row                  # (q, q)
    y = jnp.dot(W, x, preferred_element_type=jnp.float32)  # (q, p)

    # carried-in state contribution
    state = state_ref[...]                                 # (p, n)
    y += matmul_nt(Cm * jnp.exp(cs_col), state)            # (q, p)

    # state update: S' = exp(cs[-1]) S + sum_j exp(cs[-1]-cs_j) dt_j x_j B_j^T
    decay = jnp.exp(total - cs_col) * dt_col               # (q, 1)
    state_ref[...] = jnp.exp(total) * state + matmul_tn(
        x, Bm * decay)                                     # (p, n)

    y_ref[0, 0, 0] = y.astype(y_ref.dtype)

    @pl.when(ci == nc - 1)
    def _finish():
        state_out_ref[0, 0] = state_ref[...]


def ssd_scan_bhcp(x, dt, dA, B, C, *, chunk, interpret=None):
    """x: (b,s,h,p); dt,dA: (b,s,h); B,C: (b,s,g,n). s % chunk == 0.
    Returns (y (b,s,h,p), final_state (b,h,p,n) float32)."""
    b, s, h, p = x.shape
    g, n = B.shape[2], B.shape[3]
    assert s % chunk == 0, (s, chunk)
    nc = s // chunk
    rep = h // g

    # kernel-friendly layouts: (b, h, nc, ·, ·)
    xt = x.transpose(0, 2, 1, 3).reshape(b, h, nc, chunk, p)
    dtt = dt.transpose(0, 2, 1).reshape(b, h, nc, chunk)
    dAt = dA.transpose(0, 2, 1).reshape(b, h, nc, chunk)
    Bt = B.transpose(0, 2, 1, 3).reshape(b, g, nc, chunk, n)
    Ct = C.transpose(0, 2, 1, 3).reshape(b, g, nc, chunk, n)

    chunk_map = lambda i, j, c: (i, j, c, 0, 0)
    group_map = lambda i, j, c: (i, j // rep, c, 0, 0)
    col = pl.BlockSpec((1, 1, 1, chunk, 1), chunk_map)
    row = pl.BlockSpec((1, 1, 1, 1, chunk), chunk_map)
    kernel = functools.partial(_ssd_kernel, nc=nc)
    y, final = pl.pallas_call(
        kernel,
        grid=(b, h, nc),
        in_specs=[
            pl.BlockSpec((1, 1, 1, chunk, p), chunk_map),
            col, row, col, row,
            pl.BlockSpec((1, 1, 1, chunk, n), group_map),
            pl.BlockSpec((1, 1, 1, chunk, n), group_map),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, 1, chunk, p), chunk_map),
            pl.BlockSpec((1, 1, p, n), lambda i, j, c: (i, j, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, h, nc, chunk, p), x.dtype),
            jax.ShapeDtypeStruct((b, h, p, n), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((p, n), jnp.float32)],
        interpret=resolve_interpret(interpret),
    )(xt, dtt[..., None], dtt[..., None, :], dAt[..., None],
      dAt[..., None, :], Bt, Ct)
    y = y.reshape(b, h, s, p).transpose(0, 2, 1, 3)
    return y, final
