"""Block-wise online-softmax attention (flash attention) for TPU.

TPU-native layout decisions (vs the CUDA original):
  - the (bq, hd) query tile and (bk, hd) key/value tiles are MXU-shaped:
    bq/bk default to 128 (the MXU systolic dim) and hd rides the lane dim;
  - K/V for one (batch, kv-head) stream into VMEM as a single BlockSpec
    block; the kernel walks it in bk-sized slabs with an on-VREG running
    (m, l, acc) — HBM→VMEM traffic is O(S·hd), never O(S²);
  - GQA is expressed in the grid: q heads map onto their kv head via
    index_map (no repeat/materialize of K/V);
  - per-row softmax statistics are (bq, 1) columns, and the saved
    normalizer L is (B, H, S, 1): a block's last two dims must tile by
    (8, 128) or span the array, and a trailing unit dim spans it.

Grid: (B, H, nq); each step computes one (bq, hd) output tile.
Supports causal masking and sliding-window (the long_500k dense-arch
variant). Softmax statistics are float32 throughout.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import matmul_nt, resolve_interpret

NEG_INF = -1e30


def _fa_kernel(q_ref, k_ref, v_ref, o_ref, L_ref, *, scale, causal, window,
               bk, seq_k):
    bq, hd = q_ref.shape[2], q_ref.shape[3]
    qi = pl.program_id(2)
    q = q_ref[0, 0].astype(jnp.float32) * scale           # (bq, hd)

    q_pos = qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)

    nk = seq_k // bk

    def body(j, carry):
        m, l, acc = carry
        k_blk = k_ref[0, 0, pl.ds(j * bk, bk), :].astype(jnp.float32)
        v_blk = v_ref[0, 0, pl.ds(j * bk, bk), :].astype(jnp.float32)
        s = matmul_nt(q, k_blk)                           # (bq, bk)
        k_pos = j * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        mask = jnp.ones((bq, bk), bool)
        if causal:
            mask &= k_pos <= q_pos
        if window > 0:
            mask &= k_pos > (q_pos - window)
        s = jnp.where(mask, s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m - m_new)
        l_new = alpha * l + jnp.sum(p, axis=1, keepdims=True)
        acc_new = acc * alpha + jnp.dot(p, v_blk,
                                        preferred_element_type=jnp.float32)
        return m_new, l_new, acc_new

    m0 = jnp.full((bq, 1), NEG_INF, jnp.float32)
    l0 = jnp.zeros((bq, 1), jnp.float32)
    acc0 = jnp.zeros((bq, hd), jnp.float32)
    m, l, acc = jax.lax.fori_loop(0, nk, body, (m0, l0, acc0))
    l = jnp.where(l == 0.0, 1.0, l)                       # fully-masked rows
    o_ref[0, 0] = (acc / l).astype(o_ref.dtype)
    L_ref[0, 0] = m + jnp.log(l)                          # softmax normalizer


def flash_attention_bhsd(q, k, v, *, causal=True, window=0, bq=128, bk=128,
                         interpret=None):
    """q: (B,H,S,hd); k,v: (B,KV,Sk,hd) with H % KV == 0.
    Returns (out (B,H,S,hd), L (B,H,S,1) f32 softmax normalizers — the
    residual the Pallas backward recomputes P from)."""
    B, H, S, hd = q.shape
    KV, Sk = k.shape[1], k.shape[2]
    assert H % KV == 0, (H, KV)
    group = H // KV
    bq = min(bq, S)
    bk = min(bk, Sk)
    assert S % bq == 0 and Sk % bk == 0, (S, bq, Sk, bk)
    scale = 1.0 / math.sqrt(hd)
    grid = (B, H, S // bq)

    kernel = functools.partial(_fa_kernel, scale=scale, causal=causal,
                               window=window, bk=bk, seq_k=Sk)
    out, L = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, bq, hd), lambda b, h, i: (b, h, i, 0)),
            pl.BlockSpec((1, 1, Sk, hd), lambda b, h, i: (b, h // group, 0, 0)),
            pl.BlockSpec((1, 1, Sk, hd), lambda b, h, i: (b, h // group, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, bq, hd), lambda b, h, i: (b, h, i, 0)),
            pl.BlockSpec((1, 1, bq, 1), lambda b, h, i: (b, h, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, H, S, hd), q.dtype),
            jax.ShapeDtypeStruct((B, H, S, 1), jnp.float32),
        ],
        interpret=resolve_interpret(interpret),
    )(q, k, v)
    return out, L
