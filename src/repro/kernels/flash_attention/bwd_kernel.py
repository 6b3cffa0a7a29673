"""Flash attention backward pass — Pallas TPU kernels.

Standard two-pass formulation (Dao et al., re-blocked for the MXU):

    forward saves L = m + log(l) per query row (the softmax normalizer);
    D_i   = rowsum(dO ∘ O)                                    (precomputed)
    P     = exp(q k^T · scale − L)          recomputed blockwise, no O(S²)
    dS    = P ∘ (dO V^T − D)
    dq    = scale · dS K          (pass 1: grid over q blocks)
    dk    = scale · dS^T Q        (pass 2: grid over kv blocks,
    dv    = P^T dO                          accumulating over the q-head
                                            group that shares the kv head)

Both passes stream K/V (or Q/dO) through VMEM in bk/bq-sized slabs with
f32 accumulators — HBM traffic stays O(S·hd) like the forward.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import matmul_nt, matmul_tn, resolve_interpret

NEG_INF = -1e30


def _mask(bq, bk, qi, kj, *, causal, window):
    q_pos = qi + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
    k_pos = kj + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    ok = jnp.ones((bq, bk), bool)
    if causal:
        ok &= k_pos <= q_pos
    if window > 0:
        ok &= k_pos > (q_pos - window)
    return ok


def _dq_kernel(q_ref, k_ref, v_ref, do_ref, L_ref, D_ref, dq_ref, *,
               scale, causal, window, bk, seq_k):
    bq, hd = q_ref.shape[2], q_ref.shape[3]
    qi = pl.program_id(2) * bq
    q = q_ref[0, 0].astype(jnp.float32)
    do = do_ref[0, 0].astype(jnp.float32)
    Lrow = L_ref[0, 0]                                     # (bq, 1)
    Drow = D_ref[0, 0]                                     # (bq, 1)

    def body(j, dq):
        k_blk = k_ref[0, 0, pl.ds(j * bk, bk), :].astype(jnp.float32)
        v_blk = v_ref[0, 0, pl.ds(j * bk, bk), :].astype(jnp.float32)
        s = matmul_nt(q, k_blk) * scale                    # (bq, bk)
        ok = _mask(bq, bk, qi, j * bk, causal=causal, window=window)
        p = jnp.where(ok, jnp.exp(s - Lrow), 0.0)
        dp = matmul_nt(do, v_blk)                          # (bq, bk)
        ds = p * (dp - Drow)
        return dq + jnp.dot(ds, k_blk, preferred_element_type=jnp.float32)

    dq = jax.lax.fori_loop(0, seq_k // bk, body,
                           jnp.zeros((bq, hd), jnp.float32))
    dq_ref[0, 0] = (dq * scale).astype(dq_ref.dtype)


def _dkv_kernel(q_ref, k_ref, v_ref, do_ref, L_ref, D_ref,
                dk_ref, dv_ref, *, scale, causal, window, bq, seq_q, group):
    bk, hd = k_ref.shape[2], k_ref.shape[3]
    kj = pl.program_id(2) * bk
    k_blk = k_ref[0, 0].astype(jnp.float32)                # (bk, hd)
    v_blk = v_ref[0, 0].astype(jnp.float32)

    def q_loop(gi, carry):
        """Accumulate over the `group` q heads sharing this kv head AND
        the q blocks; gi enumerates (head_in_group, q_block) pairs."""
        dk, dv = carry
        g = gi // (seq_q // bq)
        i = gi % (seq_q // bq)
        q = q_ref[0, 0, g, pl.ds(i * bq, bq), :].astype(jnp.float32)
        do = do_ref[0, 0, g, pl.ds(i * bq, bq), :].astype(jnp.float32)
        Lrow = L_ref[0, 0, g, pl.ds(i * bq, bq), :]        # (bq, 1)
        Drow = D_ref[0, 0, g, pl.ds(i * bq, bq), :]
        s = matmul_nt(q, k_blk) * scale                    # (bq, bk)
        ok = _mask(bq, bk, i * bq, kj, causal=causal, window=window)
        p = jnp.where(ok, jnp.exp(s - Lrow), 0.0)
        dv = dv + matmul_tn(p, do)
        dp = matmul_nt(do, v_blk)
        ds = p * (dp - Drow)
        dk = dk + matmul_tn(ds, q)
        return dk, dv

    n = group * (seq_q // bq)
    dk, dv = jax.lax.fori_loop(
        0, n, q_loop, (jnp.zeros((bk, hd), jnp.float32),
                       jnp.zeros((bk, hd), jnp.float32)))
    dk_ref[0, 0] = (dk * scale).astype(dk_ref.dtype)
    dv_ref[0, 0] = dv.astype(dv_ref.dtype)


def flash_attention_bwd_bhsd(q, k, v, o, do, L, *, causal=True, window=0,
                             bq=128, bk=128, interpret=None):
    """Backward pass. q,o,do: (B,H,S,hd); k,v: (B,KV,Sk,hd); L: (B,H,S,1).
    Returns (dq (B,H,S,hd), dk (B,KV,Sk,hd), dv (B,KV,Sk,hd))."""
    B, H, S, hd = q.shape
    KV, Sk = k.shape[1], k.shape[2]
    group = H // KV
    bq = min(bq, S)
    bk = min(bk, Sk)
    assert S % bq == 0 and Sk % bk == 0
    scale = 1.0 / (hd ** 0.5)
    interpret = resolve_interpret(interpret)
    D = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32), axis=-1,
                keepdims=True)                             # (B,H,S,1)

    dq = pl.pallas_call(
        functools.partial(_dq_kernel, scale=scale, causal=causal,
                          window=window, bk=bk, seq_k=Sk),
        grid=(B, H, S // bq),
        in_specs=[
            pl.BlockSpec((1, 1, bq, hd), lambda b, h, i: (b, h, i, 0)),
            pl.BlockSpec((1, 1, Sk, hd), lambda b, h, i: (b, h // group, 0, 0)),
            pl.BlockSpec((1, 1, Sk, hd), lambda b, h, i: (b, h // group, 0, 0)),
            pl.BlockSpec((1, 1, bq, hd), lambda b, h, i: (b, h, i, 0)),
            pl.BlockSpec((1, 1, bq, 1), lambda b, h, i: (b, h, i, 0)),
            pl.BlockSpec((1, 1, bq, 1), lambda b, h, i: (b, h, i, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, hd), lambda b, h, i: (b, h, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, S, hd), q.dtype),
        interpret=interpret,
    )(q, k, v, do, L, D)

    # group the H q-heads by their kv head for the dk/dv pass
    qg = q.reshape(B, KV, group, S, hd)
    dog = do.reshape(B, KV, group, S, hd)
    Lg = L.reshape(B, KV, group, S, 1)
    Dg = D.reshape(B, KV, group, S, 1)

    dk, dv = pl.pallas_call(
        functools.partial(_dkv_kernel, scale=scale, causal=causal,
                          window=window, bq=bq, seq_q=S, group=group),
        grid=(B, KV, Sk // bk),
        in_specs=[
            pl.BlockSpec((1, 1, group, S, hd),
                         lambda b, g, j: (b, g, 0, 0, 0)),
            pl.BlockSpec((1, 1, bk, hd), lambda b, g, j: (b, g, j, 0)),
            pl.BlockSpec((1, 1, bk, hd), lambda b, g, j: (b, g, j, 0)),
            pl.BlockSpec((1, 1, group, S, hd),
                         lambda b, g, j: (b, g, 0, 0, 0)),
            pl.BlockSpec((1, 1, group, S, 1),
                         lambda b, g, j: (b, g, 0, 0, 0)),
            pl.BlockSpec((1, 1, group, S, 1),
                         lambda b, g, j: (b, g, 0, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, bk, hd), lambda b, g, j: (b, g, j, 0)),
            pl.BlockSpec((1, 1, bk, hd), lambda b, g, j: (b, g, j, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, KV, Sk, hd), k.dtype),
            jax.ShapeDtypeStruct((B, KV, Sk, hd), v.dtype),
        ],
        interpret=interpret,
    )(qg, k, v, dog, Lg, Dg)
    return dq, dk, dv
