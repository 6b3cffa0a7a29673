"""Single-query attention against a long KV cache (flash-decode) for TPU.

The decode shapes put one new token against caches up to 512K entries —
far beyond VMEM — so the sequence axis is blocked in the *grid*:
grid = (B, KV, ns), and the kernel carries running online-softmax state
(m, l, acc) in VMEM scratch across the ns iterations (TPU grids execute
sequentially per core, so scratch written at step j is visible at j+1 —
the idiomatic TPU replacement for the CUDA flash-decode two-phase
split-k + cross-SM reduction).

GQA: the ``group`` q heads sharing a kv head are processed together as a
(group, hd) tile, so the kv block is loaded once per group (the whole
point of GQA decode).

TPU tiling: the validity mask rides as an int32 (B, 1, S) array so its
(1, bs) block spans the unit dim, and the running softmax statistics are
(group, 1) columns.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import matmul_nt, resolve_interpret

NEG_INF = -1e30


def _decode_kernel(q_ref, k_ref, v_ref, valid_ref, o_ref,
                   m_ref, l_ref, acc_ref, *, scale, bs, ns):
    group, hd = q_ref.shape[2], q_ref.shape[3]
    si = pl.program_id(2)

    @pl.when(si == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0, 0].astype(jnp.float32) * scale            # (group, hd)
    k_blk = k_ref[0, 0].astype(jnp.float32)                # (bs, hd)
    v_blk = v_ref[0, 0].astype(jnp.float32)
    ok = valid_ref[0] != 0                                 # (1, bs)

    s = matmul_nt(q, k_blk)                                # (group, bs)
    s = jnp.where(ok, s, NEG_INF)

    m_prev = m_ref[...]
    l_prev = l_ref[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
    p = jnp.exp(s - m_new)
    alpha = jnp.exp(m_prev - m_new)
    l_new = alpha * l_prev + jnp.sum(p, axis=1, keepdims=True)
    acc_ref[...] = acc_ref[...] * alpha + jnp.dot(
        p, v_blk, preferred_element_type=jnp.float32)
    m_ref[...] = m_new
    l_ref[...] = l_new

    @pl.when(si == ns - 1)
    def _finish():
        l = l_ref[...]
        l = jnp.where(l == 0.0, 1.0, l)
        o_ref[0, 0] = (acc_ref[...] / l).astype(o_ref.dtype)


def decode_attention_bkgd(q, k, v, valid, *, bs=512, interpret=None):
    """q: (B,KV,group,hd); k,v: (B,KV,S,hd); valid: (B,S) bool.
    -> (B,KV,group,hd)."""
    B, KV, group, hd = q.shape
    S = k.shape[2]
    bs = min(bs, S)
    assert S % bs == 0, (S, bs)
    ns = S // bs
    scale = 1.0 / math.sqrt(hd)
    kernel = functools.partial(_decode_kernel, scale=scale, bs=bs, ns=ns)
    return pl.pallas_call(
        kernel,
        grid=(B, KV, ns),
        in_specs=[
            pl.BlockSpec((1, 1, group, hd), lambda b, g, s: (b, g, 0, 0)),
            pl.BlockSpec((1, 1, bs, hd), lambda b, g, s: (b, g, s, 0)),
            pl.BlockSpec((1, 1, bs, hd), lambda b, g, s: (b, g, s, 0)),
            pl.BlockSpec((1, 1, bs), lambda b, g, s: (b, 0, s)),
        ],
        out_specs=pl.BlockSpec((1, 1, group, hd), lambda b, g, s: (b, g, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((B, KV, group, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((group, 1), jnp.float32),    # running max m
            pltpu.VMEM((group, 1), jnp.float32),    # running sum l
            pltpu.VMEM((group, hd), jnp.float32),   # output accumulator
        ],
        interpret=resolve_interpret(interpret),
    )(q, k, v, valid.astype(jnp.int32)[:, None, :])
