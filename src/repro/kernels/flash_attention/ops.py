"""Jitted public wrapper for the flash attention kernel.

``flash_attention`` takes model-layout tensors (B, S, H, hd) and handles:
  - layout transpose to the kernels' (B, H, S, hd);
  - block-size selection (MXU-aligned 128 where the sequence allows,
    else a multiple of 8, else the whole sequence);
  - a custom VJP whose backward is the Pallas two-pass flash backward
    (bwd_kernel.py) — P is recomputed blockwise from the saved softmax
    normalizers L, so neither direction materializes O(S²) tensors.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.kernels import pick_block
from repro.kernels.flash_attention import ref
from repro.kernels.flash_attention.bwd_kernel import flash_attention_bwd_bhsd
from repro.kernels.flash_attention.kernel import flash_attention_bhsd


def _pick_block(s: int) -> int:
    return pick_block(s, 128, 8)            # rows ride the sublane dim


@partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def flash_attention(q, k, v, causal=True, window=0, interpret=None):
    """q: (B,S,H,hd); k,v: (B,Sk,KV,hd) -> (B,S,H,hd)."""
    out, _ = _forward(q, k, v, causal, window, interpret)
    return out


def _forward(q, k, v, causal, window, interpret):
    B, S, H, hd = q.shape
    bq = _pick_block(S)
    bk = _pick_block(k.shape[1])
    qt = q.transpose(0, 2, 1, 3)
    kt = k.transpose(0, 2, 1, 3)
    vt = v.transpose(0, 2, 1, 3)
    out_t, L = flash_attention_bhsd(qt, kt, vt, causal=causal, window=window,
                                    bq=bq, bk=bk, interpret=interpret)
    return out_t.transpose(0, 2, 1, 3), (qt, kt, vt, out_t, L)


def _fwd(q, k, v, causal, window, interpret):
    out, res = _forward(q, k, v, causal, window, interpret)
    return out, res


def _bwd(causal, window, interpret, res, g):
    qt, kt, vt, out_t, L = res
    do_t = g.transpose(0, 2, 1, 3)
    bq = _pick_block(qt.shape[2])
    bk = _pick_block(kt.shape[2])
    dq, dk, dv = flash_attention_bwd_bhsd(
        qt, kt, vt, out_t, do_t, L, causal=causal, window=window,
        bq=bq, bk=bk, interpret=interpret)
    return (dq.transpose(0, 2, 1, 3), dk.transpose(0, 2, 1, 3),
            dv.transpose(0, 2, 1, 3))


flash_attention.defvjp(_fwd, _bwd)


def flash_attention_ref_bwd(q, k, v, causal=True, window=0):
    """Oracle-differentiated variant (kept for kernel-vs-ref grad tests)."""
    return ref.attention_ref(q, k, v, causal=causal, window=window)
