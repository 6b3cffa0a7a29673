"""Jitted public wrapper for the decode attention kernel."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels import pick_block
from repro.kernels.decode_attention.kernel import decode_attention_bkgd


def decode_attention(q, k, v, valid, *, block_s=None, interpret=None):
    """q: (B,H,hd) one query per row; k,v: (B,S,KV,hd); valid: (B,S) bool.
    Returns (B,H,hd). Layout transposed to the kernel's (B,KV,...)."""
    B, H, hd = q.shape
    S, KV = k.shape[1], k.shape[2]
    group = H // KV
    bs = block_s or pick_block(S, 512, 128)   # the mask's lane dim
    qt = q.reshape(B, KV, group, hd)
    kt = k.transpose(0, 2, 1, 3)                          # (B,KV,S,hd)
    vt = v.transpose(0, 2, 1, 3)
    out = decode_attention_bkgd(qt, kt, vt, valid, bs=bs,
                                interpret=interpret)
    return out.reshape(B, H, hd)
