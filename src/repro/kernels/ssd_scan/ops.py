"""Jitted public wrapper for the SSD scan kernel.

Signature mirrors ``models.mamba.ssd_chunked`` so the mixer can switch
implementations with ``attn_impl="pallas"``; inputs that don't tile evenly
(S % chunk != 0) are padded with zero-dt steps, which leave the state
untouched (exp(0)=1 decay, 0 input weight).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels.ssd_scan.kernel import ssd_scan_bhcp


def ssd_scan(x, dt, A, B, C, chunk_size: int, initial_state=None,
             *, interpret=None):
    """x: (b,s,h,p); dt: (b,s,h) (softplus'ed); A: (h,) negative;
    B,C: (b,s,g,n). Returns (y (b,s,h,p), final_state (b,h,p,n) f32)."""
    assert initial_state is None, "kernel path starts from zero state"
    b, s, h, p = x.shape
    q = min(chunk_size, s)
    pad = (-s) % q
    if pad:
        zpad = lambda a: jnp.pad(a, [(0, 0), (0, pad)] +
                                 [(0, 0)] * (a.ndim - 2))
        x, dt, B, C = zpad(x), zpad(dt), zpad(B), zpad(C)
    dA = dt.astype(jnp.float32) * A.astype(jnp.float32)[None, None, :]
    y, final = ssd_scan_bhcp(x, dt, dA, B, C, chunk=q, interpret=interpret)
    return y[:, :s], final
