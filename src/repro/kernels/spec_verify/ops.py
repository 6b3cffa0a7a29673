"""Jitted public wrapper for the speculative verify attention kernel."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels.spec_verify.kernel import spec_verify_attention_bkgd


def spec_verify_attention(q, k_pages, v_pages, block_table, pos, *,
                          k_scales=None, v_scales=None, interpret=None):
    """q: (B,K,H,hd) K candidate queries per row, chunk K/V already
    bulk-scattered into the pool at absolute positions
    ``pos[b]..pos[b]+K-1``; k_pages,v_pages: (P,ps,KV,hd) shared page
    pool; block_table: (B,NP) int32 (-1 = unmapped); pos: (B,) int32 base
    positions. k_scales/v_scales: optional (P,ps,KV) f32 scale pools for
    int8 pages — dequantization happens in-register inside the kernel,
    after the block-table gather. Returns (B,K,H,hd).

    Query position ``j`` attends pool positions ``<= pos[b]+j`` — the
    committed context plus the chunk's causal prefix, read back from the
    pool at pool precision exactly as the sequential decode kernel would
    read them, which is what keeps speculative greedy decode bit-identical
    to non-speculative. The pool pages are read once for all K queries —
    the reason verify is nearly free relative to K sequential decode steps
    when decode is memory-bound.
    """
    B, K, H, hd = q.shape
    KV = k_pages.shape[2]
    group = H // KV
    # (B,K,KV,group,hd) -> (B,KV,K*group,hd): query row j*group+g
    qt = q.reshape(B, K, KV, group, hd).transpose(0, 2, 1, 3, 4) \
        .reshape(B, KV, K * group, hd)
    out = spec_verify_attention_bkgd(qt, k_pages, v_pages, block_table,
                                     pos, group=group, k_scales=k_scales,
                                     v_scales=v_scales, interpret=interpret)
    return out.reshape(B, KV, K, group, hd).transpose(0, 2, 1, 3, 4) \
        .reshape(B, K, H, hd)
