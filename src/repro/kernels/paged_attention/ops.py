"""Jitted public wrapper for the paged decode attention kernel."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels.paged_attention.kernel import paged_decode_attention_bkgd


def paged_decode_attention(q, k_pages, v_pages, block_table, lens, *,
                           k_scales=None, v_scales=None, interpret=None):
    """q: (B,H,hd) one query per row; k_pages,v_pages: (P,ps,KV,hd) shared
    page pool; block_table: (B,NP) int32 (-1 = unmapped); lens: (B,) int32
    live tokens per row. k_scales/v_scales: optional (P,ps,KV) f32 scale
    pools for int8 pages (kv_dtype="int8") — dequantization happens
    in-register inside the kernel, after the block-table gather. Returns
    (B,H,hd).

    Layout is reshaped to the kernel's (B,KV,group,hd) GQA tiling; k/v
    stay in the pool layout — the block-table gather happens inside the
    kernel via scalar-prefetch index maps.
    """
    B, H, hd = q.shape
    KV = k_pages.shape[2]
    group = H // KV
    qt = q.reshape(B, KV, group, hd)
    out = paged_decode_attention_bkgd(qt, k_pages, v_pages, block_table,
                                      lens, k_scales=k_scales,
                                      v_scales=v_scales, interpret=interpret)
    return out.reshape(B, H, hd)
