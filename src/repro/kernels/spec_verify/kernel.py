"""Speculative verify attention (K queries, per-query lens) for TPU.

The speculative decode loop proposes up to ``K`` tokens per slot with a
cheap draft model, bulk-scatters the whole chunk's K/V into the row's
pool pages (the k-token variant of the decode write: CoW privatization
first, quantize-on-write for int8 pools), then scores *all K positions
against the full model in one pass*. This kernel is that pass's
attention: per row, query position ``j`` (absolute position
``pos[b]+j``) attends pool positions ``<= pos[b]+j`` — the committed
context plus the chunk's own causal prefix, both living in the pool by
the time the kernel runs.

Scoring against the *scattered* chunk (rather than carrying it
in-register) is what makes speculative greedy decode bit-identical to
non-speculative decode: each query sees exactly the page-ordered,
pool-precision keys the sequential kernel would have seen at that
position, with identical online-softmax accumulation order. Positions
beyond the accepted prefix stay in the pool but above the fill line —
invisible to every later read (validity is ``idx <= pos``) and
monotonically overwritten by the next chunk before the fill line can
reach them.

Why this is nearly free relative to K single-token decode steps: decode
attention is memory-bound on the pool read, and the pool pages are read
ONCE here for all K queries (q block ``(K*group, hd)`` vs
``(group, hd)``) — the arithmetic grows K-fold but the HBM traffic does
not. The kernel is ``kernels/paged_attention``'s row kernel with a
per-query offset: chunk position ``j`` attends ``<= pos[b]+j``; int8 pools
dequantize in-register via the same scale-pool prefetch specs.
"""
from __future__ import annotations

import jax.numpy as jnp

from repro.kernels.paged_attention.kernel import paged_attention_rows


def spec_verify_attention_bkgd(q, k_pages, v_pages, block_table, pos, *,
                               group, k_scales=None, v_scales=None,
                               interpret=None):
    """q: (B,KV,K*group,hd) — K query positions flattened position-major
    into the row axis (row ``j*group + g`` is chunk position ``j``, GQA
    member ``g``); k_pages,v_pages: (P,ps,KV,hd) shared page pool (chunk
    K/V already scattered in); block_table: (B,NP) int32 (-1 = unmapped);
    pos: (B,) int32 base positions — query j attends pool positions
    ``<= pos[b]+j``. k_scales/v_scales: optional (P,ps,KV) f32 int8-pool
    scales. -> (B,KV,K*group,hd)."""
    kq = q.shape[2]
    offsets = jnp.arange(kq, dtype=jnp.int32) // group
    return paged_attention_rows(q, k_pages, v_pages, block_table, pos,
                                offsets, k_scales=k_scales,
                                v_scales=v_scales, interpret=interpret)
