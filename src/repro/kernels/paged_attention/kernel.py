"""Paged decode attention (vLLM-style block tables) for TPU.

Single-query attention where the KV cache is a shared *page pool*
``(n_pages, page_size, KV, hd)`` indexed per row through a block table —
the layout that lets the rollout engine's slot refill free pages instead
of zeroing a dense cache row.

The gather happens *in the grid*: the block table and per-row positions
are scalar-prefetch operands (``pltpu.PrefetchScalarGridSpec``), so the
k/v BlockSpec index maps read ``block_table[b, p]`` to DMA exactly the
pages a row owns — the kernel never materializes a dense per-row cache
view (the XLA fallback in ``models/layers.py`` does, which is the
bandwidth cost this kernel removes).

Online-softmax state is carried in VMEM scratch across the page axis of
the grid (TPU grids execute sequentially per core — same idiom as
``kernels/decode_attention``).

TPU tiling: a block's last two dims must tile by (8, 128) or span the
array. A page is therefore DMA'd whole, as a ``(ps, KV*hd)`` slab of the
pool viewed as ``(P, ps, KV*hd)``, once per grid step for all KV heads.
The queries of every head ride as one block-diagonal ``(KV*R, KV*hd)``
tile — row ``h*R + r`` carries head ``h``'s query ``r`` in lanes
``h*hd..(h+1)*hd`` and zeros elsewhere — so one MXU pass scores all heads
against the slab, and the wrapper keeps each head's own lane block of
the output. Int8 scale pools ``(P, ps, KV)`` ride the same block-table
index map and are expanded across each head's lanes in-register.

``paged_attention_rows`` is the shared kernel: query row ``r`` of a row
group attends pool positions ``<= pos[b] + offset[r]``. Single-token
decode is offset 0 with ``pos = lens - 1``; the speculative verify pass
(``kernels/spec_verify``) gives chunk position ``j`` offset ``j``.
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


def _expand_heads(s, n_kv, hd):
    """(ps, KV) per-head scales -> (ps, KV*hd), head h's scale broadcast
    over its lanes ``h*hd..(h+1)*hd``."""
    lane = jax.lax.broadcasted_iota(jnp.int32, (s.shape[0], n_kv * hd), 1)
    out = jnp.zeros(lane.shape, jnp.float32)
    for h in range(n_kv):
        out = jnp.where((lane >= h * hd) & (lane < (h + 1) * hd),
                        s[:, h:h + 1], out)
    return out


def _paged_kernel(bt_safe_ref, bt_ref, pos_ref, q_ref, off_ref, k_ref,
                  v_ref, *refs, scale, ps, n_pages_grid, quantized, n_kv,
                  hd):
    del bt_safe_ref                    # consumed by the BlockSpec index maps
    if quantized:
        ks_ref, vs_ref, o_ref, m_ref, l_ref, acc_ref = refs
    else:
        o_ref, m_ref, l_ref, acc_ref = refs
    rows = q_ref.shape[1]
    b = pl.program_id(0)
    p = pl.program_id(1)

    @pl.when(p == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0].astype(jnp.float32) * scale               # (rows, KV*hd)
    k_blk = k_ref[0].astype(jnp.float32)                   # (ps, KV*hd)
    v_blk = v_ref[0].astype(jnp.float32)
    if quantized:
        # fused dequant: int8 page values scaled in-register by the
        # per-(offset, kv-head) f32 scales that rode the same block-table
        # index map — exactly ``paging.dequantize_kv``, applied before the
        # online-softmax update, so no fp32 page is ever materialized
        k_blk = k_blk * _expand_heads(ks_ref[0], n_kv, hd)
        v_blk = v_blk * _expand_heads(vs_ref[0], n_kv, hd)

    # absolute positions held by this page of the row's block table; a
    # partially filled last page and unmapped entries mask the same way
    idx = p * ps + jax.lax.broadcasted_iota(jnp.int32, (rows, ps), 1)
    ok = (idx <= pos_ref[b] + off_ref[...]) & (bt_ref[b, p] >= 0)

    s = matmul_nt(q, k_blk)                                # (rows, ps)
    s = jnp.where(ok, s, NEG_INF)

    m_prev = m_ref[...]
    l_prev = l_ref[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
    pr = jnp.exp(s - m_new)
    pr = jnp.where(ok, pr, 0.0)        # masked cols contribute exactly 0
    alpha = jnp.exp(m_prev - m_new)
    acc_ref[...] = acc_ref[...] * alpha + jnp.dot(
        pr, v_blk, preferred_element_type=jnp.float32)
    m_ref[...] = m_new
    l_ref[...] = alpha * l_prev + jnp.sum(pr, axis=1, keepdims=True)

    @pl.when(p == n_pages_grid - 1)
    def _finish():
        l = l_ref[...]
        l = jnp.where(l == 0.0, 1.0, l)                    # fully masked row
        o_ref[0] = (acc_ref[...] / l).astype(o_ref.dtype)


def paged_attention_rows(q, k_pages, v_pages, block_table, pos, offsets, *,
                         k_scales=None, v_scales=None, interpret=None):
    """q: (B,KV,R,hd) — R query rows per kv head; k_pages,v_pages:
    (P,ps,KV,hd); block_table: (B,NP) int32 (-1 = unmapped); pos: (B,)
    int32; offsets: (R,) int32 — row r attends pool positions
    ``<= pos[b] + offsets[r]``. k_scales/v_scales: optional (P,ps,KV) f32
    int8-pool scales, dequantized in-register. -> (B,KV,R,hd)."""
    B, KV, R, hd = q.shape
    P, ps = k_pages.shape[0], k_pages.shape[1]
    NP = block_table.shape[1]
    scale = 1.0 / math.sqrt(hd)
    quantized = k_scales is not None
    kernel = functools.partial(_paged_kernel, scale=scale, ps=ps,
                               n_pages_grid=NP, quantized=quantized,
                               n_kv=KV, hd=hd)
    # block-diagonal queries: row h*R+r holds head h's query in lanes of h
    eye = jnp.eye(KV, dtype=q.dtype)
    q_bd = (q[:, :, :, None, :] * eye[None, :, None, :, None]) \
        .reshape(B, KV * R, KV * hd)
    off = jnp.tile(offsets.astype(jnp.int32), KV)[:, None]  # (KV*R, 1)
    # unmapped entries are masked in-kernel; clamp so the index map always
    # names a resident page for the (dead) DMA
    bt_safe = jnp.clip(block_table, 0, P - 1).astype(jnp.int32)

    def page_map(b, p, bt_safe, bt, pos):
        del bt, pos
        return (bt_safe[b, p], 0, 0)

    def row_map(b, p, bt_safe, bt, pos):
        del bt_safe, bt, pos
        return (b, 0, 0)

    def fixed_map(b, p, bt_safe, bt, pos):
        del b, p, bt_safe, bt, pos
        return (0, 0)

    slab = pl.BlockSpec((1, ps, KV * hd), page_map)
    in_specs = [pl.BlockSpec((1, KV * R, KV * hd), row_map),
                pl.BlockSpec((KV * R, 1), fixed_map), slab, slab]
    operands = [q_bd, off, k_pages.reshape(P, ps, KV * hd),
                v_pages.reshape(P, ps, KV * hd)]
    if quantized:
        # scale pools drop the trailing hd dim but ride the SAME
        # scalar-prefetch block-table indirection as their values
        in_specs += [pl.BlockSpec((1, ps, KV), page_map)] * 2
        operands += [k_scales.astype(jnp.float32),
                     v_scales.astype(jnp.float32)]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(B, NP),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, KV * R, KV * hd), row_map),
        scratch_shapes=[
            pltpu.VMEM((KV * R, 1), jnp.float32),        # running max m
            pltpu.VMEM((KV * R, 1), jnp.float32),        # running sum l
            pltpu.VMEM((KV * R, KV * hd), jnp.float32),  # accumulator
        ],
    )
    # index maps see the CLAMPED table (DMA must name a resident page);
    # the kernel masks on the RAW table (unmapped stays invalid)
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, KV * R, KV * hd), q.dtype),
        interpret=resolve_interpret(interpret),
    )(bt_safe, block_table.astype(jnp.int32), pos.astype(jnp.int32),
      *operands)
    # keep each head's own lane block
    out = out.reshape(B, KV, R, KV, hd)
    return jnp.stack([out[:, h, :, h] for h in range(KV)], axis=1)


def paged_decode_attention_bkgd(q, k_pages, v_pages, block_table, lens, *,
                                k_scales=None, v_scales=None,
                                interpret=None):
    """q: (B,KV,group,hd); k_pages,v_pages: (P,ps,KV,hd);
    block_table: (B,NP) int32 (-1 = unmapped); lens: (B,) int32 — row b
    attends positions ``< lens[b]``. k_scales/v_scales: optional
    (P,ps,KV) f32 int8-pool scales. -> (B,KV,group,hd)."""
    group = q.shape[2]
    return paged_attention_rows(
        q, k_pages, v_pages, block_table, lens.astype(jnp.int32) - 1,
        jnp.zeros((group,), jnp.int32), k_scales=k_scales,
        v_scales=v_scales, interpret=interpret)
