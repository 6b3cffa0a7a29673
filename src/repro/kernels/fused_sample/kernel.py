"""One-pass token sampling for the decode loop (TPU Pallas).

The sampler the rollout engine's fused sample-and-write step runs on the
final-layer logits: ONE streaming pass over the vocab axis computes both
the sampled token (Gumbel-argmax over noise-perturbed logits — greedy
when the noise is zero) and its log-probability (online logsumexp of the
clean logits, plus the logit value carried with the running argmax). The
reference path materializes softmax intermediates and reads the logits
twice (categorical + token log-prob); this kernel reads each vocab block
once and keeps five scalars of state per row.

Temperature and top-p are applied to the logits BEFORE the kernel (they
are cheap elementwise/sort work and keeping them outside preserves exact
``common.sample_tokens`` semantics); the kernel itself is mode-agnostic.

In-kernel PRNG (``pltpu.prng_random_bits``) is unavailable in CPU
interpret mode, so the Gumbel noise is a regular operand generated with
``jax.random`` by the wrapper — which also makes temperature sampling
bitwise ``jax.random.categorical`` (same key, same noise). A TPU-only
follow-on can seed the PRNG in-kernel and drop the operand.

TPU tiling: the grid walks (row blocks, vocab blocks). A block is ``rb``
rows (the whole batch up to 8, else 8) by ``block_v`` lanes, a multiple
of 128; ``BLOCK_V`` keeps two double-buffered f32 operand blocks at
8 x 8192 (1 MiB in all) far inside the chip's scoped VMEM at any vocab.
Per-row state is (rb, 1) columns.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import resolve_interpret

NEG_INF = -1e30
BLOCK_V = 8192        # vocab lanes per block (a multiple of 128)
ROW_BLOCK = 8         # f32 sublanes per vreg


def _fused_sample_kernel(lg_ref, noise_ref, tok_ref, lp_ref,
                         m_ref, l_ref, bs_ref, bi_ref, bl_ref, *, bv,
                         n_blocks):
    v = pl.program_id(1)

    @pl.when(v == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        bs_ref[...] = jnp.full_like(bs_ref, NEG_INF)
        bi_ref[...] = jnp.zeros_like(bi_ref)
        bl_ref[...] = jnp.full_like(bl_ref, NEG_INF)

    lg = lg_ref[...].astype(jnp.float32)                    # (rb, bv)
    noise = noise_ref[...].astype(jnp.float32)

    # online logsumexp of the clean logits (the log-prob denominator)
    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, jnp.max(lg, axis=1, keepdims=True))
    l_ref[...] = l_ref[...] * jnp.exp(m_prev - m_new) \
        + jnp.sum(jnp.exp(lg - m_new), axis=1, keepdims=True)
    m_ref[...] = m_new

    # running argmax of the perturbed logits, carrying the winner's CLEAN
    # logit for the numerator. The block's first maximal lane, and strict
    # > across blocks, keep the earliest max on ties — the same tie-break
    # as a global argmax.
    score = lg + noise
    lane = jax.lax.broadcasted_iota(jnp.int32, score.shape, 1)
    bmax = jnp.max(score, axis=1, keepdims=True)            # (rb, 1)
    barg = jnp.min(jnp.where(score == bmax, lane, bv), axis=1,
                   keepdims=True)
    blog = jnp.sum(jnp.where(lane == barg, lg, 0.0), axis=1, keepdims=True)
    better = bmax > bs_ref[...]
    bs_ref[...] = jnp.where(better, bmax, bs_ref[...])
    bi_ref[...] = jnp.where(better, v * bv + barg, bi_ref[...])
    bl_ref[...] = jnp.where(better, blog, bl_ref[...])

    @pl.when(v == n_blocks - 1)
    def _finish():
        tok_ref[...] = bi_ref[...]
        lp_ref[...] = bl_ref[...] - (m_ref[...] + jnp.log(l_ref[...]))


def fused_sample_bkgd(lg, noise, *, block_v: int = BLOCK_V, interpret=None):
    """lg: (B, V) f32 logits (already tempered / top-p masked); noise:
    (B, V) f32 additive perturbation (Gumbel; zeros = greedy). Returns
    ``(tokens (B,) int32, logprobs (B,) f32)`` with ``tokens = argmax(lg
    + noise)`` and ``logprobs = lg[tok] - logsumexp(lg)``."""
    B, V = lg.shape
    assert block_v % 128 == 0, block_v
    bv = V if V <= block_v else block_v
    n_blocks = -(-V // bv)
    rb = B if B <= ROW_BLOCK else ROW_BLOCK
    pad_v = n_blocks * bv - V
    pad_b = -B % rb
    if pad_v or pad_b:
        # NEG_INF logit pad: zero mass in the logsumexp, never the argmax;
        # padded rows are dropped below
        lg = jnp.pad(lg, ((0, pad_b), (0, pad_v)), constant_values=NEG_INF)
        noise = jnp.pad(noise, ((0, pad_b), (0, pad_v)))
    kernel = functools.partial(_fused_sample_kernel, bv=bv,
                               n_blocks=n_blocks)

    def blk_map(r, v):
        return (r, v)

    def row_map(r, v):
        return (r, 0)

    col = lambda dtype: pltpu.VMEM((rb, 1), dtype)
    tok, lp = pl.pallas_call(
        kernel,
        grid=((B + pad_b) // rb, n_blocks),
        in_specs=[
            pl.BlockSpec((rb, bv), blk_map),
            pl.BlockSpec((rb, bv), blk_map),
        ],
        out_specs=[
            pl.BlockSpec((rb, 1), row_map),
            pl.BlockSpec((rb, 1), row_map),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B + pad_b, 1), jnp.int32),
            jax.ShapeDtypeStruct((B + pad_b, 1), jnp.float32),
        ],
        scratch_shapes=[
            col(jnp.float32),      # running max m
            col(jnp.float32),      # running sum l
            col(jnp.float32),      # best perturbed score
            col(jnp.int32),        # best token index
            col(jnp.float32),      # best clean logit
        ],
        interpret=resolve_interpret(interpret),
    )(lg.astype(jnp.float32), noise.astype(jnp.float32))
    return tok[:B, 0], lp[:B, 0]
