"""Jitted public wrapper for the fused sampling kernel.

``fused_sample_tokens`` is a drop-in for ``rl.engine.common.
sample_tokens`` (same key discipline, same greedy/temperature semantics,
plus top-p) built on the one-pass kernel. Temperature sampling draws the
SAME Gumbel noise ``jax.random.categorical`` derives from the key, so
fused and reference sampling agree token-for-token under an identical
rng stream.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels.fused_sample.kernel import NEG_INF, fused_sample_bkgd


def apply_top_p(lg, top_p: float):
    """Nucleus filter on (B, V) f32 logits: keep the smallest set of
    top-probability tokens whose cumulative mass reaches ``top_p`` (a
    token survives iff the mass strictly above it is < top_p, so the
    top-1 token always survives); everything else goes to ``NEG_INF``.
    Downstream softmaxes renormalize over the survivors automatically."""
    lg = jnp.asarray(lg).astype(jnp.float32)
    sorted_lg = jnp.sort(lg, axis=-1)[..., ::-1]
    probs = jax.nn.softmax(sorted_lg, axis=-1)
    csum = jnp.cumsum(probs, axis=-1)
    keep = (csum - probs) < top_p                  # mass above this token
    thr = jnp.min(jnp.where(keep, sorted_lg, jnp.inf), axis=-1,
                  keepdims=True)
    return jnp.where(lg >= thr, lg, NEG_INF)


def fused_sample_tokens(rng, logits, temperature: float, *,
                        top_p: float = 1.0, interpret=None):
    """Sample next tokens from (B, V) logits in one kernel pass. Returns
    ``(tokens, logprobs)`` — ``common.sample_tokens`` semantics:
    ``temperature <= 0`` is greedy argmax with log-probs from the
    untempered distribution (rng unused, top_p ignored); otherwise
    Gumbel-argmax over ``logits / temperature`` (token-identical to
    ``jax.random.categorical`` on the same key), with an optional
    nucleus (top-p) filter applied before sampling."""
    lg = jnp.asarray(logits).astype(jnp.float32)
    if temperature <= 0.0:
        noise = jnp.zeros_like(lg)
    else:
        lg = lg / temperature
        if top_p < 1.0:
            lg = apply_top_p(lg, top_p)
        noise = jax.random.gumbel(rng, lg.shape, jnp.float32)
    return fused_sample_bkgd(lg, noise, interpret=interpret)
