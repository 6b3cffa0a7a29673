"""Every Pallas kernel of the main path, sized to the model it serves,
beside its pure-jnp oracle.

One table serves two checks: the described-topology TPU compile test
(``tests/test_tpu_compile.py``, shapes only) and the on-chip
kernel-versus-oracle phase of ``chip_smoke.py`` (real arrays). Attention
kernels take their head layout from a dense config (qwen2-0.5b: H=14,
KV=2, hd=64, vocab 151936), the SSD scan from an SSM config (mamba2-370m).
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Callable, List

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig


@dataclass(frozen=True)
class KernelCase:
    name: str
    args: Callable         # key -> tuple of arrays
    kernel: Callable       # (*args, interpret=...) -> pytree of arrays
    ref: Callable          # (*args) -> pytree of arrays like ``kernel``'s
    tol: float             # bound on ``max_error(kernel, ref)``


def max_error(out, ref) -> float:
    """Largest |out - ref| over float leaves, relative to max |ref| of the
    leaf; integer leaves must match exactly (inf otherwise)."""
    worst = 0.0
    for a, b in zip(jax.tree.leaves(out), jax.tree.leaves(ref)):
        a, b = np.asarray(a), np.asarray(b)
        if a.shape != b.shape:
            return float("inf")
        if np.issubdtype(b.dtype, np.integer):
            if not np.array_equal(a, b):
                return float("inf")
            continue
        a, b = a.astype(np.float64), b.astype(np.float64)
        if not np.isfinite(a).all():
            return float("inf")
        scale = max(float(np.abs(b).max()), 1e-6)
        worst = max(worst, float(np.abs(a - b).max()) / scale)
    return worst


def _normal(key, shape, dtype=jnp.bfloat16):
    return jax.random.normal(key, shape, jnp.float32).astype(dtype)


def kernel_cases(attn: ModelConfig, ssm: ModelConfig, *, batch: int = 8,
                 seq: int = 1024, page_size: int = 16,
                 spec_k: int = 4) -> List[KernelCase]:
    """The eight kernel calls of the main path: flash forward and
    backward over ``seq`` tokens, dense decode over a ``4*seq`` cache,
    paged decode on bf16 and int8 pools of ``seq``-token rows,
    speculative verify of ``spec_k`` tokens, the fused sampler over the
    full vocab, and the SSD scan over ``seq`` tokens."""
    from repro.kernels.decode_attention import (decode_attention,
                                                decode_attention_ref)
    from repro.kernels.flash_attention import attention_ref, flash_attention
    from repro.kernels.fused_sample import fused_sample_ref
    from repro.kernels.fused_sample.kernel import fused_sample_bkgd
    from repro.kernels.paged_attention import (paged_decode_attention,
                                               paged_decode_attention_ref)
    from repro.kernels.spec_verify import (spec_verify_attention,
                                           spec_verify_attention_ref)
    from repro.kernels.ssd_scan import ssd_ref, ssd_scan
    from repro.models import paging

    B, S = batch, seq
    H, KV, hd, V = attn.n_heads, attn.n_kv_heads, attn.head_dim_, \
        attn.vocab_size
    ps = page_size
    NP = S // ps
    P = B * NP

    def flash_args(key):
        kq, kk, kv, kd = jax.random.split(key, 4)
        return (_normal(kq, (B, S, H, hd)), _normal(kk, (B, S, KV, hd)),
                _normal(kv, (B, S, KV, hd)), _normal(kd, (B, S, H, hd)))

    def flash_fwd(q, k, v, do, interpret=None):
        del do
        return flash_attention(q, k, v, True, 0, interpret)

    def flash_fwd_ref(q, k, v, do):
        del do
        return attention_ref(q, k, v, causal=True)

    def flash_bwd(q, k, v, do, interpret=None):
        _, vjp = jax.vjp(lambda *a: flash_attention(*a, True, 0, interpret),
                         q, k, v)
        return vjp(do)

    def flash_bwd_ref(q, k, v, do):
        _, vjp = jax.vjp(lambda *a: attention_ref(*a, causal=True), q, k, v)
        return vjp(do)

    def decode_args(key):
        kq, kk, kv, kl = jax.random.split(key, 4)
        S4 = 4 * S
        lens = jax.random.randint(kl, (B,), 1, S4 + 1)
        valid = jnp.arange(S4)[None, :] < lens[:, None]
        return (_normal(kq, (B, H, hd)), _normal(kk, (B, S4, KV, hd)),
                _normal(kv, (B, S4, KV, hd)), valid)

    def decode(q, k, v, valid, interpret=None):
        return decode_attention(q, k, v, valid, interpret=interpret)

    def pool_args(key, *, int8: bool):
        kq, kk, kv, kp, kl = jax.random.split(key, 5)
        kp_, vp_ = _normal(kk, (P, ps, KV, hd)), _normal(kv, (P, ps, KV, hd))
        perm = jax.random.permutation(kp, P).reshape(B, NP).astype(jnp.int32)
        lens = jax.random.randint(kl, (B,), 1, S + 1)
        # unmapped tail pages (-1) past each row's live length
        bt = jnp.where(jnp.arange(NP)[None, :] < -(-lens // ps)[:, None],
                       perm, -1)
        q = _normal(kq, (B, H, hd))
        if int8:
            (qk, sk), (qv, sv) = paging.quantize_kv(kp_), \
                paging.quantize_kv(vp_)
            return q, qk, qv, bt, lens, sk, sv
        return q, kp_, vp_, bt, lens

    def paged(q, k, v, bt, lens, ks=None, vs=None, interpret=None):
        return paged_decode_attention(q, k, v, bt, lens, k_scales=ks,
                                      v_scales=vs, interpret=interpret)

    def paged_ref(q, k, v, bt, lens, ks=None, vs=None):
        return paged_decode_attention_ref(q, k, v, bt, lens, ks, vs)

    def spec_args(key):
        _, kp_, vp_, bt, lens = pool_args(key, int8=False)
        pos = jnp.maximum(lens - spec_k, 0)
        q = _normal(jax.random.fold_in(key, 1), (B, spec_k, H, hd))
        return q, kp_, vp_, bt, pos

    def spec(q, k, v, bt, pos, interpret=None):
        return spec_verify_attention(q, k, v, bt, pos, interpret=interpret)

    def sample_args(key):
        kl, kn = jax.random.split(key)
        lg = jax.random.normal(kl, (B, V), jnp.float32) * 3.0
        return lg, jax.random.gumbel(kn, (B, V), jnp.float32)

    def sample(lg, noise, interpret=None):
        return fused_sample_bkgd(lg, noise, interpret=interpret)

    s_cfg = ssm.ssm

    def ssd_args(key):
        kx, kd, ka, kb, kc = jax.random.split(key, 5)
        b, h, p = 2, s_cfg.n_heads, s_cfg.head_dim
        g, n = s_cfg.n_groups, s_cfg.state_size
        dt = jax.nn.softplus(jax.random.normal(kd, (b, S, h)))
        A = -jnp.exp(jax.random.normal(ka, (h,)) * 0.3)
        return (_normal(kx, (b, S, h, p)) * 0.5, dt, A,
                _normal(kb, (b, S, g, n)) * 0.5,
                _normal(kc, (b, S, g, n)) * 0.5)

    def ssd(x, dt, A, Bm, Cm, interpret=None):
        return ssd_scan(x, dt, A, Bm, Cm, s_cfg.chunk_size,
                        interpret=interpret)

    def ssd_oracle(x, dt, A, Bm, Cm):
        return ssd_ref(x, dt, A, Bm, Cm, s_cfg.chunk_size)

    return [
        KernelCase("flash_fwd", flash_args, flash_fwd, flash_fwd_ref, 2e-2),
        KernelCase("flash_bwd", flash_args, flash_bwd, flash_bwd_ref, 2e-2),
        KernelCase("decode", decode_args, decode, decode_attention_ref, 2e-2),
        KernelCase("paged_bf16", lambda k: pool_args(k, int8=False), paged,
                   paged_ref, 2e-2),
        KernelCase("paged_int8", lambda k: pool_args(k, int8=True), paged,
                   paged_ref, 2e-2),
        KernelCase("spec_verify", spec_args, spec, spec_verify_attention_ref,
                   2e-2),
        KernelCase("fused_sample", sample_args, sample, fused_sample_ref,
                   1e-4),
        KernelCase("ssd_scan", ssd_args, ssd, ssd_oracle, 2e-2),
    ]
