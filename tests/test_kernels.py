"""Per-kernel correctness: Pallas (interpret mode) vs pure-jnp oracles,
swept over shapes and dtypes (the brief's per-kernel allclose gate)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.decode_attention import (decode_attention,
                                            decode_attention_ref)
from repro.kernels.flash_attention import attention_ref, flash_attention
from repro.kernels.paged_attention import (paged_decode_attention,
                                           paged_decode_attention_ref)
from repro.kernels.ssd_scan import ssd_ref, ssd_scan

TOLS = {jnp.float32: dict(atol=2e-5, rtol=1e-4),
        jnp.bfloat16: dict(atol=2e-2, rtol=2e-2)}


def _rand(key, shape, dtype):
    return jax.random.normal(key, shape, jnp.float32).astype(dtype)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("B,S,H,KV,hd,window", [
    (2, 256, 4, 2, 64, 0),
    (1, 128, 8, 8, 32, 0),
    (2, 256, 4, 1, 64, 64),      # MQA + sliding window
    (1, 512, 2, 2, 128, 128),
    (1, 64, 14, 2, 64, 0),       # qwen2's non-pow2 head count
])
def test_flash_attention_matches_ref(B, S, H, KV, hd, window, dtype, rng):
    q = _rand(rng, (B, S, H, hd), dtype)
    k = _rand(jax.random.fold_in(rng, 1), (B, S, KV, hd), dtype)
    v = _rand(jax.random.fold_in(rng, 2), (B, S, KV, hd), dtype)
    out = flash_attention(q, k, v, True, window, True)
    expect = attention_ref(q, k, v, causal=True, window=window)
    assert out.shape == expect.shape and out.dtype == expect.dtype
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(expect, np.float32),
        **TOLS[dtype])


@pytest.mark.parametrize("B,S,H,KV,hd,window", [
    (1, 64, 4, 2, 32, 0),
    (2, 128, 4, 1, 64, 0),       # MQA
    (1, 128, 2, 2, 32, 32),      # sliding window
    (1, 64, 6, 3, 32, 0),        # group=2
])
def test_flash_attention_grad_matches_ref(B, S, H, KV, hd, window, rng):
    """The PALLAS two-pass backward (bwd_kernel.py) agrees with
    differentiating the unfused oracle — dq, dk and dv."""
    q = _rand(rng, (B, S, H, hd), jnp.float32)
    k = _rand(jax.random.fold_in(rng, 1), (B, S, KV, hd), jnp.float32)
    v = _rand(jax.random.fold_in(rng, 2), (B, S, KV, hd), jnp.float32)
    f_k = lambda q, k, v: jnp.sum(
        flash_attention(q, k, v, True, window, True) ** 2)
    f_r = lambda q, k, v: jnp.sum(
        attention_ref(q, k, v, causal=True, window=window) ** 2)
    gk = jax.grad(f_k, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(f_r, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gk, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=2e-4, rtol=1e-3)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("B,S,H,KV,hd", [
    (2, 1024, 4, 2, 64),
    (1, 2048, 8, 8, 32),
    (3, 512, 6, 2, 128),
    (2, 256, 14, 2, 64),         # qwen2 heads
])
def test_decode_attention_matches_ref(B, S, H, KV, hd, dtype, rng):
    q = _rand(rng, (B, H, hd), dtype)
    k = _rand(jax.random.fold_in(rng, 1), (B, S, KV, hd), dtype)
    v = _rand(jax.random.fold_in(rng, 2), (B, S, KV, hd), dtype)
    pos = jax.random.randint(jax.random.fold_in(rng, 3), (B,), 1, S)
    valid = jnp.arange(S)[None, :] <= pos[:, None]
    out = decode_attention(q, k, v, valid, interpret=True)
    expect = decode_attention_ref(q, k, v, valid)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(expect, np.float32),
        **TOLS[dtype])


def test_decode_attention_fully_masked_rows_are_finite(rng):
    B, S, H, KV, hd = 2, 256, 4, 2, 32
    q = _rand(rng, (B, H, hd), jnp.float32)
    k = _rand(jax.random.fold_in(rng, 1), (B, S, KV, hd), jnp.float32)
    v = _rand(jax.random.fold_in(rng, 2), (B, S, KV, hd), jnp.float32)
    valid = jnp.zeros((B, S), bool)
    out = decode_attention(q, k, v, valid, interpret=True)
    assert bool(jnp.isfinite(out).all())


# ---------------------------------------------------------------------------
# Paged decode attention (block-table gather inside the kernel grid)
# ---------------------------------------------------------------------------

def _paged_case(rng, B, NP, P, ps, H, KV, hd, dtype, *, lens=None):
    """Random paged-attention inputs with a NON-CONTIGUOUS block table
    (pages drawn by permutation, so consecutive slot positions live on
    scattered pool pages) and ragged per-row lengths whose last page is
    partially filled."""
    q = _rand(rng, (B, H, hd), dtype)
    kp = _rand(jax.random.fold_in(rng, 1), (P, ps, KV, hd), dtype)
    vp = _rand(jax.random.fold_in(rng, 2), (P, ps, KV, hd), dtype)
    perm = jax.random.permutation(jax.random.fold_in(rng, 3),
                                  P)[:B * NP].reshape(B, NP)
    if lens is None:
        lens = jax.random.randint(jax.random.fold_in(rng, 4), (B,), 1,
                                  NP * ps + 1)
    lens = jnp.asarray(lens, jnp.int32)
    npages = -(-lens // ps)                # mapped pages per row
    bt = jnp.where(jnp.arange(NP)[None, :] < npages[:, None], perm, -1)
    return q, kp, vp, bt, lens


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("B,NP,P,ps,H,KV,hd", [
    (2, 4, 16, 8, 4, 2, 64),
    (3, 8, 32, 16, 8, 8, 32),
    (2, 4, 8, 8, 14, 2, 64),     # qwen2's non-pow2 head count, exact pool
    (1, 2, 64, 128, 2, 1, 128),  # MQA, big pages, mostly-unmapped pool
])
def test_paged_decode_attention_matches_ref(B, NP, P, ps, H, KV, hd, dtype,
                                            rng):
    q, kp, vp, bt, lens = _paged_case(rng, B, NP, P, ps, H, KV, hd, dtype)
    out = paged_decode_attention(q, kp, vp, bt, lens, interpret=True)
    expect = paged_decode_attention_ref(q, kp, vp, bt, lens)
    assert out.shape == expect.shape and out.dtype == expect.dtype
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(expect, np.float32),
        **TOLS[dtype])


def test_paged_decode_attention_partial_last_page(rng):
    """Pin the ragged boundary explicitly: one full-page row, one row one
    token into a fresh page, one row one token short of a page."""
    B, NP, P, ps, H, KV, hd = 3, 4, 16, 8, 4, 2, 32
    lens = [ps * 2, ps + 1, ps - 1]
    q, kp, vp, bt, lens = _paged_case(rng, B, NP, P, ps, H, KV, hd,
                                      jnp.float32, lens=lens)
    out = paged_decode_attention(q, kp, vp, bt, lens, interpret=True)
    expect = paged_decode_attention_ref(q, kp, vp, bt, lens)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expect),
                               **TOLS[jnp.float32])


def test_paged_decode_attention_matches_dense_kernel(rng):
    """The paged kernel agrees with the DENSE decode kernel on the same
    logical cache: scatter a dense (B,S,KV,hd) cache into pool pages
    through a shuffled block table and compare (the acceptance gate for
    swapping cache layouts under the engine)."""
    B, S, H, KV, hd, ps = 2, 128, 4, 2, 64, 16
    NP = S // ps
    P = B * NP + 4                          # spare pages stay unmapped
    q = _rand(rng, (B, H, hd), jnp.float32)
    k = _rand(jax.random.fold_in(rng, 1), (B, S, KV, hd), jnp.float32)
    v = _rand(jax.random.fold_in(rng, 2), (B, S, KV, hd), jnp.float32)
    pos = jax.random.randint(jax.random.fold_in(rng, 3), (B,), 1, S)
    valid = jnp.arange(S)[None, :] <= pos[:, None]

    perm = jax.random.permutation(jax.random.fold_in(rng, 4),
                                  P)[:B * NP].reshape(B, NP)
    kp = jnp.zeros((P, ps, KV, hd), jnp.float32).at[perm].set(
        k.reshape(B, NP, ps, KV, hd))
    vp = jnp.zeros((P, ps, KV, hd), jnp.float32).at[perm].set(
        v.reshape(B, NP, ps, KV, hd))

    dense = decode_attention(q, k, v, valid, interpret=True)
    paged = paged_decode_attention(q, kp, vp, perm, pos + 1,
                                   interpret=True)
    np.testing.assert_allclose(np.asarray(paged), np.asarray(dense),
                               atol=2e-5, rtol=1e-4)


def test_paged_decode_attention_fully_masked_rows_are_finite(rng):
    B, NP, P, ps, H, KV, hd = 2, 2, 8, 8, 4, 2, 32
    q, kp, vp, bt, _ = _paged_case(rng, B, NP, P, ps, H, KV, hd,
                                   jnp.float32)
    lens = jnp.zeros((B,), jnp.int32)
    out = paged_decode_attention(q, kp, vp, bt, lens, interpret=True)
    assert bool(jnp.isfinite(out).all())
    ref = paged_decode_attention_ref(q, kp, vp, bt, lens)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))


def test_page_allocator_rank_matching():
    """alloc/release invariants: distinct pages per needing row, sentinel
    on exhaustion, released pages immediately reusable."""
    from repro.models import paging
    rc = jnp.zeros((4,), jnp.int32)                  # refcount 0 == free
    pages, rc = paging.alloc_pages(rc, jnp.array([True, False, True]))
    assert np.asarray(pages)[1] == 4                 # sentinel: no need
    assert len({int(pages[0]), int(pages[2])}) == 2  # distinct pages
    assert int(paging.pages_in_use(rc)) == 2
    # exhaust: 3 needing rows, 2 free pages -> one sentinel
    pages2, rc = paging.alloc_pages(rc, jnp.array([True, True, True]))
    got = np.asarray(pages2)
    assert (got < 4).sum() == 2 and (got == 4).sum() == 1
    assert int(paging.pages_in_use(rc)) == 4
    # release row 0's pages through a block table; pool drains back
    bt = jnp.array([[int(pages[0]), int(pages[2])], [-1, -1]], jnp.int32)
    rc, bt = paging.release_pages(rc, bt, jnp.array([True, False]))
    assert int(paging.pages_in_use(rc)) == 2
    assert (np.asarray(bt)[0] == -1).all()
    pages3, _ = paging.alloc_pages(rc, jnp.array([True, True]))
    assert (np.asarray(pages3) < 4).all()            # reuse succeeded


def test_paged_prefill_matches_dense_prefill(rng):
    """Prompt pass parity across cache layouts: same last-token logits,
    and the pages hold exactly the dense cache's K/V (including a
    PARTIALLY FILLED last page: S % page_size != 0 exercises the
    pad-and-scatter write). Continued decode stays in lockstep across the
    prefill/decode boundary."""
    import jax.numpy as jnp
    from repro.configs.base import get_smoke_config
    from repro.models.registry import build_model

    cfg = get_smoke_config("qwen2-0.5b")
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    B, S, CAP, ps = 2, 21, 32, 8             # 21 = 2 full pages + 5
    toks = jax.random.randint(rng, (B, CAP), 0, cfg.vocab_size)
    ld, dcache = model.prefill(params, toks[:, :S], model.init_cache(B, CAP))
    lp, pcache = model.prefill(
        params, toks[:, :S],
        model.init_cache(B, CAP, layout="paged", page_size=ps))
    np.testing.assert_allclose(np.asarray(ld, np.float32),
                               np.asarray(lp, np.float32),
                               atol=2e-2, rtol=2e-2)
    # cache contents: gather the pages back into the dense layout
    bt = np.asarray(pcache.block_table)
    kp = np.asarray(pcache.kv.k, np.float32)  # (L, P, ps, KV, hd)
    kd = np.asarray(dcache.kv.k, np.float32)  # (L, B, CAP, KV, hd)
    for b in range(B):
        for s in range(S):
            page, off = bt[b, s // ps], s % ps
            assert page >= 0
            np.testing.assert_array_equal(kp[:, page, off], kd[:, b, s])
    assert int((pcache.refcount > 0).sum()) == B * (-(-S // ps))
    # decode across the prefill boundary (first step lands mid-page)
    for t in range(S, CAP):
        ld, dcache = model.decode_step(params, toks[:, t], dcache)
        lp, pcache = model.decode_step(params, toks[:, t], pcache)
        np.testing.assert_allclose(np.asarray(ld, np.float32),
                                   np.asarray(lp, np.float32),
                                   atol=2e-2, rtol=2e-2)


def test_paged_cache_exhaustion_recovery_scrubs_recycled_pages(rng):
    """Transient pool exhaustion drops a row's writes while its pos keeps
    advancing; when a freed page is later mapped mid-row, the recycled
    contents below the fill line must be scrubbed — otherwise the freed
    episode's K/V would sit inside the new row's validity window."""
    import jax.numpy as jnp
    from repro.configs.base import get_smoke_config
    from repro.models.registry import build_model
    from repro.rl.engine import paging as epaging

    cfg = get_smoke_config("qwen2-0.5b")
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    B, ps = 2, 4
    cache = model.init_cache(B, 16, layout="paged", page_size=ps, n_pages=1)
    # poison the pool so any stale read is detectable
    cache = cache._replace(kv=cache.kv._replace(
        k=jnp.full_like(cache.kv.k, 100.0),
        v=jnp.full_like(cache.kv.v, 100.0)))
    toks = jax.random.randint(rng, (B, 8), 0, cfg.vocab_size)
    for t in range(3):     # row 0 owns the only page; row 1's writes drop
        _, cache = model.decode_step(params, toks[:, t], cache)
    assert int(cache.block_table[1, 0]) == -1 and int(cache.pos[1]) == 3
    # engine refill frees row 0's page; frozen row 0 leaves the single
    # free page to row 1, which maps it MID-ROW (woff = 3)
    cache = epaging.release_slot_pages(cache, jnp.array([True, False]))
    logits, cache = model.decode_step(params, toks[:, 3], cache,
                                      advance=jnp.array([False, True]))
    assert int(cache.block_table[1, 0]) == 0
    k_page = np.asarray(cache.kv.k[0, 0], np.float32)      # (ps, KV, hd)
    assert (np.abs(k_page[:3]) < 50).all(), "stale K/V survived the scrub"
    assert bool(jnp.isfinite(logits.astype(jnp.float32)).all())


# ---------------------------------------------------------------------------
# Int8-quantized pages: dequantization fused inside the paged decode kernel
# ---------------------------------------------------------------------------

def _int8_paged_case(rng, B, NP, P, ps, H, KV, hd, *, lens=None):
    """A `_paged_case` whose pools are int8-quantized with per-entry
    scales (the paged pool's kv_dtype="int8" storage format)."""
    from repro.models import paging
    q, kp, vp, bt, lens = _paged_case(rng, B, NP, P, ps, H, KV, hd,
                                      jnp.float32, lens=lens)
    qk, sk = paging.quantize_kv(kp)
    qv, sv = paging.quantize_kv(vp)
    return q, qk, qv, sk, sv, bt, lens


@pytest.mark.parametrize("B,NP,P,ps,H,KV,hd", [
    (2, 4, 16, 8, 4, 2, 64),
    (3, 8, 32, 16, 8, 8, 32),
    (2, 4, 8, 8, 14, 2, 64),     # qwen2's non-pow2 head count, exact pool
    (1, 2, 64, 128, 2, 1, 128),  # MQA, big pages, mostly-unmapped pool
])
def test_paged_decode_attention_int8_matches_ref(B, NP, P, ps, H, KV, hd,
                                                 rng):
    """In-kernel dequant vs the pure-jnp oracle that materializes the
    dequantized pool up front — ragged lens, non-contiguous block table."""
    q, qk, qv, sk, sv, bt, lens = _int8_paged_case(rng, B, NP, P, ps, H,
                                                   KV, hd)
    out = paged_decode_attention(q, qk, qv, bt, lens, k_scales=sk,
                                 v_scales=sv, interpret=True)
    expect = paged_decode_attention_ref(q, qk, qv, bt, lens, k_scales=sk,
                                        v_scales=sv)
    assert out.shape == expect.shape and out.dtype == expect.dtype
    np.testing.assert_allclose(np.asarray(out), np.asarray(expect),
                               **TOLS[jnp.float32])


def test_paged_decode_attention_int8_partial_last_page(rng):
    """Pin the ragged boundary for quantized pages: one full-page row,
    one row one token into a fresh page, one a token short of a page."""
    B, NP, P, ps, H, KV, hd = 3, 4, 16, 8, 4, 2, 32
    lens = [ps * 2, ps + 1, ps - 1]
    q, qk, qv, sk, sv, bt, lens = _int8_paged_case(rng, B, NP, P, ps, H,
                                                   KV, hd, lens=lens)
    out = paged_decode_attention(q, qk, qv, bt, lens, k_scales=sk,
                                 v_scales=sv, interpret=True)
    expect = paged_decode_attention_ref(q, qk, qv, bt, lens, k_scales=sk,
                                        v_scales=sv)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expect),
                               **TOLS[jnp.float32])


def test_paged_decode_attention_int8_bitwise_vs_dequantized_pool(rng):
    """The fusion contract: in-kernel dequant is BITWISE identical to
    running the same kernel on pools materialized through
    ``paging.dequantize_kv`` — the fusion only moves where the multiply
    happens, never what is computed."""
    from repro.models import paging
    B, NP, P, ps, H, KV, hd = 2, 4, 16, 8, 4, 2, 64
    q, qk, qv, sk, sv, bt, lens = _int8_paged_case(rng, B, NP, P, ps, H,
                                                   KV, hd)
    fused = paged_decode_attention(q, qk, qv, bt, lens, k_scales=sk,
                                   v_scales=sv, interpret=True)
    materialized = paged_decode_attention(
        q, paging.dequantize_kv(qk, sk), paging.dequantize_kv(qv, sv),
        bt, lens, interpret=True)
    np.testing.assert_array_equal(np.asarray(fused),
                                  np.asarray(materialized))


def test_paged_decode_attention_int8_fully_masked_rows_are_finite(rng):
    B, NP, P, ps, H, KV, hd = 2, 2, 8, 8, 4, 2, 32
    q, qk, qv, sk, sv, bt, _ = _int8_paged_case(rng, B, NP, P, ps, H, KV,
                                                hd)
    lens = jnp.zeros((B,), jnp.int32)
    out = paged_decode_attention(q, qk, qv, bt, lens, k_scales=sk,
                                 v_scales=sv, interpret=True)
    assert bool(jnp.isfinite(out).all())


# ---------------------------------------------------------------------------
# Fused sample kernel (one streaming pass: token + logprob)
# ---------------------------------------------------------------------------

def test_fused_sample_kernel_matches_ref_exactly(rng):
    """One-pass Gumbel-argmax + online logsumexp vs the two-read oracle:
    tokens exact, logprobs to fp accumulation order."""
    from repro.kernels.fused_sample import fused_sample_ref
    from repro.kernels.fused_sample.kernel import fused_sample_bkgd
    B, V = 4, 2500                           # V % block_v != 0 (pad path)
    lg = jax.random.normal(rng, (B, V), jnp.float32) * 3.0
    noise = jax.random.gumbel(jax.random.fold_in(rng, 1), (B, V),
                              jnp.float32)
    tok, lp = fused_sample_bkgd(lg, noise, block_v=1024, interpret=True)
    tok_r, lp_r = fused_sample_ref(lg, noise)
    np.testing.assert_array_equal(np.asarray(tok), np.asarray(tok_r))
    np.testing.assert_allclose(np.asarray(lp), np.asarray(lp_r),
                               atol=1e-5, rtol=1e-5)


def test_fused_sample_greedy_matches_reference_sampler(rng):
    """temperature <= 0: greedy argmax with untempered logprobs — same
    contract as ``common.sample_tokens``, token-exact."""
    from repro.kernels.fused_sample import fused_sample_tokens
    from repro.rl.engine import common
    lg = jax.random.normal(rng, (5, 977), jnp.float32) * 2.0
    tok, lp = fused_sample_tokens(rng, lg, 0.0, interpret=True)
    tok_r, lp_r = common.sample_tokens(rng, lg, 0.0)
    np.testing.assert_array_equal(np.asarray(tok), np.asarray(tok_r))
    np.testing.assert_allclose(np.asarray(lp), np.asarray(lp_r),
                               atol=1e-5, rtol=1e-5)


def test_fused_sample_temperature_matches_categorical(rng):
    """Temperature sampling reuses the Gumbel noise jax.random.categorical
    derives from the key, so fused and reference sampling pick the SAME
    token on the same rng stream."""
    from repro.kernels.fused_sample import fused_sample_tokens
    from repro.rl.engine import common
    lg = jax.random.normal(rng, (6, 512), jnp.float32) * 2.0
    for i in range(4):
        key = jax.random.fold_in(rng, i)
        tok, lp = fused_sample_tokens(key, lg, 0.7, interpret=True)
        tok_r, lp_r = common.sample_tokens(key, lg, 0.7)
        np.testing.assert_array_equal(np.asarray(tok), np.asarray(tok_r))
        np.testing.assert_allclose(np.asarray(lp), np.asarray(lp_r),
                                   atol=1e-5, rtol=1e-5)


def test_fused_sample_top_p_filters_to_nucleus(rng):
    """With top-p active, sampled tokens always come from the nucleus
    (the smallest top-probability set reaching the mass); a tiny top_p
    degenerates to greedy (top-1 always survives the filter)."""
    from repro.kernels.fused_sample import apply_top_p, fused_sample_tokens
    lg = jax.random.normal(rng, (4, 257), jnp.float32) * 4.0
    nucleus = np.asarray(apply_top_p(lg / 0.8, 0.6)) > -1e29
    for i in range(8):
        key = jax.random.fold_in(rng, i)
        tok, _ = fused_sample_tokens(key, lg, 0.8, top_p=0.6,
                                     interpret=True)
        assert all(nucleus[b, t] for b, t in enumerate(np.asarray(tok)))
    tok, _ = fused_sample_tokens(rng, lg, 0.8, top_p=1e-6, interpret=True)
    np.testing.assert_array_equal(np.asarray(tok),
                                  np.argmax(np.asarray(lg), axis=-1))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("b,s,h,g,p,n,chunk", [
    (2, 256, 4, 1, 32, 16, 64),
    (1, 512, 8, 2, 64, 32, 128),
    (2, 100, 4, 4, 16, 8, 32),   # ragged: s % chunk != 0 (pad path)
    (1, 128, 2, 1, 64, 128, 64), # wide state (mamba2-370m n=128)
])
def test_ssd_scan_matches_ref(b, s, h, g, p, n, chunk, dtype, rng):
    x = _rand(rng, (b, s, h, p), dtype) * 0.5
    dt = jax.nn.softplus(
        jax.random.normal(jax.random.fold_in(rng, 1), (b, s, h)))
    A = -jnp.exp(jax.random.normal(jax.random.fold_in(rng, 2), (h,)) * 0.3)
    B = _rand(jax.random.fold_in(rng, 3), (b, s, g, n), dtype) * 0.5
    C = _rand(jax.random.fold_in(rng, 4), (b, s, g, n), dtype) * 0.5
    y, fin = ssd_scan(x, dt, A, B, C, chunk, interpret=True)
    ye, fine = ssd_ref(x, dt, A, B, C, chunk)
    np.testing.assert_allclose(np.asarray(y, np.float32),
                               np.asarray(ye, np.float32), **TOLS[dtype])
    np.testing.assert_allclose(np.asarray(fin), np.asarray(fine),
                               atol=5e-3, rtol=5e-3)


def test_ssd_scan_equals_sequential_recurrence(rng):
    """Chunked dual form == naive per-token recurrence (independent of the
    chunked oracle — catches shared bugs in both chunked paths)."""
    from repro.models.mamba import ssd_decode_step
    b, s, h, g, p, n = 1, 32, 2, 1, 8, 4
    x = _rand(rng, (b, s, h, p), jnp.float32)
    dt = jax.nn.softplus(
        jax.random.normal(jax.random.fold_in(rng, 1), (b, s, h)))
    A = -jnp.exp(jax.random.normal(jax.random.fold_in(rng, 2), (h,)) * 0.3)
    B = _rand(jax.random.fold_in(rng, 3), (b, s, g, n), jnp.float32)
    C = _rand(jax.random.fold_in(rng, 4), (b, s, g, n), jnp.float32)
    y_k, fin_k = ssd_scan(x, dt, A, B, C, 8, interpret=True)

    state = jnp.zeros((b, h, p, n), jnp.float32)
    ys = []
    for t in range(s):
        y_t, state = ssd_decode_step(state, x[:, t], dt[:, t], A,
                                     B[:, t], C[:, t])
        ys.append(y_t)
    y_seq = jnp.stack(ys, axis=1)
    np.testing.assert_allclose(np.asarray(y_k), np.asarray(y_seq),
                               atol=1e-4, rtol=1e-3)
    np.testing.assert_allclose(np.asarray(fin_k), np.asarray(state),
                               atol=1e-4, rtol=1e-3)


def test_ring_kv_cache_matches_full_cache_window(rng):
    """Sliding-window decode through the O(window) ring buffer produces the
    same logits as decoding with a full-length cache (§Perf-A feature)."""
    from dataclasses import replace
    import numpy as np
    from repro.configs.base import get_smoke_config
    from repro.models.registry import build_model

    base = get_smoke_config("qwen2-0.5b")
    W = 8
    cfg_win = replace(base, sliding_window=W)
    cfg_full = replace(base, sliding_window=0)
    m_win = build_model(cfg_win)
    m_full = build_model(cfg_full)
    params = m_win.init(jax.random.PRNGKey(0))

    B, total = 2, 24
    toks = jax.random.randint(rng, (B, total), 0, base.vocab_size)
    # ring path: cache allocated at W slots even though context runs to 24
    cache_w = m_win.init_cache(B, total)
    assert cache_w.kv.k.shape[2] == W          # ring allocation
    # reference: full cache, windowed mask applied over all slots
    cache_f = m_full.init_cache(B, total)

    lw = lf = None
    for t in range(total):
        lw, cache_w = m_win.decode_step(params, toks[:, t], cache_w)
        lf_t, cache_f = m_full.decode_step(params, toks[:, t], cache_f)
        # full-cache model has window=0 (attends to everything); emulate the
        # window by comparing only while t < W where they must agree
        if t < W - 1:
            np.testing.assert_allclose(
                np.asarray(lw, np.float32), np.asarray(lf_t, np.float32),
                atol=0.02, rtol=0.02)
    # beyond W steps: ring logits still finite and cache pos tracks t
    assert bool(jnp.isfinite(lw.astype(jnp.float32)).all())
    assert int(cache_w.pos[0]) == total


@pytest.mark.parametrize("name", [
    "flash_fwd", "flash_bwd", "decode", "paged_bf16", "paged_int8",
    "spec_verify", "fused_sample", "ssd_scan"])
def test_kernel_case_matches_oracle_at_smoke_widths(name, rng):
    """The kernel-versus-oracle table that the on-chip smoke check runs at
    full width, run here in interpret mode at the smoke configs' widths."""
    from repro.configs.base import get_smoke_config
    from repro.kernels.cases import kernel_cases, max_error
    cases = {c.name: c for c in kernel_cases(
        get_smoke_config("qwen2-0.5b"), get_smoke_config("mamba2-370m"),
        batch=2, seq=128, page_size=16)}
    case = cases[name]
    args = case.args(rng)
    err = max_error(case.kernel(*args, interpret=True), case.ref(*args))
    assert err <= case.tol, (name, err)


@pytest.mark.parametrize("backend,expect", [
    ("cpu", True), ("tpu", False), ("gpu", RuntimeError)])
def test_interpret_mode_follows_backend(backend, expect, monkeypatch):
    """Kernels interpret on CPU, compile on TPU, and refuse anything else
    rather than silently falling back to the interpreter."""
    from repro import kernels
    monkeypatch.setattr(kernels.jax, "default_backend", lambda: backend)
    if expect is RuntimeError:
        with pytest.raises(RuntimeError, match=backend):
            kernels.default_interpret()
    else:
        assert kernels.default_interpret() is expect
        assert kernels.resolve_interpret(None) is expect
        assert kernels.resolve_interpret(not expect) is (not expect)
