"""Shared transformer layers: RMSNorm, RoPE, GQA attention, SwiGLU MLP.

All functions are pure; parameters are plain dict pytrees declared with
``ParamDef`` (see param.py). Attention supports:
  - full causal (train / prefill)
  - KV-cache decode (one new token against a seq_len cache)
  - sliding-window decode (windowed dynamic-slice over the cache) — the
    sub-quadratic dense-arch variant used for the long_500k shape
  - GQA with non-divisible head counts (kv heads broadcast via reshape)

Softmax and normalization accumulate in float32; matmuls run in the model
dtype (bfloat16 by default) to match the MXU-native numerics of the TPU
target.
"""
from __future__ import annotations

from typing import Any, NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax import lax

from repro.models import paging
from repro.models.param import pdef

NEG_INF = -1e30


# ---------------------------------------------------------------------------
# Activation sharding constraints (MaxText-style logical annotations)
# ---------------------------------------------------------------------------

def _ambient_mesh():
    """The mesh installed by ``jax.set_mesh(mesh)`` around jit/lower, or
    None. Model code runs unchanged on a single device (no mesh -> no-op)."""
    m = jax.sharding.get_abstract_mesh()
    return None if m.empty else m


def constrain(x, *axes):
    """Constrain activation sharding by logical dim names.

    axes: one entry per dim — 'batch' (→ ('pod','data')), 'model', or None.
    Without this, XLA's sharding propagation gives up inside scanned layer
    bodies and replicates the batch (empirically: 256-row attention scores
    per device on the 16x16 mesh). Divisibility fallback replicates."""
    mesh = _ambient_mesh()
    if mesh is None:
        return x
    from jax.sharding import NamedSharding, PartitionSpec
    spec = []
    for dim, a in zip(x.shape, axes):
        if a == "batch":
            ba = tuple(n for n in ("pod", "data") if n in mesh.axis_names)
            size = 1
            for n in ba:
                size *= mesh.shape[n]
            if ba and size > 1 and dim % size == 0:
                spec.append(ba if len(ba) > 1 else ba[0])
            else:
                spec.append(None)
        elif (a == "model" and "model" in mesh.axis_names
                and dim % mesh.shape["model"] == 0 and dim > 0):
            spec.append("model")
        else:
            spec.append(None)
    return lax.with_sharding_constraint(
        x, NamedSharding(mesh, PartitionSpec(*spec)))


# ---------------------------------------------------------------------------
# Normalization
# ---------------------------------------------------------------------------

def rms_norm_def(d_model: int):
    return pdef((d_model,), ("embed",), init="ones")


def rms_norm(x, w, eps: float = 1e-5):
    # every block in every family enters through rms_norm, so this single
    # constraint re-anchors batch sharding inside scanned layer bodies
    # (see ``constrain`` above).
    x = constrain(x, *(["batch"] + [None] * (x.ndim - 1)))
    xf = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
    out = xf * lax.rsqrt(var + eps)
    return (out * w.astype(jnp.float32)).astype(x.dtype)


# ---------------------------------------------------------------------------
# Rotary position embeddings
# ---------------------------------------------------------------------------

def rope_frequencies(head_dim: int, theta: float) -> jax.Array:
    exponent = jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim
    return 1.0 / (theta ** exponent)                      # (head_dim//2,)


def apply_rope(x, positions, theta: float):
    """x: (..., S, H, hd); positions: broadcastable to (..., S)."""
    hd = x.shape[-1]
    freqs = rope_frequencies(hd, theta)                   # (hd//2,)
    angles = positions[..., :, None].astype(jnp.float32) * freqs  # (...,S,hd//2)
    cos = jnp.cos(angles)[..., None, :]                   # (...,S,1,hd//2)
    sin = jnp.sin(angles)[..., None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


def _maybe_seq_parallel(q, n_heads):
    """Sequence-parallel attention for TP-unshardable head counts (§Perf-B).

    When n_heads doesn't divide the model axis (qwen2: 14 heads on a
    16-way axis; whisper: 20), the head dim replicates and XLA computes the
    FULL (S, S) attention on every model-axis rank. Sharding the *query
    sequence* over the model axis instead splits the quadratic score
    tensor S/tp ways; K/V stay whole (they are KV-head-small), and the
    output reshards to batch-only at the next rms_norm constraint."""
    mesh = _ambient_mesh()
    if mesh is None or "model" not in mesh.axis_names:
        return q
    tp = mesh.shape["model"]
    if tp <= 1 or n_heads % tp == 0:
        return q                      # heads shard fine; keep TP semantics
    # only worth it when the quadratic term dominates; at short S the
    # backward-pass reshards cost more than the score split saves
    # (measured: granite train_4k coll 18.4s -> 70s with S=4096 — refuted)
    if q.shape[1] < 16384 or q.shape[1] % tp != 0:
        return q
    return constrain(q, "batch", "model", None, None)


# ---------------------------------------------------------------------------
# Attention
# ---------------------------------------------------------------------------

def attention_defs(d_model, n_heads, n_kv_heads, head_dim, *, qkv_bias=False,
                   layers=None):
    """ParamDef tree for one attention block (optionally layer-stacked)."""
    L = (layers,) if layers else ()
    ax = ("layers",) if layers else ()
    defs = {
        "wq": pdef(L + (d_model, n_heads * head_dim), ax + ("embed", "heads"),
                   init="scaled"),
        "wk": pdef(L + (d_model, n_kv_heads * head_dim),
                   ax + ("embed", "kv_heads"), init="scaled"),
        "wv": pdef(L + (d_model, n_kv_heads * head_dim),
                   ax + ("embed", "kv_heads"), init="scaled"),
        "wo": pdef(L + (n_heads * head_dim, d_model), ax + ("heads", "embed"),
                   init="scaled"),
    }
    if qkv_bias:
        defs["bq"] = pdef(L + (n_heads * head_dim,), ax + ("heads",), "zeros")
        defs["bk"] = pdef(L + (n_kv_heads * head_dim,), ax + ("kv_heads",),
                          "zeros")
        defs["bv"] = pdef(L + (n_kv_heads * head_dim,), ax + ("kv_heads",),
                          "zeros")
    return defs


def _project_qkv(p, x, n_heads, n_kv_heads, head_dim):
    B, S, _ = x.shape
    q = jnp.einsum("bsd,dh->bsh", x, p["wq"])
    k = jnp.einsum("bsd,dh->bsh", x, p["wk"])
    v = jnp.einsum("bsd,dh->bsh", x, p["wv"])
    if "bq" in p:
        q = q + p["bq"]
        k = k + p["bk"]
        v = v + p["bv"]
    q = q.reshape(B, S, n_heads, head_dim)
    k = k.reshape(B, S, n_kv_heads, head_dim)
    v = v.reshape(B, S, n_kv_heads, head_dim)
    return q, k, v


def _sdpa(q, k, v, mask):
    """q: (B,Sq,H,hd)  k,v: (B,Sk,KV,hd)  mask: (B|1,1,Sq,Sk) additive."""
    B, Sq, H, hd = q.shape
    KV = k.shape[2]
    groups = H // KV
    q = q.reshape(B, Sq, KV, groups, hd)
    scores = jnp.einsum("bqkgh,bskh->bkgqs", q, k).astype(jnp.float32)
    scores = scores / jnp.sqrt(hd).astype(jnp.float32)
    scores = scores + mask[:, :, None, :, :]              # (B,KV,G,Sq,Sk)
    probs = jax.nn.softmax(scores, axis=-1).astype(v.dtype)
    out = jnp.einsum("bkgqs,bskh->bqkgh", probs, v)
    return out.reshape(B, Sq, H, hd)


def causal_mask(Sq, Sk, *, q_offset=0, window: int = 0):
    """Additive mask (1,1,Sq,Sk). q position i attends to k<=i+q_offset,
    and (if window>0) k > i+q_offset-window."""
    qpos = jnp.arange(Sq)[:, None] + q_offset
    kpos = jnp.arange(Sk)[None, :]
    ok = kpos <= qpos
    if window > 0:
        ok &= kpos > (qpos - window)
    return jnp.where(ok, 0.0, NEG_INF).astype(jnp.float32)[None, None]


def self_attention(p, x, *, n_heads, n_kv_heads, head_dim, rope_theta,
                   positions=None, window: int = 0, cross_kv=None,
                   attn_impl: str = "xla"):
    """Full-sequence self-attention (train / prefill).

    cross_kv: optional (k, v) tuple — if given, attend to those instead of
    self-derived k/v (encoder-decoder cross attention; no causal mask).
    """
    B, S, _ = x.shape
    if positions is None:
        positions = jnp.arange(S)[None, :]
    q, k, v = _project_qkv(p, x, n_heads, n_kv_heads, head_dim)
    if cross_kv is not None:
        # (§Perf-B note: seq-parallelizing q here was tried and REFUTED —
        # it left whisper's memory term unchanged and tripled the train_4k
        # collective term from resharding; see EXPERIMENTS.md §Perf.)
        k, v = cross_kv
        mask = jnp.zeros((1, 1, S, k.shape[1]), jnp.float32)
    else:
        q = apply_rope(q, positions, rope_theta)
        k = apply_rope(k, positions, rope_theta)
        q = _maybe_seq_parallel(q, n_heads)
        mask = causal_mask(S, S, window=window)
    if attn_impl == "pallas" and cross_kv is None:
        from repro.kernels.flash_attention import ops as fa_ops
        out = fa_ops.flash_attention(q, k, v, causal=True, window=window)
    else:
        out = _sdpa(q, k, v, mask)
    out = out.reshape(B, S, n_heads * head_dim)
    return jnp.einsum("bsh,hd->bsd", out, p["wo"])


class KVEntry(NamedTuple):
    k: jax.Array      # (B, S_max, KV, hd); paged: (P, ps, KV, hd)
    v: jax.Array
    # position of next write is tracked by the caller (shared across layers)
    # quantized page pools (kv_dtype="int8") carry per-(page, offset,
    # kv-head) f32 scales alongside the int8 values; None for full-
    # precision pools and every dense cache (paging.quantize_kv).
    k_scale: Any = None   # (P, ps, KV) f32, or None
    v_scale: Any = None


def _pool_is_quantized(kv: "KVEntry") -> bool:
    return kv.k_scale is not None


def _gather_pool(kv: "KVEntry", bt_c, B, n_tok, n_kv_heads, head_dim,
                 out_dtype):
    """Gather pool pages through a clamped block table into a dense
    (B, n_tok, KV, hd) view, dequantizing int8 pools in the same step —
    the shared read path of the XLA fallbacks (the semantic twin of the
    in-kernel dequant in ``kernels/paged_attention``)."""
    k = kv.k[bt_c].reshape(B, n_tok, n_kv_heads, head_dim)
    v = kv.v[bt_c].reshape(B, n_tok, n_kv_heads, head_dim)
    if _pool_is_quantized(kv):
        ks = kv.k_scale[bt_c].reshape(B, n_tok, n_kv_heads)
        vs = kv.v_scale[bt_c].reshape(B, n_tok, n_kv_heads)
        k = paging.dequantize_kv(k, ks)
        v = paging.dequantize_kv(v, vs)
    return k.astype(out_dtype), v.astype(out_dtype)


def _scatter_pool(kv: "KVEntry", pages, k, v, B, npp, ps, pad):
    """Scatter new (B, S, KV, hd) K/V into pool pages ``pages`` (B, npp)
    with ``mode="drop"`` sentinel semantics, quantizing on write for int8
    pools — values and their per-entry scales land in the same scatter, so
    a dropped write drops both."""
    def put(pool, new):
        widths = ((0, 0), (0, pad)) + ((0, 0),) * (new.ndim - 2)
        buf = jnp.pad(new.astype(pool.dtype), widths)
        buf = buf.reshape((B, npp, ps) + new.shape[2:])
        return pool.at[pages].set(buf, mode="drop")

    if _pool_is_quantized(kv):
        qk, sk = paging.quantize_kv(k)
        qv, sv = paging.quantize_kv(v)
        return KVEntry(put(kv.k, qk), put(kv.v, qv),
                       put(kv.k_scale, sk), put(kv.v_scale, sv))
    return KVEntry(put(kv.k, k), put(kv.v, v))


def init_kv(batch, s_max, n_kv_heads, head_dim, dtype=jnp.bfloat16):
    shape = (batch, s_max, n_kv_heads, head_dim)
    return KVEntry(jnp.zeros(shape, dtype), jnp.zeros(shape, dtype))


def prefill_attention(p, x, kv: KVEntry, *, n_heads, n_kv_heads, head_dim,
                      rope_theta, window: int = 0, attn_impl: str = "xla"):
    """Causal attention over the prompt; writes k/v into cache[0:S)."""
    B, S, _ = x.shape
    positions = jnp.arange(S)[None, :]
    q, k, v = _project_qkv(p, x, n_heads, n_kv_heads, head_dim)
    q = apply_rope(q, positions, rope_theta)
    k = apply_rope(k, positions, rope_theta)
    q = _maybe_seq_parallel(q, n_heads)
    new_kv = KVEntry(
        lax.dynamic_update_slice(kv.k, k.astype(kv.k.dtype), (0, 0, 0, 0)),
        lax.dynamic_update_slice(kv.v, v.astype(kv.v.dtype), (0, 0, 0, 0)),
    )
    mask = causal_mask(S, S, window=window)
    if attn_impl == "pallas":
        from repro.kernels.flash_attention import ops as fa_ops
        out = fa_ops.flash_attention(q, k, v, causal=True, window=window)
    else:
        out = _sdpa(q, k, v, mask)
    out = out.reshape(B, S, n_heads * head_dim)
    return jnp.einsum("bsh,hd->bsd", out, p["wo"]), new_kv


def decode_attention(p, x, kv: KVEntry, pos, *, n_heads, n_kv_heads,
                     head_dim, rope_theta, window: int = 0,
                     attn_impl: str = "xla", advance=None):
    """One-token decode: x (B,1,D).

    pos: (B,) int32 per-row ABSOLUTE token positions (ragged batches: rows
    of a multi-turn rollout act at different times), or a scalar broadcast.
    advance: optional (B,) bool — rows with False neither write the cache
    nor should their output be consumed (the rollout engine feeds PAD to
    rows waiting on the rest of the batch).

    Ring-buffer semantics (§Perf-A): slot for token t is ``t % s_max``, so
    a sliding-window cache is allocated at s_max == window and old entries
    are overwritten in place — per-token cost and footprint are O(window)
    instead of O(total context). When s_max covers the full context the
    modulo is the identity and this is a plain linear cache. Slot i holds
    absolute position ``kpos_i = pos - ((pos - i) mod s_max)``; validity
    masks negative / future / out-of-window entries. (The previous
    implementation kept the FULL-length cache and dynamic-sliced a window
    around pos; under a seq-sharded cache XLA lowered that to an
    all-gather of the whole cache per token — 12 GiB/token for qwen2's
    long_500k — see EXPERIMENTS.md §Perf.)
    """
    B, S1, _ = x.shape
    assert S1 == 1
    s_max = kv.k.shape[1]
    pos = jnp.broadcast_to(jnp.asarray(pos, jnp.int32), (B,))
    if advance is None:
        advance = jnp.ones((B,), bool)
    positions = pos[:, None]
    q, k_new, v_new = _project_qkv(p, x, n_heads, n_kv_heads, head_dim)
    q = apply_rope(q, positions, rope_theta)
    k_new = apply_rope(k_new, positions, rope_theta)
    rows = jnp.arange(B)
    slot = pos % s_max                                    # ring write slot
    old_k = kv.k[rows, slot]                              # (B,KV,hd)
    old_v = kv.v[rows, slot]
    wk = jnp.where(advance[:, None, None], k_new[:, 0].astype(kv.k.dtype),
                   old_k)
    wv = jnp.where(advance[:, None, None], v_new[:, 0].astype(kv.v.dtype),
                   old_v)
    kv = KVEntry(kv.k.at[rows, slot].set(wk), kv.v.at[rows, slot].set(wv))
    k, v = kv.k, kv.v
    # absolute position held by each ring slot (identity when s_max > pos)
    idx = jnp.arange(s_max)[None, :]
    kpos = pos[:, None] - jnp.mod(pos[:, None] - idx, s_max)      # (B,Sk)
    valid = (kpos >= 0) & (kpos <= pos[:, None])
    if window > 0:
        valid &= kpos > (pos[:, None] - window)
    mask = jnp.where(valid, 0.0, NEG_INF).astype(jnp.float32)[:, None, None, :]
    if attn_impl == "pallas":
        from repro.kernels.decode_attention import ops as da_ops
        out = da_ops.decode_attention(q[:, 0], k, v, valid)
        out = out[:, None]
    else:
        out = _sdpa(q, k.astype(q.dtype), v.astype(q.dtype), mask)
    out = out.reshape(B, 1, n_heads * head_dim)
    return jnp.einsum("bsh,hd->bsd", out, p["wo"]), kv


def paged_prefill_attention(p, x, kv: KVEntry, block_table, *, n_heads,
                            n_kv_heads, head_dim, rope_theta,
                            attn_impl: str = "xla"):
    """Causal attention over the prompt; scatters k/v into pool pages.

    kv.k/v: (P, ps, KV, hd) — this layer's slice of the shared page pool.
    block_table: (B, NP) int32, already populated for ``ceil(S/ps)``
    pages per row (``transformer._paged_prefill`` allocates once, outside
    the layer scan). Attention itself is identical to
    ``prefill_attention`` — the prompt's q/k/v are all in hand; only the
    cache write changes (a per-page scatter instead of a dense slice).
    """
    B, S, _ = x.shape
    P, ps = kv.k.shape[0], kv.k.shape[1]
    positions = jnp.arange(S)[None, :]
    q, k, v = _project_qkv(p, x, n_heads, n_kv_heads, head_dim)
    q = apply_rope(q, positions, rope_theta)
    k = apply_rope(k, positions, rope_theta)
    q = _maybe_seq_parallel(q, n_heads)

    npp = -(-S // ps)                      # pages covering the prompt
    pad = npp * ps - S
    pages = block_table[:, :npp]
    pages = jnp.where(pages >= 0, pages, P)                 # OOB -> drop
    new_kv = _scatter_pool(kv, pages, k, v, B, npp, ps, pad)
    mask = causal_mask(S, S)
    if attn_impl in ("pallas", "paged"):
        from repro.kernels.flash_attention import ops as fa_ops
        out = fa_ops.flash_attention(q, k, v, causal=True, window=0)
    else:
        out = _sdpa(q, k, v, mask)
    out = out.reshape(B, S, n_heads * head_dim)
    return jnp.einsum("bsh,hd->bsd", out, p["wo"]), new_kv


def paged_chunk_attention(p, x, kv: KVEntry, block_table, start, *, n_heads,
                          n_kv_heads, head_dim, rope_theta,
                          attn_impl: str = "xla"):
    """Causal attention for a CHUNK of positions ``[start, start+S)``
    whose preceding context already lives in the pool — the per-slot
    suffix phase of the shared-prefix prefill (``transformer.
    _paged_prefill``): the forked prefix pages hold positions
    ``[0, start)``, this computes only the suffix's q/k/v, scatters the
    suffix K/V into the slot's (already mapped) pages, and attends each
    suffix query over the gathered full context. ``start`` is static and
    page-aligned (the shared run is full pages only).
    """
    B, S, _ = x.shape
    P, ps = kv.k.shape[0], kv.k.shape[1]
    assert start % ps == 0, (start, ps)
    positions = start + jnp.arange(S)[None, :]
    q, k, v = _project_qkv(p, x, n_heads, n_kv_heads, head_dim)
    q = apply_rope(q, positions, rope_theta)
    k = apply_rope(k, positions, rope_theta)

    j0 = start // ps
    npp = -(-S // ps)                      # pages covering the chunk
    pad = npp * ps - S
    pages = block_table[:, j0:j0 + npp]
    pages = jnp.where(pages >= 0, pages, P)                 # OOB -> drop
    new_kv = _scatter_pool(kv, pages, k, v, B, npp, ps, pad)
    # gather the full context [0, start+S) back through the block table
    # (prefix pages included) — the xla oracle layout, as in the paged
    # decode fallback; masked positions never contribute
    ctx_np = j0 + npp
    bt = block_table[:, :ctx_np]
    bt_c = jnp.clip(bt, 0, P - 1)
    kc, vc = _gather_pool(new_kv, bt_c, B, ctx_np * ps, n_kv_heads,
                          head_dim, q.dtype)
    s_idx = jnp.arange(ctx_np * ps)[None, None, :]          # (1,1,Sk)
    valid = ((s_idx <= positions[:, :, None])               # causal
             & jnp.repeat(bt >= 0, ps, axis=1)[:, None, :])
    mask = jnp.where(valid, 0.0, NEG_INF).astype(jnp.float32)[:, None]
    out = _sdpa(q, kc, vc, mask)
    out = out.reshape(B, S, n_heads * head_dim)
    return jnp.einsum("bsh,hd->bsd", out, p["wo"]), new_kv


def paged_decode_attention(p, x, kv: KVEntry, block_table, pos, *, wpage,
                           woff, scrub=None, cow_src=None, cow_dst=None,
                           n_heads, n_kv_heads, head_dim,
                           rope_theta, attn_impl: str = "xla"):
    """One-token decode against a paged KV pool. x: (B,1,D).

    kv.k/v: (P, ps, KV, hd) — this layer's slice of the shared page pool.
    block_table: (B, NP) int32 (-1 = unmapped); pos: (B,) absolute token
    positions. wpage/woff: per-row write page + in-page offset, computed
    once per token by the caller (the allocator runs OUTSIDE the layer
    scan — every layer shares the same block table). ``wpage == P`` is
    the drop sentinel (non-advancing rows, exhausted pool). scrub:
    optional (B,) page indices to zero before the write (pages mapped
    mid-row while recovering from pool exhaustion — the recycled
    contents must not leak into the validity window; sentinel P = none).
    cow_src/cow_dst: optional (B,) page pairs from the copy-on-write
    allocator (``paging.cow_pages``) — dst is a freshly privatized copy
    of the shared src page; this layer's slice of src is copied into dst
    BEFORE the write lands (sentinel P = no copy). The caller already
    remapped the block table, so reads go through dst.

    attn_impl: "xla" gathers the row's pages into a dense view and reuses
    the masked-softmax math (the pure-jnp oracle layout); "paged" (or
    "pallas") runs the Pallas kernel that gathers through the block table
    in the grid — no dense per-row view is ever materialized.
    """
    B, S1, _ = x.shape
    assert S1 == 1
    P, ps = kv.k.shape[0], kv.k.shape[1]
    NP = block_table.shape[1]
    pos = jnp.broadcast_to(jnp.asarray(pos, jnp.int32), (B,))
    positions = pos[:, None]
    q, k_new, v_new = _project_qkv(p, x, n_heads, n_kv_heads, head_dim)
    q = apply_rope(q, positions, rope_theta)
    k_new = apply_rope(k_new, positions, rope_theta)
    quant = _pool_is_quantized(kv)
    if cow_src is not None:
        # privatize shared pages first (CoW): the copied content below
        # the row's fill line must be in place before scrub/write. CoW
        # dst pages and exhaustion-recovery scrub pages are disjoint (a
        # freshly allocated page has refcount 1 — never CoW'd). Scales
        # travel with their values — a privatized page reads bitwise as
        # the shared original until the row's own write lands.
        src_c = jnp.clip(cow_src, 0, P - 1)
        kv = kv._replace(
            k=kv.k.at[cow_dst].set(kv.k[src_c], mode="drop"),
            v=kv.v.at[cow_dst].set(kv.v[src_c], mode="drop"))
        if quant:
            kv = kv._replace(
                k_scale=kv.k_scale.at[cow_dst].set(kv.k_scale[src_c],
                                                   mode="drop"),
                v_scale=kv.v_scale.at[cow_dst].set(kv.v_scale[src_c],
                                                   mode="drop"))
    if scrub is not None:
        zero = jnp.zeros((), kv.k.dtype)
        kv = kv._replace(k=kv.k.at[scrub].set(zero, mode="drop"),
                         v=kv.v.at[scrub].set(zero, mode="drop"))
        if quant:
            # zero scale -> dequant 0 exactly: a scrubbed page reads as
            # zeros no matter what int8 residue the values slots held
            zf = jnp.zeros((), jnp.float32)
            kv = kv._replace(k_scale=kv.k_scale.at[scrub].set(zf,
                                                              mode="drop"),
                             v_scale=kv.v_scale.at[scrub].set(zf,
                                                              mode="drop"))
    if quant:
        qk, sk = paging.quantize_kv(k_new[:, 0])    # (B,KV,hd) i8 + (B,KV)
        qv, sv = paging.quantize_kv(v_new[:, 0])
        kv = KVEntry(
            kv.k.at[wpage, woff].set(qk, mode="drop"),
            kv.v.at[wpage, woff].set(qv, mode="drop"),
            kv.k_scale.at[wpage, woff].set(sk, mode="drop"),
            kv.v_scale.at[wpage, woff].set(sv, mode="drop"))
    else:
        kv = KVEntry(
            kv.k.at[wpage, woff].set(k_new[:, 0].astype(kv.k.dtype),
                                     mode="drop"),
            kv.v.at[wpage, woff].set(v_new[:, 0].astype(kv.v.dtype),
                                     mode="drop"))
    lens = pos + 1                         # current token included
    if attn_impl in ("paged", "pallas"):
        from repro.kernels.paged_attention import ops as pa_ops
        out = pa_ops.paged_decode_attention(q[:, 0], kv.k, kv.v,
                                            block_table, lens,
                                            k_scales=kv.k_scale,
                                            v_scales=kv.v_scale)
        out = out[:, None]
    else:
        # gather + mask per kernels/paged_attention/ref.py (keep the
        # validity predicate in sync with the oracle), but attend via
        # _sdpa rather than the f32 oracle itself: the fallback must
        # match the DENSE decode path's mixed-precision numerics (bf16
        # matmuls) bitwise, or dense-vs-paged engine trajectories drift
        bt_c = jnp.clip(block_table, 0, P - 1)
        k, v = _gather_pool(kv, bt_c, B, NP * ps, n_kv_heads, head_dim,
                            q.dtype)
        s_idx = jnp.arange(NP * ps)[None, :]
        valid = ((s_idx < lens[:, None])
                 & jnp.repeat(block_table >= 0, ps, axis=1))
        mask = jnp.where(valid, 0.0,
                         NEG_INF).astype(jnp.float32)[:, None, None, :]
        out = _sdpa(q, k, v, mask)
    out = out.reshape(B, 1, n_heads * head_dim)
    return jnp.einsum("bsh,hd->bsd", out, p["wo"]), kv


def spec_verify_chunk_attention(p, x, kv: KVEntry, block_table, pos, *,
                                wpage, woff, scrub=None, cow_src=None,
                                cow_dst=None, n_heads, n_kv_heads, head_dim,
                                rope_theta, attn_impl: str = "xla"):
    """Speculative-verify attention for a chunk of K candidate tokens.
    x: (B,K,D) chunk hidden states at absolute positions
    ``pos[b]..pos[b]+K-1``; the committed pool context ends at ``pos``.

    The k-token generalization of ``paged_decode_attention``'s write-then-
    attend step: the WHOLE chunk's K/V is bulk-scattered into pool entries
    ``(wpage, woff)`` (both (B,K); sentinel ``P`` drops — non-advancing
    rows, positions beyond the row's token budget, exhausted pool,
    CoW-blocked), quantizing on write for int8 pools exactly like the
    single-token path (per-token-per-kv-head scales, so the stored bytes
    are bitwise what K sequential writes would have stored). Attention
    then reads everything BACK from the pool with per-query validity
    ``idx <= pos+j`` — each query sees the page-ordered, pool-precision
    keys the sequential step would have seen at its position, which is
    what keeps speculative greedy decode bit-identical to non-speculative.
    Chunk entries beyond the eventually accepted prefix stay above the
    fill line: invisible to every later read and rewritten by the next
    chunk before the fill line can reach them.

    scrub / cow_src / cow_dst: same single-page-per-row semantics as
    ``paged_decode_attention`` — only the chunk's FIRST page can pre-exist
    (mid-page fill line / shared prefix run); later chunk pages are
    freshly allocated at offset 0.
    """
    B, K, _ = x.shape
    P, ps = kv.k.shape[0], kv.k.shape[1]
    NP = block_table.shape[1]
    pos = jnp.broadcast_to(jnp.asarray(pos, jnp.int32), (B,))
    positions = pos[:, None] + jnp.arange(K)[None, :]       # (B,K)
    q, k_new, v_new = _project_qkv(p, x, n_heads, n_kv_heads, head_dim)
    q = apply_rope(q, positions, rope_theta)
    k_new = apply_rope(k_new, positions, rope_theta)
    quant = _pool_is_quantized(kv)
    if cow_src is not None:
        src_c = jnp.clip(cow_src, 0, P - 1)
        kv = kv._replace(
            k=kv.k.at[cow_dst].set(kv.k[src_c], mode="drop"),
            v=kv.v.at[cow_dst].set(kv.v[src_c], mode="drop"))
        if quant:
            kv = kv._replace(
                k_scale=kv.k_scale.at[cow_dst].set(kv.k_scale[src_c],
                                                   mode="drop"),
                v_scale=kv.v_scale.at[cow_dst].set(kv.v_scale[src_c],
                                                   mode="drop"))
    if scrub is not None:
        zero = jnp.zeros((), kv.k.dtype)
        kv = kv._replace(k=kv.k.at[scrub].set(zero, mode="drop"),
                         v=kv.v.at[scrub].set(zero, mode="drop"))
        if quant:
            zf = jnp.zeros((), jnp.float32)
            kv = kv._replace(k_scale=kv.k_scale.at[scrub].set(zf,
                                                              mode="drop"),
                             v_scale=kv.v_scale.at[scrub].set(zf,
                                                              mode="drop"))
    if quant:
        qk, sk = paging.quantize_kv(k_new)      # (B,K,KV,hd) i8 + (B,K,KV)
        qv, sv = paging.quantize_kv(v_new)
        kv = KVEntry(
            kv.k.at[wpage, woff].set(qk, mode="drop"),
            kv.v.at[wpage, woff].set(qv, mode="drop"),
            kv.k_scale.at[wpage, woff].set(sk, mode="drop"),
            kv.v_scale.at[wpage, woff].set(sv, mode="drop"))
    else:
        kv = KVEntry(
            kv.k.at[wpage, woff].set(k_new.astype(kv.k.dtype), mode="drop"),
            kv.v.at[wpage, woff].set(v_new.astype(kv.v.dtype), mode="drop"))
    if attn_impl in ("paged", "pallas"):
        from repro.kernels.spec_verify import ops as sv_ops
        out = sv_ops.spec_verify_attention(q, kv.k, kv.v, block_table, pos,
                                           k_scales=kv.k_scale,
                                           v_scales=kv.v_scale)
    else:
        # gather + mask per kernels/spec_verify/ref.py; attend via _sdpa so
        # the fallback matches the single-token paged fallback's
        # mixed-precision numerics bitwise per query position
        bt_c = jnp.clip(block_table, 0, P - 1)
        k, v = _gather_pool(kv, bt_c, B, NP * ps, n_kv_heads, head_dim,
                            q.dtype)
        s_idx = jnp.arange(NP * ps)[None, None, :]          # (1,1,Sk)
        valid = ((s_idx <= positions[:, :, None])
                 & jnp.repeat(block_table >= 0, ps, axis=1)[:, None, :])
        mask = jnp.where(valid, 0.0, NEG_INF).astype(jnp.float32)[:, None]
        out = _sdpa(q, k, v, mask)
    out = out.reshape(B, K, n_heads * head_dim)
    return jnp.einsum("bsh,hd->bsd", out, p["wo"]), kv


# ---------------------------------------------------------------------------
# SwiGLU MLP
# ---------------------------------------------------------------------------

def mlp_defs(d_model, d_ff, *, layers=None):
    L = (layers,) if layers else ()
    ax = ("layers",) if layers else ()
    return {
        "w_gate": pdef(L + (d_model, d_ff), ax + ("embed", "mlp"), "scaled"),
        "w_up": pdef(L + (d_model, d_ff), ax + ("embed", "mlp"), "scaled"),
        "w_down": pdef(L + (d_ff, d_model), ax + ("mlp", "embed"), "scaled"),
    }


def mlp(p, x):
    gate = jnp.einsum("bsd,df->bsf", x, p["w_gate"])
    up = jnp.einsum("bsd,df->bsf", x, p["w_up"])
    act = jax.nn.silu(gate.astype(jnp.float32)).astype(x.dtype) * up
    return jnp.einsum("bsf,fd->bsd", act, p["w_down"])


# ---------------------------------------------------------------------------
# Embedding / head
# ---------------------------------------------------------------------------

def embedding_defs(vocab, d_model):
    return pdef((vocab, d_model), ("vocab", "embed"), init="normal")


def embed(emb, tokens):
    return jnp.take(emb, tokens, axis=0)


def unembed(emb_or_head, x):
    """x: (B,S,D) -> logits (B,S,V). Accepts (V,D) table (tied) or (D,V)."""
    if emb_or_head.shape[0] < emb_or_head.shape[1]:
        return jnp.einsum("bsd,dv->bsv", x, emb_or_head)
    return jnp.einsum("bsd,vd->bsv", x, emb_or_head)
