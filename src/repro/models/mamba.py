"""Mamba2 (SSD — state-space duality) model. [arXiv:2405.21060]

The sequence mixer is the chunked SSD algorithm: within a chunk the
recurrence is computed in its *dual* quadratic-attention form (pure matmuls,
MXU-friendly on the TPU target); across chunks a linear state recurrence is
scanned. This jnp implementation is also the numerical oracle for the Pallas
``ssd_scan`` kernel (kernels/ssd_scan/ref.py re-exports it).

Decode is the O(1)-per-token recurrent form with a (conv_state, ssm_state)
cache — this is why the long_500k shape is native for SSM/hybrid archs.
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax import lax

from repro.configs.base import ModelConfig
from repro.models import layers as L
from repro.models.param import pdef


# ---------------------------------------------------------------------------
# SSD core (chunked dual form) — kernel oracle
# ---------------------------------------------------------------------------

def ssd_chunked(x, dt, A, B, C, chunk_size: int, initial_state=None):
    """Chunked SSD scan.

    x:  (b, s, h, p)   per-head inputs
    dt: (b, s, h)      positive step sizes (already softplus'ed)
    A:  (h,)           negative per-head decay
    B:  (b, s, g, n)   input projections (g groups, h % g == 0)
    C:  (b, s, g, n)   output projections
    Returns (y (b,s,h,p), final_state (b,h,p,n)).
    """
    b, s, h, p = x.shape
    g, n = B.shape[2], B.shape[3]
    q = min(chunk_size, s)
    pad = (-s) % q
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))
        dt = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))
        B = jnp.pad(B, ((0, 0), (0, pad), (0, 0), (0, 0)))
        C = jnp.pad(C, ((0, 0), (0, pad), (0, 0), (0, 0)))
    sp = s + pad
    nc = sp // q
    rep = h // g
    Bh = jnp.repeat(B, rep, axis=2)                       # (b,sp,h,n)
    Ch = jnp.repeat(C, rep, axis=2)

    xc = x.reshape(b, nc, q, h, p)
    dtc = dt.reshape(b, nc, q, h).astype(jnp.float32)
    Bc = Bh.reshape(b, nc, q, h, n)
    Cc = Ch.reshape(b, nc, q, h, n)

    dA = dtc * A.astype(jnp.float32)                      # (b,nc,q,h) <= 0
    dA_cs = jnp.cumsum(dA, axis=2)

    # Intra-chunk (dual/"attention" form): L[i,j] = exp(cs[i]-cs[j]), j<=i.
    diff = dA_cs[:, :, :, None, :] - dA_cs[:, :, None, :, :]   # (b,nc,i,j,h)
    tril = jnp.tril(jnp.ones((q, q), bool))
    Lmat = jnp.where(tril[None, None, :, :, None], jnp.exp(diff), 0.0)
    CB = jnp.einsum("bcihn,bcjhn->bcijh", Cc.astype(jnp.float32),
                    Bc.astype(jnp.float32))
    W = (CB * Lmat * dtc[:, :, None, :, :]).astype(x.dtype)    # (b,nc,i,j,h)
    y_diag = jnp.einsum("bcijh,bcjhp->bcihp", W, xc)

    # Chunk-final states: sum_j exp(cs[-1]-cs[j]) * dt[j] * B[j] (x) x[j]
    dA_sum = dA_cs[:, :, -1, :]                                # (b,nc,h)
    decay = jnp.exp(dA_sum[:, :, None, :] - dA_cs) * dtc       # (b,nc,q,h)
    chunk_states = jnp.einsum("bcjh,bcjhn,bcjhp->bchpn",
                              decay.astype(jnp.float32),
                              Bc.astype(jnp.float32),
                              xc.astype(jnp.float32))          # (b,nc,h,p,n)

    # Inter-chunk recurrence.
    if initial_state is None:
        initial_state = jnp.zeros((b, h, p, n), jnp.float32)

    def step(state, inp):
        states_c, dA_sum_c = inp
        emit = state                                           # state BEFORE
        state = jnp.exp(dA_sum_c)[..., None, None] * state + states_c
        return state, emit

    final, prev_states = lax.scan(
        step, initial_state.astype(jnp.float32),
        (chunk_states.swapaxes(0, 1), dA_sum.swapaxes(0, 1)))
    prev_states = prev_states.swapaxes(0, 1)                   # (b,nc,h,p,n)

    # Off-diagonal contribution from carried-in state.
    y_off = jnp.einsum("bcihn,bcih,bchpn->bcihp",
                       Cc.astype(jnp.float32), jnp.exp(dA_cs), prev_states)
    y = (y_diag.astype(jnp.float32) + y_off).reshape(b, sp, h, p)
    return y[:, :s].astype(x.dtype), final


def ssd_decode_step(state, x, dt, A, B, C):
    """O(1) recurrent step. x:(b,h,p) dt:(b,h) B,C:(b,g,n) state:(b,h,p,n)."""
    b, h, p = x.shape
    g = B.shape[1]
    rep = h // g
    Bh = jnp.repeat(B, rep, axis=1).astype(jnp.float32)        # (b,h,n)
    Ch = jnp.repeat(C, rep, axis=1).astype(jnp.float32)
    dtf = dt.astype(jnp.float32)
    dA = jnp.exp(dtf * A.astype(jnp.float32))                  # (b,h)
    upd = (dtf[..., None] * Bh)[:, :, None, :] * \
        x.astype(jnp.float32)[..., None]                        # (b,h,p,n)
    state = dA[..., None, None] * state + upd
    y = jnp.einsum("bhpn,bhn->bhp", state, Ch)
    return y.astype(x.dtype), state


# ---------------------------------------------------------------------------
# Mamba2 layer
# ---------------------------------------------------------------------------

def _dims(cfg: ModelConfig):
    s = cfg.ssm
    d_inner = s.n_heads * s.head_dim
    conv_ch = d_inner + 2 * s.n_groups * s.state_size
    proj_out = 2 * d_inner + 2 * s.n_groups * s.state_size + s.n_heads
    return s, d_inner, conv_ch, proj_out


def mamba_layer_defs(cfg: ModelConfig, *, layers=None):
    s, d_inner, conv_ch, proj_out = _dims(cfg)
    n = (layers,) if layers else ()
    ax = ("layers",) if layers else ()
    return {
        "in_proj": pdef(n + (cfg.d_model, proj_out), ax + ("embed", "ssm_inner"),
                        "scaled"),
        "conv_w": pdef(n + (s.conv_width, conv_ch), ax + (None, "ssm_inner"),
                       "scaled"),
        "conv_b": pdef(n + (conv_ch,), ax + ("ssm_inner",), "zeros"),
        "A_log": pdef(n + (s.n_heads,), ax + ("ssm_heads",), "zeros"),
        "D": pdef(n + (s.n_heads,), ax + ("ssm_heads",), "ones"),
        "dt_bias": pdef(n + (s.n_heads,), ax + ("ssm_heads",), "zeros"),
        "norm_w": pdef(n + (d_inner,), ax + ("ssm_inner",), "ones"),
        "out_proj": pdef(n + (d_inner, cfg.d_model), ax + ("ssm_inner", "embed"),
                         "scaled"),
    }


def _split_proj(cfg, proj):
    _, d_inner, conv_ch, _ = _dims(cfg)
    z = proj[..., :d_inner]
    xbc = proj[..., d_inner:d_inner + conv_ch]
    dt = proj[..., d_inner + conv_ch:]
    return z, xbc, dt


def _causal_conv(xbc, w, b):
    """Depthwise causal conv. xbc: (B,S,C), w: (W,C)."""
    W = w.shape[0]
    pad = jnp.pad(xbc, ((0, 0), (W - 1, 0), (0, 0)))
    out = sum(pad[:, i:i + xbc.shape[1], :] * w[i] for i in range(W))
    return jax.nn.silu((out + b).astype(jnp.float32)).astype(xbc.dtype)


def mamba_mixer(cfg: ModelConfig, p, x, *, initial_state=None,
                attn_impl: str = "xla"):
    """Full-sequence Mamba2 mixer. x: (B,S,D) -> (y, final_state).
    attn_impl="pallas" routes the scan through the ssd_scan TPU kernel
    (interpreted on CPU, compiled on TPU); "xla" uses the pure-jnp chunked
    form."""
    s, d_inner, conv_ch, _ = _dims(cfg)
    Bsz, S, _ = x.shape
    proj = jnp.einsum("bsd,de->bse", x, p["in_proj"])
    z, xbc, dt = _split_proj(cfg, proj)
    xbc = _causal_conv(xbc, p["conv_w"], p["conv_b"])
    gn = s.n_groups * s.state_size
    xs = xbc[..., :d_inner].reshape(Bsz, S, s.n_heads, s.head_dim)
    Bmat = xbc[..., d_inner:d_inner + gn].reshape(Bsz, S, s.n_groups,
                                                  s.state_size)
    Cmat = xbc[..., d_inner + gn:].reshape(Bsz, S, s.n_groups, s.state_size)
    dt = jax.nn.softplus(dt.astype(jnp.float32) + p["dt_bias"])
    A = -jnp.exp(p["A_log"].astype(jnp.float32))
    if attn_impl == "pallas" and initial_state is None:
        from repro.kernels.ssd_scan import ops as ssd_ops
        y, final = ssd_ops.ssd_scan(xs, dt, A, Bmat, Cmat, s.chunk_size)
    else:
        y, final = ssd_chunked(xs, dt, A, Bmat, Cmat, s.chunk_size,
                               initial_state=initial_state)
    y = y + xs * p["D"][None, None, :, None].astype(y.dtype)
    y = y.reshape(Bsz, S, d_inner)
    y = L.rms_norm(y * jax.nn.silu(z.astype(jnp.float32)).astype(y.dtype),
                   p["norm_w"], cfg.rms_eps)
    out = jnp.einsum("bse,ed->bsd", y, p["out_proj"])
    # conv cache tail: last (W-1) pre-conv xbc values (pre-activation inputs)
    return out, final


def mamba_mixer_decode(cfg: ModelConfig, p, x, conv_state, ssm_state):
    """One-token mixer. x: (B,1,D). conv_state: (B, W-1, conv_ch)."""
    s, d_inner, conv_ch, _ = _dims(cfg)
    Bsz = x.shape[0]
    proj = jnp.einsum("bsd,de->bse", x, p["in_proj"])[:, 0]    # (B,E)
    z, xbc, dt = _split_proj(cfg, proj)
    # causal conv over [conv_state ; xbc]
    W = s.conv_width
    window = jnp.concatenate([conv_state, xbc[:, None, :]], axis=1)  # (B,W,C)
    conv_out = jnp.einsum("bwc,wc->bc", window, p["conv_w"]) + p["conv_b"]
    xbc_c = jax.nn.silu(conv_out.astype(jnp.float32)).astype(x.dtype)
    new_conv_state = window[:, 1:]
    gn = s.n_groups * s.state_size
    xs = xbc_c[..., :d_inner].reshape(Bsz, s.n_heads, s.head_dim)
    Bmat = xbc_c[..., d_inner:d_inner + gn].reshape(Bsz, s.n_groups,
                                                    s.state_size)
    Cmat = xbc_c[..., d_inner + gn:].reshape(Bsz, s.n_groups, s.state_size)
    dt = jax.nn.softplus(dt.astype(jnp.float32) + p["dt_bias"])
    A = -jnp.exp(p["A_log"].astype(jnp.float32))
    y, new_ssm = ssd_decode_step(ssm_state, xs, dt, A, Bmat, Cmat)
    y = y + xs * p["D"][None, :, None].astype(y.dtype)
    y = y.reshape(Bsz, d_inner)
    y = L.rms_norm(y * jax.nn.silu(z.astype(jnp.float32)).astype(y.dtype),
                   p["norm_w"], cfg.rms_eps)
    out = jnp.einsum("be,ed->bd", y, p["out_proj"])
    return out[:, None], new_conv_state, new_ssm


def _conv_tail(cfg, p_layer, x):
    """Recompute the pre-conv xbc tail for the decode conv cache."""
    s, d_inner, conv_ch, _ = _dims(cfg)
    W = s.conv_width
    proj = jnp.einsum("bsd,de->bse", x[:, -(W - 1):], p_layer["in_proj"])
    _, xbc, _ = _split_proj(cfg, proj)
    return xbc                                              # (B, W-1, conv_ch)


# ---------------------------------------------------------------------------
# Full model
# ---------------------------------------------------------------------------

def block_defs(cfg: ModelConfig):
    n = cfg.n_layers
    return {
        "ln": pdef((n, cfg.d_model), ("layers", "embed"), "ones"),
        "mixer": mamba_layer_defs(cfg, layers=n),
    }


def model_defs(cfg: ModelConfig):
    defs = {
        "embedding": L.embedding_defs(cfg.vocab_size, cfg.d_model),
        "layers": block_defs(cfg),
        "ln_f": pdef((cfg.d_model,), ("embed",), "ones"),
    }
    if not cfg.tie_embeddings:
        defs["lm_head"] = pdef((cfg.d_model, cfg.vocab_size),
                               ("embed", "vocab"), "scaled")
    return defs


def _block_apply(cfg, layer_p, x, *, attn_impl: str = "xla"):
    h = L.rms_norm(x, layer_p["ln"], cfg.rms_eps)
    out, _ = mamba_mixer(cfg, layer_p["mixer"], h, attn_impl=attn_impl)
    return x + out


def forward(cfg: ModelConfig, params, tokens, *, extra=None,
            attn_impl: str = "xla"):
    del extra
    x = L.embed(params["embedding"], tokens)
    from functools import partial
    apply = partial(_block_apply, attn_impl=attn_impl)

    def body(carry, layer_p):
        fn = apply
        if cfg.remat == "full":
            fn = jax.checkpoint(fn, static_argnums=(0,),
                                policy=jax.checkpoint_policies.nothing_saveable)
        return fn(cfg, layer_p, carry), None

    x, _ = lax.scan(body, x, params["layers"])
    x = L.rms_norm(x, params["ln_f"], cfg.rms_eps)
    head = params.get("lm_head", params["embedding"])
    return L.unembed(head, x)


class MambaCache(NamedTuple):
    conv: jax.Array     # (L, B, W-1, conv_ch)
    ssm: jax.Array      # (L, B, H, P, N) float32
    pos: jax.Array


def init_cache(cfg: ModelConfig, batch: int, s_max: int, dtype=jnp.bfloat16):
    s, d_inner, conv_ch, _ = _dims(cfg)
    del s_max  # state is O(1) in sequence length — the SSM advantage
    return MambaCache(
        conv=jnp.zeros((cfg.n_layers, batch, cfg.ssm.conv_width - 1, conv_ch),
                       dtype),
        ssm=jnp.zeros((cfg.n_layers, batch, cfg.ssm.n_heads,
                       cfg.ssm.head_dim, cfg.ssm.state_size), jnp.float32),
        pos=jnp.zeros((batch,), jnp.int32),
    )


def prefill(cfg: ModelConfig, params, tokens, cache: MambaCache, *,
            extra=None, attn_impl: str = "xla"):
    del extra, attn_impl
    x = L.embed(params["embedding"], tokens)

    def body(x, scanned):
        layer_p, _conv0, ssm0 = scanned
        h = L.rms_norm(x, layer_p["ln"], cfg.rms_eps)
        out, final = mamba_mixer(cfg, layer_p["mixer"], h,
                                 initial_state=ssm0)
        conv_tail = _conv_tail(cfg, layer_p["mixer"], h)
        return x + out, (conv_tail.astype(cache.conv.dtype), final)

    x, (new_conv, new_ssm) = lax.scan(
        body, x, (params["layers"], cache.conv, cache.ssm))
    x = L.rms_norm(x[:, -1:], params["ln_f"], cfg.rms_eps)
    head = params.get("lm_head", params["embedding"])
    logits = L.unembed(head, x)[:, 0]
    B = tokens.shape[0]
    return logits, MambaCache(conv=new_conv, ssm=new_ssm,
                              pos=jnp.full((B,), tokens.shape[1], jnp.int32))


def decode_step(cfg: ModelConfig, params, token, cache: MambaCache, *,
                extra=None, attn_impl: str = "xla", advance=None):
    del extra, attn_impl
    x = L.embed(params["embedding"], token[:, None])
    B = token.shape[0]
    adv = jnp.ones((B,), bool) if advance is None else advance

    def body(x, scanned):
        layer_p, conv_l, ssm_l = scanned
        h = L.rms_norm(x, layer_p["ln"], cfg.rms_eps)
        out, new_conv, new_ssm = mamba_mixer_decode(
            cfg, layer_p["mixer"], h, conv_l, ssm_l)
        new_conv = jnp.where(adv[:, None, None], new_conv, conv_l)
        new_ssm = jnp.where(adv[:, None, None, None], new_ssm, ssm_l)
        return x + out, (new_conv, new_ssm)

    x, (new_conv, new_ssm) = lax.scan(
        body, x, (params["layers"], cache.conv, cache.ssm))
    x = L.rms_norm(x, params["ln_f"], cfg.rms_eps)
    head = params.get("lm_head", params["embedding"])
    logits = L.unembed(head, x)[:, 0]
    return logits, MambaCache(conv=new_conv, ssm=new_ssm,
                              pos=cache.pos + adv.astype(jnp.int32))
