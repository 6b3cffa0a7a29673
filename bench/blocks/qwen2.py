"""The dense Qwen2 block (arXiv:2407.10671, the ``Qwen2ForCausalLM`` code
of Hugging Face transformers): RMSNorm, grouped-query attention with
optional biases on Q, K and V and rotary embeddings in the rotate-half
form, and a SwiGLU MLP. ``attention`` is written so that other blocks
with the same attention can take it.

Seeded values: projections N(0, 1/fan_in), the token table N(0, 0.02),
an untied head N(0, 1/d), QKV biases N(0, 0.05) and norm weights
1 + N(0, 0.05), so that every bias and norm is exercised.

FLOPs: two for each weight of a matmul (the Q, K, V and output
projections, the three SwiGLU matrices, and the head over the whole
vocabulary), plus causal attention: at position ``t`` (1-based) the
scores and the weighted sum take ``4 * t * n_heads * head_dim`` per
layer. Norms, biases and the softmax are not counted.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from bench.reference import HIGHEST, mm, rms, scan_layers

PROGRAM_PATH = {
    "embed": ("embedding",),
    "head": ("lm_head",),
    "final_norm": ("ln_f",),
    "attn_norm": ("layers", "ln1"),
    "wq": ("layers", "attn", "wq"),
    "bq": ("layers", "attn", "bq"),
    "wk": ("layers", "attn", "wk"),
    "bk": ("layers", "attn", "bk"),
    "wv": ("layers", "attn", "wv"),
    "bv": ("layers", "attn", "bv"),
    "wo": ("layers", "attn", "wo"),
    "mlp_norm": ("layers", "ln2"),
    "w_gate": ("layers", "mlp", "w_gate"),
    "w_up": ("layers", "mlp", "w_up"),
    "w_down": ("layers", "mlp", "w_down"),
}

LAYER_KEYS = ("attn_norm", "wq", "bq", "wk", "bk", "wv", "bv", "wo",
              "mlp_norm", "w_gate", "w_up", "w_down")


def attention_shapes(m: dict) -> dict:
    """Shapes of the stacked attention leaves and their norm."""
    L, d, H, KV = m["n_layers"], m["d_model"], m["n_heads"], m["n_kv_heads"]
    hd = m["head_dim"]
    s = {"attn_norm": (L, d), "wq": (L, d, H * hd), "wk": (L, d, KV * hd),
         "wv": (L, d, KV * hd), "wo": (L, H * hd, d)}
    if m["qkv_bias"]:
        s.update(bq=(L, H * hd), bk=(L, KV * hd), bv=(L, KV * hd))
    return s


def shapes(m: dict) -> dict:
    L, d, f, V = m["n_layers"], m["d_model"], m["d_ff"], m["vocab_size"]
    s = {"embed": (V, d), "final_norm": (d,), **attention_shapes(m),
         "mlp_norm": (L, d), "w_gate": (L, d, f), "w_up": (L, d, f),
         "w_down": (L, f, d)}
    if not m["tie_embeddings"]:
        s["head"] = (d, V)
    return s


def init(name: str, shape, m: dict):
    if name == "embed":
        return 0.0, 0.02
    if name == "head":
        return 0.0, m["d_model"] ** -0.5
    if name.endswith("norm"):
        return 1.0, 0.05
    if name.startswith("b"):
        return 0.0, 0.05
    return 0.0, shape[-2] ** -0.5


def program_config(m: dict) -> dict:
    return dict(m)


def _rope(x, theta):
    """x: (B, T, heads, hd), rotate-half form."""
    T, hd = x.shape[1], x.shape[-1]
    inv = 1.0 / theta ** (np.arange(0, hd, 2, dtype=np.float32) / hd)
    ang = np.arange(T, dtype=np.float32)[:, None] * inv[None, :]
    ang = np.concatenate([ang, ang], axis=-1)            # (T, hd)
    cos = jnp.asarray(np.cos(ang))[None, :, None, :]
    sin = jnp.asarray(np.sin(ang))[None, :, None, :]
    half = hd // 2
    rot = jnp.concatenate([-x[..., half:], x[..., :half]], axis=-1)
    return x * cos + rot * sin


def attention(m, precision, x, p):
    """The residual stream after the block's causal attention."""
    B, T, _ = x.shape
    H, KV, hd = m["n_heads"], m["n_kv_heads"], m["head_dim"]
    h = rms(x, p["attn_norm"], m["rms_eps"])
    q = mm("btd,dh->bth", h, p["wq"], precision)
    k = mm("btd,dh->bth", h, p["wk"], precision)
    v = mm("btd,dh->bth", h, p["wv"], precision)
    if "bq" in p:
        q = q + p["bq"].astype(jnp.float32)
        k = k + p["bk"].astype(jnp.float32)
        v = v + p["bv"].astype(jnp.float32)
    q = _rope(q.reshape(B, T, H, hd), m["rope_theta"])
    k = _rope(k.reshape(B, T, KV, hd), m["rope_theta"])
    v = v.reshape(B, T, KV, hd)
    rep = H // KV                                        # q head i -> kv i//rep
    k = jnp.repeat(k, rep, axis=2)
    v = jnp.repeat(v, rep, axis=2)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k, precision=HIGHEST) / np.sqrt(hd)
    causal = np.tril(np.ones((T, T), bool))
    s = jnp.where(causal[None, None], s, -jnp.inf)
    a = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhqk,bkhd->bqhd", a, v, precision=HIGHEST)
    return x + mm("bth,hd->btd", o.reshape(B, T, H * hd), p["wo"], precision)


def _layer(m, precision, x, p):
    x = attention(m, precision, x, p)
    h = rms(x, p["mlp_norm"], m["rms_eps"])
    g = mm("btd,df->btf", h, p["w_gate"], precision)
    u = mm("btd,df->btf", h, p["w_up"], precision)
    return x + mm("btf,fd->btd", jax.nn.silu(g) * u, p["w_down"], precision)


def layers(m, precision, x, w):
    return scan_layers(partial(_layer, m, precision), x, w, LAYER_KEYS)


def attention_weights(m: dict) -> int:
    d, H, KV, hd = m["d_model"], m["n_heads"], m["n_kv_heads"], m["head_dim"]
    return d * H * hd + 2 * d * KV * hd + H * hd * d


def matmul_weights(m: dict) -> int:
    per_layer = attention_weights(m) + 3 * m["d_model"] * m["d_ff"]
    return m["n_layers"] * per_layer + m["d_model"] * m["vocab_size"]


def attention_flops(m: dict, n: int) -> float:
    """Causal attention over one sequence of ``n`` tokens."""
    return 2.0 * m["n_layers"] * m["n_heads"] * m["head_dim"] * n * (n + 1)
