"""A sparse-expert block for the tests, added to a cell's root as a file
of its own: the Qwen2 attention (``bench/blocks/qwen2.py``), then a
softmax router over ``n_experts`` SwiGLU experts whose ``top_k`` gates
are renormalised to sum to one, as ``models/moe.py`` routes. The
reference runs every expert on every token and keeps the top-k gates, so
it drops nothing, in float32.

The configuration's "moe" section holds ``n_experts``, ``top_k``,
``d_ff_expert``, ``capacity_factor`` and ``router_aux_weight``.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from bench.blocks import qwen2
from bench.reference import HIGHEST, mm, rms, scan_layers

PROGRAM_PATH = {
    **qwen2.PROGRAM_PATH,
    "router": ("layers", "moe", "router"),
    "w_gate": ("layers", "moe", "w_gate"),
    "w_up": ("layers", "moe", "w_up"),
    "w_down": ("layers", "moe", "w_down"),
}

LAYER_KEYS = ("attn_norm", "wq", "bq", "wk", "bk", "wv", "bv", "wo",
              "mlp_norm", "router", "w_gate", "w_up", "w_down")


def shapes(m: dict) -> dict:
    L, d, V = m["n_layers"], m["d_model"], m["vocab_size"]
    E, f = m["moe"]["n_experts"], m["moe"]["d_ff_expert"]
    s = {"embed": (V, d), "final_norm": (d,), **qwen2.attention_shapes(m),
         "mlp_norm": (L, d), "router": (L, d, E), "w_gate": (L, E, d, f),
         "w_up": (L, E, d, f), "w_down": (L, E, f, d)}
    if not m["tie_embeddings"]:
        s["head"] = (d, V)
    return s


init = qwen2.init


def program_config(m: dict) -> dict:
    from repro.configs.base import MoEConfig
    return dict(m, moe=MoEConfig(**m["moe"]))


def _experts(m, precision, x, p):
    E, K = m["moe"]["n_experts"], m["moe"]["top_k"]
    h = rms(x, p["mlp_norm"], m["rms_eps"])
    probs = jax.nn.softmax(mm("btd,de->bte", h, p["router"], precision), -1)
    gate, idx = jax.lax.top_k(probs, K)
    gate = gate / jnp.sum(gate, axis=-1, keepdims=True)
    combine = jnp.sum(gate[..., None] * jax.nn.one_hot(idx, E), axis=-2)
    g = mm("btd,edf->betf", h, p["w_gate"], precision)
    u = mm("btd,edf->betf", h, p["w_up"], precision)
    y = mm("betf,efd->betd", jax.nn.silu(g) * u, p["w_down"], precision)
    return x + jnp.einsum("bte,betd->btd", combine, y, precision=HIGHEST)


def _layer(m, precision, x, p):
    return _experts(m, precision, qwen2.attention(m, precision, x, p), p)


def layers(m, precision, x, w):
    return scan_layers(partial(_layer, m, precision), x, w, LAYER_KEYS)


def matmul_weights(m: dict) -> int:
    """The router and the ``top_k`` experts a token is sent to."""
    d, moe = m["d_model"], m["moe"]
    per_layer = (qwen2.attention_weights(m) + d * moe["n_experts"]
                 + moe["top_k"] * 3 * d * moe["d_ff_expert"])
    return m["n_layers"] * per_layer + d * m["vocab_size"]


attention_flops = qwen2.attention_flops
