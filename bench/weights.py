"""Seeded weights of a dense Qwen2 block stack, made by the benchmark.

``make_weights`` builds every leaf on the device in one jitted call from
the seed, in bfloat16, the type the program serves. The tree is the
benchmark's own flat layout (``shapes``); ``to_program`` renames it into
the program's parameter tree, so neither the program's initialiser nor
its tree reaches the reference. Per-layer leaves are stacked on a leading
layer axis.

Distributions: projections N(0, 1/fan_in), the token table N(0, 0.02),
an untied head N(0, 1/d), QKV biases N(0, 0.05) and norm weights
1 + N(0, 0.05), so that every bias and norm is exercised.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

# canonical name -> path in the program's tree
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


def shapes(m: dict) -> dict:
    """Canonical leaf shapes for model sizes ``m`` (the config's "model")."""
    L, d, H, KV = m["n_layers"], m["d_model"], m["n_heads"], m["n_kv_heads"]
    hd, f, V = m["head_dim"], m["d_ff"], m["vocab_size"]
    s = {"embed": (V, d), "final_norm": (d,),
         "attn_norm": (L, d), "wq": (L, d, H * hd), "wk": (L, d, KV * hd),
         "wv": (L, d, KV * hd), "wo": (L, H * hd, d), "mlp_norm": (L, d),
         "w_gate": (L, d, f), "w_up": (L, d, f), "w_down": (L, f, d)}
    if m["qkv_bias"]:
        s.update(bq=(L, H * hd), bk=(L, KV * hd), bv=(L, KV * hd))
    if not m["tie_embeddings"]:
        s["head"] = (d, V)
    return s


def _std(name: str, shape, m: dict) -> float:
    if name == "embed":
        return 0.02
    if name == "head":
        return m["d_model"] ** -0.5
    if name.startswith("b") or name.endswith("norm"):
        return 0.05
    return shape[-2] ** -0.5


def make_weights(m: dict, key) -> dict:
    """All canonical leaves, bfloat16, from ``key``, in one jitted call."""
    shp = shapes(m)
    names = sorted(shp)

    def build(key):
        out = {}
        for i, n in enumerate(names):
            z = jax.random.normal(jax.random.fold_in(key, i), shp[n],
                                  jnp.float32) * _std(n, shp[n], m)
            if n.endswith("norm"):
                z = z + 1.0
            out[n] = z.astype(jnp.bfloat16)
        return out

    return jax.jit(build)(key)


def to_program(w: dict) -> dict:
    tree: dict = {}
    for name, leaf in w.items():
        node = tree
        path = PROGRAM_PATH[name]
        for k in path[:-1]:
            node = node.setdefault(k, {})
        node[path[-1]] = leaf
    return tree


def from_program(tree: dict, names) -> dict:
    out = {}
    for n in names:
        node = tree
        for k in PROGRAM_PATH[n]:
            node = node[k]
        out[n] = node
    return out


def seed_key(seed: int):
    """A PRNG key for any whole number (seeds may exceed 32 bits)."""
    seed = int(seed)
    k = jax.random.PRNGKey(seed & 0x7FFFFFFF)
    return jax.random.fold_in(k, (seed >> 31) & 0x7FFFFFFF)


def _norms(f):
    """Per-leaf Frobenius norms of the f32 tree ``f`` builds, per layer for
    stacked leaves: {"wq.3": norm, ...}. One fused jitted reduction."""
    def reduce(*trees):
        out = {}
        for n, x in f(*trees).items():
            axes = tuple(range(1 if n in LAYER_KEYS else 0, x.ndim))
            out[n] = jnp.sqrt(jnp.sum(jnp.square(x), axis=axes)).reshape(-1)
        return out
    return reduce


def _flatten(vals: dict) -> dict:
    res = {}
    for n, v in jax.device_get(vals).items():
        if n in LAYER_KEYS:
            res.update({f"{n}.{i}": float(x) for i, x in enumerate(v)})
        else:
            res[n] = float(v[0])
    return res


def leaf_norms(w: dict) -> dict:
    f = _norms(lambda a: {n: x.astype(jnp.float32) for n, x in a.items()})
    return _flatten(jax.jit(f)(w))


def change_norms(new: dict, old: dict) -> dict:
    """Per-leaf norm of ``new - old``, in float32."""
    f = _norms(lambda a, b: {n: a[n].astype(jnp.float32)
                             - b[n].astype(jnp.float32) for n in a})
    return _flatten(jax.jit(f)(new, old))
