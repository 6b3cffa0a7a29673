"""Seeded weights of a configuration's block stack, made by the benchmark.

``make_weights`` builds every leaf of the block's ``shapes`` on the device
in one jitted call from the seed, in bfloat16, the type the program
serves: leaf ``i`` of the sorted names is ``mean + N(0, std)`` from
``fold_in(key, i)``, with the block's ``init``. The tree is the
benchmark's own flat layout; ``to_program`` renames it into the program's
parameter tree through the block's ``PROGRAM_PATH``, so neither the
program's initialiser nor its tree reaches the reference. Leaves in the
block's ``LAYER_KEYS`` are stacked on a leading layer axis, and their
norms are taken per layer.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def make_weights(block, m: dict, key) -> dict:
    """All canonical leaves, bfloat16, from ``key``, in one jitted call."""
    shp = block.shapes(m)
    names = sorted(shp)

    def build(key):
        out = {}
        for i, n in enumerate(names):
            mean, std = block.init(n, shp[n], m)
            z = jax.random.normal(jax.random.fold_in(key, i), shp[n],
                                  jnp.float32) * std + mean
            out[n] = z.astype(jnp.bfloat16)
        return out

    return jax.jit(build)(key)


def to_program(block, w: dict) -> dict:
    tree: dict = {}
    for name, leaf in w.items():
        node = tree
        path = block.PROGRAM_PATH[name]
        for k in path[:-1]:
            node = node.setdefault(k, {})
        node[path[-1]] = leaf
    return tree


def from_program(block, tree: dict, names) -> dict:
    out = {}
    for n in names:
        node = tree
        for k in block.PROGRAM_PATH[n]:
            node = node[k]
        out[n] = node
    return out


def seed_key(seed: int):
    """A PRNG key for any whole number (seeds may exceed 32 bits)."""
    seed = int(seed)
    k = jax.random.PRNGKey(seed & 0x7FFFFFFF)
    return jax.random.fold_in(k, (seed >> 31) & 0x7FFFFFFF)


def _per_leaf_norms(block, f, *trees) -> dict:
    """Frobenius norms of the float32 leaves of ``f(*trees)``, per layer
    for the block's stacked leaves: {"wq.3": norm, ...}. One fused jitted
    reduction."""
    stacked = block.LAYER_KEYS

    def reduce(*trees):
        out = {}
        for n, x in f(*trees).items():
            axes = tuple(range(1 if n in stacked else 0, x.ndim))
            out[n] = jnp.sqrt(jnp.sum(jnp.square(x), axis=axes)).reshape(-1)
        return out

    res = {}
    for n, v in jax.device_get(jax.jit(reduce)(*trees)).items():
        if n in stacked:
            res.update({f"{n}.{i}": float(x) for i, x in enumerate(v)})
        else:
            res[n] = float(v[0])
    return res


def leaf_norms(block, w: dict) -> dict:
    return _per_leaf_norms(
        block, lambda a: {n: x.astype(jnp.float32) for n, x in a.items()}, w)


def change_norms(block, new: dict, old: dict) -> dict:
    """Per-leaf norm of ``new - old``, in float32."""
    return _per_leaf_norms(
        block, lambda a, b: {n: a[n].astype(jnp.float32)
                             - b[n].astype(jnp.float32) for n in a},
        new, old)
