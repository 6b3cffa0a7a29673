"""Pallas TPU kernels for the model and rollout hot paths.

Each kernel package holds ``kernel.py`` (the ``pallas_call``), ``ops.py``
(the model-layout wrapper) and ``ref.py`` (the pure-jnp oracle).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def matmul_nt(a, b):
    """a @ b.T in f32, without materializing the transpose (the MXU's
    transposed-operand form)."""
    return jax.lax.dot_general(a, b, (((1,), (1,)), ((), ())),
                               preferred_element_type=jnp.float32)


def matmul_tn(a, b):
    """a.T @ b in f32: contracts the leading (row) dims of both."""
    return jax.lax.dot_general(a, b, (((0,), (0,)), ((), ())),
                               preferred_element_type=jnp.float32)


def default_interpret() -> bool:
    """Pallas interpret mode for the current JAX backend: True on CPU
    (the Pallas interpreter), False on TPU (compiled Mosaic kernels).
    Any other backend has no kernel path and is an error."""
    backend = jax.default_backend()
    if backend == "cpu":
        return True
    if backend == "tpu":
        return False
    raise RuntimeError(f"no Pallas kernel path for backend {backend!r}")


def resolve_interpret(interpret) -> bool:
    """``interpret`` as given, or the backend default when it is None."""
    return default_interpret() if interpret is None else bool(interpret)


def pick_block(s: int, target: int, align: int) -> int:
    """Largest multiple of ``align`` <= target dividing s, else s itself:
    a block dim must tile by the TPU's (8 sublane, 128 lane) tiling or
    span the whole array dim."""
    for b in range(min(target, s) // align * align, 0, -align):
        if s % b == 0:
            return b
    return s
