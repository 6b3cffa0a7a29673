"""Token protocol shared by the environment copies (a copy of
``repro.rl.envs.base``'s constants and helpers).

Control tokens sit at the bottom of the vocabulary; actions are read by
the program's rollout engines from token ids ``32 + action``. Rewards:
win +1, draw 0, loss -1, illegal move -1 (terminal).
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

TOK_PAD = 0
TOK_BOS = 1
TOK_TURN = 2
TOK_WIN = 3
TOK_LOSS = 4
TOK_DRAW = 5
TOK_ILLEGAL = 6
TOK_OBS_BASE = 8


class StepResult(NamedTuple):
    reward: jax.Array        # (B,) float32, nonzero on the terminal step
    done: jax.Array          # (B,) bool
    obs_tokens: jax.Array    # (B, obs_len) int32, the next observation


def default_reset_rows(env, rng, state, mask):
    """Rows where ``mask`` get a fresh episode state."""
    mask = jnp.asarray(mask)
    fresh = env.reset(rng, mask.shape[0])

    def mix(f, s):
        m = mask.reshape(mask.shape + (1,) * (s.ndim - 1))
        return jnp.where(m, f, s)

    return jax.tree.map(mix, fresh, state)
