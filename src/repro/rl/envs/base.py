"""Vectorized two-player board-game environments, pure jnp.

The agent (LLM policy) always plays piece 1; the built-in opponent (uniform
random over legal moves) plays piece 2 and moves immediately after the agent
inside ``step``. All arrays carry a leading batch dimension and the whole
env is jit/vmap-friendly; finished episodes absorb (further steps are
no-ops).

Token protocol (shared by both games): each environment exposes a small
control-token region at the bottom of the model's vocabulary; the rollout
engine renders observations with ``encode_obs`` and decodes the agent's
action from the last generated token of the turn (``action = token %
n_actions``). Rewards: win=+1, draw=0, loss=-1, illegal move=-1 (terminal).

Compiled-engine protocol: an env declares ``jit_safe = True`` when its
``reset`` / ``step`` / ``encode_obs`` are pure ``jnp`` (traceable inside
``jax.jit``); the python engine then also compiles its per-turn env
transition (``jit_rollout_env``) instead of running ``step`` op by op.
A jit-safe env provides ``reset_rows(rng, state, mask)`` — a pure
row-wise reset used for in-graph slot refill (``default_reset_rows``
below covers any env with batch-leading state leaves). Optionally it
declares ``prompt_prefix_len``: the number of LEADING tokens of every
episode's *initial* observation that are identical across episodes and
rows (system prompt / rules / tool schemas). The compiled engine's
copy-on-write prefix sharing (``share_prefix=True``) prefills those
tokens once per rollout and forks the covering KV pages into every
slot, so the contract must hold for every reset the env can produce.
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

# Control-token ids (kept below any game's OBS_BASE)
TOK_PAD = 0
TOK_BOS = 1
TOK_TURN = 2          # "your move" marker
TOK_WIN = 3
TOK_LOSS = 4
TOK_DRAW = 5
TOK_ILLEGAL = 6
TOK_OBS_BASE = 8      # cell encodings start here: empty/agent/opponent


class StepResult(NamedTuple):
    reward: jax.Array        # (B,) float32 — nonzero only on terminal step
    done: jax.Array          # (B,) bool
    obs_tokens: jax.Array    # (B, obs_len) int32 — next observation


def default_reset_rows(env, rng, state, mask):
    """Pure slot-refill: rows where ``mask`` get a fresh episode state.

    Used by the compiled rollout engine to reset finished slots *inside*
    the generation graph (continuous batching): a full fresh batch state is
    built with ``env.reset`` and blended row-wise into the existing state.
    Works for any env whose state leaves carry a leading batch dimension.
    """
    mask = jnp.asarray(mask)
    fresh = env.reset(rng, mask.shape[0])

    def mix(f, s):
        m = mask.reshape(mask.shape + (1,) * (s.ndim - 1))
        return jnp.where(m, f, s)

    return jax.tree.map(mix, fresh, state)
