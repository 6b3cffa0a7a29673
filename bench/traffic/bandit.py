"""Vectorized multi-armed bandit — the single-turn lower bound (a copy of
``repro.rl.envs.bandit``, kept as the benchmark's traffic generator).

Scenario-diversity env for rollout-engine benchmarking: an episode is one
decision. Each episode draws per-arm payout probabilities at reset; the
observation encodes a *noisy quantized hint* of each arm's mean (the
"noisy reward-observation tokens"), the agent picks an arm with one action
token, and the episode terminates with a ±1 stochastic payout. With
``max_turns = 1`` and a tiny observation this is the shortest episode the
engines can run — the continuous-batching engine's slot-refill machinery
gets exercised at maximum churn (every macro-step frees every slot).

Token protocol: hint levels occupy ``TOK_OBS_BASE + [0, obs_levels)``;
actions are the shared ``ACTION_BASE`` region like the board games.
Rewards: +1 payout with probability ``mean[arm]``, else -1.

``prompt_len`` prepends a fixed deterministic "system prompt" token run
to every observation — the agentic-RL shape where each episode opens
with the same instructions + tool schemas and only a short episode-
specific suffix differs. ``prompt_prefix_len`` (= BOS + prompt) declares
how much of the initial observation is identical across episodes, which
is what the engine's copy-on-write prefix sharing forks across slots:
with a long prompt and maximum churn this env is the shared-prompt
benchmark regime (``benchmarks/bench_rollout``).
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from bench.traffic.envbase import (StepResult, TOK_BOS, TOK_LOSS,
                                   TOK_OBS_BASE, TOK_TURN, TOK_WIN,
                                   default_reset_rows)


class BanditState(NamedTuple):
    means: jax.Array     # (B, n_arms) f32 in [0,1] — per-episode payout prob
    hints: jax.Array     # (B, n_arms) int32 — noisy quantized mean levels
    done: jax.Array      # (B,) bool
    reward: jax.Array    # (B,) f32 (sticky terminal reward)


class MultiArmedBandit:
    jit_safe = True      # pure jnp: usable inside the compiled engine

    def __init__(self, n_arms: int = 5, hint_noise: float = 0.15,
                 obs_levels: int = 4, prompt_len: int = 0):
        self.n_actions = n_arms
        self.n_arms = n_arms
        self.hint_noise = hint_noise
        self.obs_levels = obs_levels
        self.prompt_len = prompt_len
        # BOS + prompt + hints + result + TURN
        self.obs_len = 1 + prompt_len + n_arms + 2
        # BOS + the fixed prompt are identical for every episode; the
        # hints that follow are per-episode draws
        self.prompt_prefix_len = 1 + prompt_len

    def reset(self, rng, batch: int) -> BanditState:
        rng = jax.random.PRNGKey(0) if rng is None else rng
        mrng, nrng = jax.random.split(jax.random.fold_in(rng, 0x6BAD))
        means = jax.random.uniform(mrng, (batch, self.n_arms))
        noisy = means + self.hint_noise * jax.random.normal(
            nrng, (batch, self.n_arms))
        hints = jnp.clip((noisy * self.obs_levels).astype(jnp.int32),
                         0, self.obs_levels - 1)
        return BanditState(
            means=means,
            hints=hints,
            done=jnp.zeros((batch,), bool),
            reward=jnp.zeros((batch,), jnp.float32),
        )

    def reset_rows(self, rng, state: BanditState, mask) -> BanditState:
        return default_reset_rows(self, rng, state, mask)

    def legal_mask(self, state: BanditState):
        return jnp.ones(state.means.shape, bool)         # every arm pullable

    def encode_obs(self, state: BanditState, result_tok=None):
        B = state.means.shape[0]
        bos = jnp.full((B, 1), TOK_BOS, jnp.int32)
        parts = [bos]
        if self.prompt_len > 0:
            # fixed deterministic preamble, identical for every episode
            pre = TOK_OBS_BASE + (jnp.arange(self.prompt_len,
                                             dtype=jnp.int32)
                                  % self.obs_levels)
            parts.append(jnp.broadcast_to(pre[None, :],
                                          (B, self.prompt_len)))
        hints = TOK_OBS_BASE + state.hints.astype(jnp.int32)
        res = (jnp.full((B, 1), TOK_TURN, jnp.int32)
               if result_tok is None else result_tok[:, None])
        turn = jnp.full((B, 1), TOK_TURN, jnp.int32)
        return jnp.concatenate(parts + [hints, res, turn], axis=1)

    def step(self, state: BanditState, actions, rng) -> tuple:
        """One pull ends the episode. actions: (B,) int32 in [0, n_arms)."""
        B = actions.shape[0]
        chosen = jnp.take_along_axis(
            state.means, actions[:, None], axis=1)[:, 0]
        u = jax.random.uniform(rng, (B,))
        payout = jnp.where(u < chosen, 1.0, -1.0).astype(jnp.float32)

        newly = ~state.done                               # absorbing done rows
        new_reward = jnp.where(newly, payout, state.reward)
        new_done = jnp.ones((B,), bool)
        result_tok = jnp.where(new_reward > 0, TOK_WIN,
                               TOK_LOSS).astype(jnp.int32)
        new_state = BanditState(means=state.means, hints=state.hints,
                                done=new_done, reward=new_reward)
        obs = self.encode_obs(new_state, result_tok)
        return new_state, StepResult(reward=new_reward * newly,
                                     done=new_done, obs_tokens=obs)
