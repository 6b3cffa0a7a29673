"""Multi-turn agentic rollout engine (the paper's Rollout stage, Fig. 2 ①).

Per turn: the policy decodes tokens one at a time (temperature sampling,
or greedy argmax when ``temperature <= 0``) until it emits an *action
token* (or hits the per-turn cap); the action is applied to the vectorized
environment; the environment's observation tokens are then teacher-forced
into the context, and the next turn begins. The loop ends when every
episode is done or the context limit would be exceeded (a *truncation* —
the failure mode of paper Fig. 1, which EARL's dynamic parallelism exists
to push out).

The action protocol, sampling, rng derivation and stats live in
``rl/engine/common.py``, shared with the compiled slot engine
(``rl/engine/compiled.py``). This per-token python loop is the
CPU-friendly reference path: it host-syncs on every token, which is
exactly the overhead the compiled engine removes; a parity test pins both
engines to identical greedy trajectories. It does not dispatch op by op:
each sampled token runs one program (``jit_rollout_sample``: key, sample,
log-prob, action mask) and each turn's env transition another
(``jit_rollout_env``: fallback actions, env step), the latter only for an
env that declares ``jit_safe`` (otherwise the same function runs eagerly).
Under a profiler session each phase shows as an ``earl.rollout.*`` span
and each host read as an ``earl.sync`` span (``utils/tracing.py``).
"""
from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np

from repro.rl.algo import reinforce_advantages
from repro.rl.engine import common
from repro.rl.engine.common import ACTION_BASE, RolloutStats  # re-exported
from repro.rl.envs.base import TOK_PAD
from repro.rl.experience import ExperienceBatch
from repro.utils.tracing import span, to_host


@dataclass
class RolloutEngine:
    model: object                   # repro.models.Model
    env: object
    max_turns: int = 4
    max_turn_tokens: int = 8
    max_context: int = 256
    temperature: float = 1.0
    top_p: float = 1.0              # nucleus filter (1.0 = off)

    def __post_init__(self):
        cfg = self.model.cfg
        assert ACTION_BASE + self.env.n_actions <= cfg.vocab_size
        model = self.model

        # named, so their device ops read jit_rollout_decode/... and
        # jit_rollout_prefill/... in a profile
        def rollout_decode(p, tok, cache, adv):
            return model.decode_step(p, tok, cache, advance=adv)

        def rollout_prefill(p, toks, cache):
            return model.prefill(p, toks, cache)

        self._decode = jax.jit(rollout_decode)
        self._prefill = jax.jit(rollout_prefill)

        env = self.env
        temperature, top_p = self.temperature, self.top_p

        def rollout_env(state, actions, last_tok, active, acted, trng):
            # fallback action for rows that never emitted an action; rows
            # not taking the turn absorb inside env.step (action 0)
            actions = common.fallback_actions(actions, last_tok, active,
                                              acted, env.n_actions)
            env_actions = jnp.where(active, actions, 0).astype(jnp.int32)
            state, res = env.step(state, env_actions, common.env_rng(trng))
            return state, actions, res.obs_tokens, res.done

        def rollout_sample(logits, trng, t):
            tok, lp = common.sample_tokens(common.sample_rng(trng, t),
                                           logits, temperature, top_p)
            return tok, lp, common.action_mask(tok, env.n_actions)

        # an env that is not pure jnp runs the same transition eagerly
        self._env_compiled = bool(getattr(env, "jit_safe", False))
        self._env_step = (jax.jit(rollout_env) if self._env_compiled
                          else rollout_env)
        self._sample = jax.jit(rollout_sample)

        # reference-model pass for signature parity with the compiled
        # engine's in-graph fold; the python path is the semantic
        # reference, so it reuses the canonical ExpPrep stage program
        # (import deferred: repro.core's package init imports this module)
        from repro.core.train_step import make_ref_logprob_step
        self._ref_lp = jax.jit(make_ref_logprob_step(self.model))

    # ------------------------------------------------------------------
    def run(self, params, rng, batch: int, *, n_episodes=None, extra=None,
            ref_params=None, params_version: int = -1):
        """Roll out ``batch`` episodes. Returns (ExperienceBatch, stats).

        ``n_episodes`` exists for signature parity with the compiled
        engine; the python loop has no slot refill, so it must equal
        ``batch`` (or be None). ``ref_params`` fills
        ``exp.ref_logprobs`` (the compiled engine folds the same pass
        into its macro-step); ``params_version`` tags the stats."""
        if n_episodes is not None and n_episodes != batch:
            raise ValueError(
                "the python reference engine has no slot refill; use "
                "CompiledRolloutEngine for n_episodes != batch")
        env, model = self.env, self.model
        T = self.max_context
        B = batch

        with span("rollout.reset", rows=B):
            state = env.reset(rng, B)
            obs = to_host(env.encode_obs(state), "obs")   # (B, obs_len)

        tokens = np.full((B, T), TOK_PAD, np.int32)
        gen_mask = np.zeros((B, T), bool)
        logprobs = np.zeros((B, T), np.float32)
        turn_lengths = np.zeros((B, self.max_turns), np.int32)
        n_turns = np.zeros(B, np.int32)
        truncated = np.zeros(B, bool)

        olen = obs.shape[1]
        tokens[:, :olen] = obs
        pos = np.full(B, olen, np.int32)                  # per-row write ptr

        with span("rollout.prefill", rows=B):
            cache = model.init_cache(B, T)
            logits_buf, cache = self._prefill(
                params, jnp.asarray(tokens[:, :olen]), cache)
        done = np.zeros(B, bool)
        base_rng = jax.random.fold_in(rng, 1)

        def advance_rows(fed_tokens, mask):
            """Feed per-row tokens; only ``mask`` rows advance."""
            nonlocal logits_buf, cache
            with span("rollout.decode", rows=B, live=int(mask.sum())):
                new_logits, cache2 = self._decode(
                    params, jnp.asarray(fed_tokens), cache,
                    jnp.asarray(mask))
                logits_buf = jnp.where(jnp.asarray(mask)[:, None],
                                       new_logits, logits_buf)
                cache = cache2

        for turn in range(self.max_turns):
            if done.all():
                break
            trng = common.turn_rng(base_rng, turn)
            # rows that cannot fit another turn + observation get truncated
            room = pos + self.max_turn_tokens + olen <= T
            truncated |= (~done) & (~room)
            active = (~done) & room
            if not active.any():
                break

            waiting = ~active                            # rows skipping turn
            acted = waiting.copy()
            actions = np.zeros(B, np.int32)
            last_tok = np.zeros(B, np.int32)
            with span("rollout.generate", turn=turn, rows=int(active.sum())):
                for t in range(self.max_turn_tokens):
                    write = ~acted
                    if not write.any():
                        break
                    with span("rollout.sample", compiled=1):
                        sampled, lp, is_action = self._sample(
                            logits_buf, trng, t)
                        sampled_np = to_host(sampled, "sampled", np.int32)
                        lp_np = to_host(lp, "logprob", np.float32)

                        rows = np.nonzero(write)[0]
                        tokens[rows, pos[rows]] = sampled_np[rows]
                        gen_mask[rows, pos[rows]] = True
                        logprobs[rows, pos[rows]] = lp_np[rows]
                        pos[rows] += 1
                        turn_lengths[rows, turn] += 1
                        last_tok[rows] = sampled_np[rows]

                        newly = write & to_host(is_action, "action_mask")
                        actions[newly] = sampled_np[newly] - ACTION_BASE
                        acted |= newly

                    advance_rows(sampled_np, write)

            with span("rollout.env", compiled=int(self._env_compiled)):
                state, actions, res_obs, new_done = self._env_step(
                    state, actions, last_tok, active, acted, trng)
                actions = to_host(actions, "actions", np.int32)
                n_turns[active] += 1
                res_obs = to_host(res_obs, "obs")
                new_done = to_host(new_done, "done")

            # teacher-force the observation for still-running rows; rows
            # out of turn budget skip it (no generation can follow — the
            # trailing obs would only burn context and decode steps)
            feed = active & ~new_done
            if turn + 1 < self.max_turns and feed.any():
                with span("rollout.observe", tokens=olen):
                    for j in range(olen):
                        col_tok = np.where(feed, res_obs[:, j],
                                           TOK_PAD).astype(np.int32)
                        rows = np.nonzero(feed)[0]
                        tokens[rows, pos[rows]] = col_tok[rows]
                        pos[rows] += 1
                        advance_rows(col_tok, feed)
            done |= new_done | truncated

        rewards = to_host(state.reward, "reward", np.float32)
        # truncated episodes: zero reward (the Fig. 1 "low-quality data")
        rewards = np.where(truncated, 0.0, rewards)

        ref_logprobs = jnp.zeros((B, T), jnp.float32)
        if ref_params is not None:
            # match the compiled fold's convention: values only at fed
            # positions 1..pos-1, zero elsewhere (PAD tail excluded)
            fed = ((np.arange(T)[None, :] >= 1)
                   & (np.arange(T)[None, :] < pos[:, None]))
            with span("ref"):
                ref_lp = to_host(self._ref_lp(ref_params,
                                              jnp.asarray(tokens)),
                                 "ref_logprob")
            ref_logprobs = jnp.asarray(np.where(fed, ref_lp, 0.0))

        exp = ExperienceBatch(
            tokens=jnp.asarray(tokens),
            gen_mask=jnp.asarray(gen_mask),
            loss_mask=jnp.asarray(gen_mask),
            logprobs=jnp.asarray(logprobs),
            ref_logprobs=ref_logprobs,
            rewards=jnp.asarray(rewards),
            returns=jnp.asarray(rewards),
            advantages=jnp.asarray(reinforce_advantages(jnp.asarray(rewards))),
            context_len=jnp.asarray(pos),
            truncated=jnp.asarray(truncated),
        )
        stats = common.summarize(
            turn_lengths, pos.copy(), n_turns, truncated, rewards,
            episodes_started=B, episodes_returned=B,
            params_version=params_version)
        return exp, stats
