"""RL substrate tests: advantage estimators, losses, environments, and the
multi-turn rollout engine."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.rl.algo import (group_relative_advantages, policy_gradient_loss,
                           reinforce_advantages, returns_to_go,
                           token_logprobs)
from repro.rl.envs import make_env
from repro.rl.experience import ExperienceBatch, zeros_like_experience


class TestAdvantages:
    def test_loo_baseline_is_mean_zero_ish(self):
        """Leave-one-out REINFORCE advantages sum to ~0 when rewards vary
        (property-based; skipped when hypothesis is not installed)."""
        pytest.importorskip("hypothesis")
        from hypothesis import given, settings, strategies as st

        @settings(max_examples=100, deadline=None)
        @given(st.lists(st.floats(min_value=-10, max_value=10,
                                  allow_nan=False), min_size=2, max_size=64))
        def prop(rewards):
            r = jnp.asarray(rewards, jnp.float32)
            adv = reinforce_advantages(r)
            # identity: sum of LOO advantages = sum(r) - sum(loo) = 0 exactly
            # when every loo is the mean of the others: B/(B-1) * (sum - ...)
            assert float(jnp.abs(jnp.mean(adv))) < 1e-3 + 0.1 * float(
                jnp.std(r))

        prop()

    def test_loo_is_independent_of_own_reward(self):
        r1 = jnp.array([1.0, 0.0, 0.0, 0.0])
        r2 = jnp.array([5.0, 0.0, 0.0, 0.0])
        a1 = reinforce_advantages(r1)
        a2 = reinforce_advantages(r2)
        # baseline for row 0 is mean of others — unchanged
        assert float(a1[0] - (1.0 - 0.0)) == pytest.approx(0.0)
        assert float(a2[0] - (5.0 - 0.0)) == pytest.approx(0.0)

    def test_group_advantages_normalize_per_group(self):
        r = jnp.array([1.0, 2.0, 3.0, 4.0, 10.0, 20.0, 30.0, 40.0])
        adv = group_relative_advantages(r, group_size=4)
        g = np.asarray(adv).reshape(2, 4)
        np.testing.assert_allclose(g.mean(axis=1), 0.0, atol=1e-5)
        np.testing.assert_allclose(g.std(axis=1), 1.0, atol=1e-2)

    def test_returns_to_go(self):
        r = jnp.array([[0.0, 0.0, 1.0]])
        np.testing.assert_allclose(np.asarray(returns_to_go(r, 0.5)[0]),
                                   [0.25, 0.5, 1.0])


class TestLoss:
    def test_token_logprobs_matches_take_along_axis(self, rng):
        logits = jax.random.normal(rng, (2, 5, 17))
        toks = jax.random.randint(jax.random.fold_in(rng, 1), (2, 5), 0, 17)
        lp = token_logprobs(logits, toks)
        expect = jnp.take_along_axis(
            jax.nn.log_softmax(logits, -1), toks[..., None], -1)[..., 0]
        np.testing.assert_allclose(np.asarray(lp), np.asarray(expect),
                                   atol=1e-5, rtol=1e-5)

    def test_reinforce_gradient_direction(self, rng):
        """Positive-advantage tokens get their logprob pushed UP."""
        logits = jnp.zeros((1, 1, 4))
        toks = jnp.array([[2]])
        mask = jnp.ones((1, 1))

        def loss_fn(lg):
            lp = token_logprobs(lg, toks)
            loss, _ = policy_gradient_loss(lp, jnp.array([1.0]), mask)
            return loss

        g = jax.grad(loss_fn)(logits)
        assert float(g[0, 0, 2]) < 0          # decrease loss => raise logit

    def test_ppo_clip_caps_ratio(self):
        lp_new = jnp.array([[1.0]])           # ratio = e
        lp_old = jnp.array([[0.0]])
        mask = jnp.ones((1, 1))
        loss, m = policy_gradient_loss(lp_new, jnp.array([1.0]), mask,
                                       old_logprobs=lp_old, clip_eps=0.2)
        assert float(loss) == pytest.approx(-1.2)   # clipped at 1+eps
        assert float(m["clip_frac"]) == 1.0

    def test_kl_penalty_zero_at_match(self):
        lp = jnp.array([[0.5, -0.3]])
        mask = jnp.ones((1, 2))
        loss_with, m = policy_gradient_loss(lp, jnp.array([0.0]), mask,
                                            ref_logprobs=lp, kl_coef=0.1)
        assert float(m["kl"]) == pytest.approx(0.0, abs=1e-6)


@pytest.mark.parametrize("env_name", ["tictactoe", "connect_four", "bandit"])
class TestEnvs:
    def test_reset_shapes(self, env_name, rng):
        env = make_env(env_name)
        state = env.reset(rng, 4)
        obs = env.encode_obs(state)
        assert obs.shape == (4, env.obs_len)
        assert bool((obs >= 0).all())

    def test_episode_terminates_and_rewards_bounded(self, env_name, rng):
        env = make_env(env_name)
        B = 8
        state = env.reset(rng, B)
        for t in range(50):
            legal = np.asarray(env.legal_mask(state))
            acts = np.array([np.flatnonzero(row)[0] if row.any() else 0
                             for row in legal], np.int32)
            rng, sub = jax.random.split(rng)
            state, res = env.step(state, jnp.asarray(acts), sub)
            if bool(np.asarray(res.done).all()):
                break
        assert bool(np.asarray(state.done).all()), "episodes must terminate"
        r = np.asarray(state.reward)
        assert ((r >= -1.0) & (r <= 1.0)).all()

    def test_repeated_action_eventually_ends_episode(self, env_name, rng):
        """Hammering one action must terminate (illegal-move rule in
        tictactoe; column-full or win/loss in connect_four; single pull in
        bandit)."""
        env = make_env(env_name)
        state = env.reset(rng, 2)
        for _ in range(10):
            rng, sub = jax.random.split(rng)
            state, res = env.step(state, jnp.array([0, 0]), sub)
        done = np.asarray(state.done)
        reward = np.asarray(state.reward)
        assert done.all()
        assert ((reward >= -1) & (reward <= 1)).all()

    def test_reset_rows_refreshes_only_masked(self, env_name, rng):
        """Slot-refill primitive: masked rows get a fresh episode, others
        keep their state bit-for-bit."""
        env = make_env(env_name)
        state = env.reset(rng, 4)
        for _ in range(2):
            rng, sub = jax.random.split(rng)
            state, _ = env.step(state, jnp.zeros(4, jnp.int32), sub)
        mask = jnp.array([True, False, True, False])
        rng, sub = jax.random.split(rng)
        state2 = env.reset_rows(sub, state, mask)
        fresh = env.reset(sub, 4)
        for new, old, ref in zip(jax.tree.leaves(state2),
                                 jax.tree.leaves(state),
                                 jax.tree.leaves(fresh)):
            np.testing.assert_array_equal(np.asarray(new[1::2]),
                                          np.asarray(old[1::2]))
            np.testing.assert_array_equal(np.asarray(new[0::2]),
                                          np.asarray(ref[0::2]))


class TestBandit:
    def test_single_pull_terminates_with_signed_payout(self, rng):
        env = make_env("bandit")
        state = env.reset(rng, 16)
        acts = jnp.asarray(np.arange(16) % env.n_actions, jnp.int32)
        state, res = env.step(state, acts, jax.random.fold_in(rng, 1))
        assert bool(np.asarray(res.done).all())
        r = np.asarray(res.reward)
        assert np.isin(r, [-1.0, 1.0]).all()

    def test_hints_are_quantized_mean_levels(self, rng):
        env = make_env("bandit")
        state = env.reset(rng, 8)
        obs = np.asarray(env.encode_obs(state))
        from repro.rl.envs.base import TOK_OBS_BASE
        hints = obs[:, 1:1 + env.n_arms] - TOK_OBS_BASE
        assert (hints >= 0).all() and (hints < env.obs_levels).all()

    def test_best_arm_pull_beats_worst_in_expectation(self, rng):
        """The noisy hints must carry signal: pulling the true best arm
        wins more often than the true worst arm."""
        env = make_env("bandit")
        B = 256
        state = env.reset(rng, B)
        means = np.asarray(state.means)
        best = jnp.asarray(means.argmax(1), jnp.int32)
        worst = jnp.asarray(means.argmin(1), jnp.int32)
        _, res_b = env.step(state, best, jax.random.fold_in(rng, 1))
        _, res_w = env.step(state, worst, jax.random.fold_in(rng, 1))
        assert float(np.asarray(res_b.reward).mean()) > float(
            np.asarray(res_w.reward).mean())


class TestRolloutEngine:
    @pytest.fixture(scope="class")
    def setup(self):
        from repro.configs.base import get_smoke_config
        from repro.models.registry import build_model
        from repro.rl.rollout import RolloutEngine
        cfg = get_smoke_config("qwen2-0.5b")
        model = build_model(cfg)
        params = model.init(jax.random.PRNGKey(0))
        env = make_env("tictactoe")
        eng = RolloutEngine(model, env, max_turns=2, max_turn_tokens=4,
                            max_context=64)
        return eng, params

    def test_rollout_experience_invariants(self, setup, rng):
        eng, params = setup
        exp, stats = eng.run(params, rng, 4)
        assert isinstance(exp, ExperienceBatch)
        assert exp.tokens.shape == (4, 64)
        gen = np.asarray(exp.gen_mask)
        lp = np.asarray(exp.logprobs)
        # logprobs only where generated, and always <= 0
        assert (lp[~gen] == 0).all()
        assert (lp[gen] <= 1e-6).all()
        ctx = np.asarray(exp.context_len)
        assert (ctx <= 64).all() and (ctx > 0).all()
        assert stats.mean_context_len == pytest.approx(ctx.mean())

    def test_rollout_is_reproducible(self, setup, rng):
        eng, params = setup
        e1, _ = eng.run(params, rng, 3)
        e2, _ = eng.run(params, rng, 3)
        np.testing.assert_array_equal(np.asarray(e1.tokens),
                                      np.asarray(e2.tokens))

    @pytest.mark.parametrize("temperature", [0.0, 1.0])
    @pytest.mark.parametrize("env_name", ["tictactoe", "connect_four"])
    def test_env_program_matches_eager_env(self, setup, rng, env_name,
                                           temperature):
        """The env transition compiled (``jit_safe``) and run eagerly give
        the same episodes."""
        from repro.rl.rollout import RolloutEngine
        model = setup[0].model
        params = setup[1]
        env = make_env(env_name)
        eager_env = type("Eager", (type(env),), {"jit_safe": False})()
        out = []
        for e in (env, eager_env):
            eng = RolloutEngine(model, e, max_turns=3, max_turn_tokens=3,
                                max_context=160, temperature=temperature)
            assert eng._env_compiled == e.jit_safe
            out.append(eng.run(params, rng, 4)[0])
        jitted, eager = out
        for f in ("tokens", "gen_mask", "rewards", "context_len"):
            np.testing.assert_array_equal(np.asarray(getattr(jitted, f)),
                                          np.asarray(getattr(eager, f)))
        np.testing.assert_allclose(np.asarray(jitted.logprobs),
                                   np.asarray(eager.logprobs), atol=1e-5)

    @pytest.mark.parametrize("temperature,top_p",
                             [(0.0, 1.0), (1.0, 1.0), (0.7, 0.9)])
    def test_sample_program_matches_eager_ops(self, setup, temperature,
                                              top_p):
        """One compiled sampler call gives the tokens, log-probs and action
        mask of the eager key derivation, sampler and mask, at every token
        position of a turn."""
        from repro.rl.engine import common
        from repro.rl.rollout import RolloutEngine
        eng = RolloutEngine(setup[0].model, make_env("tictactoe"),
                            temperature=temperature, top_p=top_p)
        logits = jax.random.normal(jax.random.PRNGKey(5), (6, 512)) * 4
        # favour action tokens so the mask reads both ways
        logits = logits.at[:3, common.ACTION_BASE + 2].add(30.0)
        trng = common.turn_rng(jax.random.PRNGKey(6), 1)
        for t in range(3):
            tok, lp, is_action = eng._sample(logits, trng, t)
            want_tok, want_lp = common.sample_tokens(
                common.sample_rng(trng, t), logits, temperature, top_p)
            np.testing.assert_array_equal(np.asarray(tok),
                                          np.asarray(want_tok))
            np.testing.assert_allclose(np.asarray(lp), np.asarray(want_lp),
                                       atol=1e-5)
            np.testing.assert_array_equal(
                np.asarray(is_action),
                np.asarray(common.action_mask(want_tok, 9)))
            assert np.asarray(is_action).tolist() == [True] * 3 + [False] * 3
        assert eng._sample._cache_size() == 1     # one compile for every t


class TestActionFallback:
    """Regression for the fallback mask: rows that never emit an action
    token within the turn budget must fall back to last_token % n_actions
    (the mask is ``active & ~acted`` — ``acted`` starts as ``~active``)."""

    def test_fallback_mask_semantics(self):
        from repro.rl.engine.common import fallback_actions
        active = np.array([True, True, False])
        # row 0 never acted; row 1 emitted an action; row 2 was waiting
        # (acted is seeded with ~active, so waiting rows read as acted)
        acted = np.array([False, True, True])
        actions = np.array([0, 3, 5], np.int32)
        last_tok = np.array([10, 7, 9], np.int32)
        out = np.asarray(fallback_actions(actions, last_tok, active, acted,
                                          n_actions=9))
        assert out[0] == 10 % 9          # fallback fired
        assert out[1] == 3               # kept its emitted action
        assert out[2] == 5               # waiting row untouched

    def test_fallback_fires_end_to_end(self, rng):
        """A policy that never emits an action token must still act: the
        env receives last_token % n_actions for every row."""
        from types import SimpleNamespace
        from repro.rl.envs.tictactoe import TicTacToe
        from repro.rl.rollout import RolloutEngine

        FAV = 1                              # favored token: TOK_BOS < 32

        class NoActionModel:
            """Minimal Model stand-in whose logits always argmax to a
            non-action token."""
            cfg = SimpleNamespace(vocab_size=64)

            @staticmethod
            def _logits(B):
                return jnp.full((B, 64), -30.0).at[:, FAV].set(10.0)

            def init_cache(self, B, T, dtype=None):
                return jnp.zeros((B,), jnp.int32)

            def prefill(self, params, toks, cache, **kw):
                return self._logits(toks.shape[0]), cache

            def decode_step(self, params, tok, cache, advance=None, **kw):
                return self._logits(tok.shape[0]), cache

        seen = []

        class RecordingTTT(TicTacToe):
            # records on the host, so it cannot be traced
            jit_safe = False

            def step(self, state, actions, rng_):
                seen.append(np.asarray(actions))
                return super().step(state, actions, rng_)

        eng = RolloutEngine(NoActionModel(), RecordingTTT(), max_turns=1,
                            max_turn_tokens=3, max_context=64,
                            temperature=0.0)
        exp, _ = eng.run({}, rng, 4)
        assert len(seen) == 1
        np.testing.assert_array_equal(seen[0],
                                      np.full(4, FAV % 9, np.int32))
        # the fallback turn still logged its generated reasoning tokens
        assert (np.asarray(exp.gen_mask).sum(axis=1) == 3).all()


def test_experience_specs_match_zeros():
    from repro.rl.experience import experience_specs
    z = zeros_like_experience(4, 32)
    specs = experience_specs(4, 32)
    for a, s in zip(z, specs):
        assert a.shape == s.shape and a.dtype == s.dtype
