"""Program spans (``repro.utils.tracing``) as the profiler records them.

Each test runs a piece of the program at test size under
``jax.profiler.trace`` and reads the ``earl.*`` host events back from the
trace file: the python engine's spans must number exactly what its loop
structure implies, the compiled engine reads its returned-episode counter
once per macro-step, and the trainer's stages and garbage collections
appear with their stats.
"""
import gc
import glob
from collections import Counter
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.rl.envs.base import TOK_OBS_BASE, StepResult
from repro.utils import tracing

B, TURNS, TOKENS, OBS = 2, 2, 2, 3


class EndlessState(NamedTuple):
    done: jax.Array
    reward: jax.Array


class Endless:
    """An environment whose episodes never end, so every row takes every
    turn and gets every observation."""
    n_actions = 4
    jit_safe = True

    def reset(self, rng, batch):
        return EndlessState(done=jnp.zeros((batch,), bool),
                            reward=jnp.zeros((batch,), jnp.float32))

    def encode_obs(self, state):
        b = state.done.shape[0]
        return jnp.full((b, OBS), TOK_OBS_BASE, jnp.int32)

    def step(self, state, actions, rng):
        return state, StepResult(reward=state.reward, done=state.done,
                                 obs_tokens=self.encode_obs(state))


def profiled(tmp_path, fn):
    """``fn()`` under ``jax.profiler.trace``; returns its result and the
    ``earl.*`` host events as (name, start_ns, end_ns, stats)."""
    with jax.profiler.trace(str(tmp_path)):
        out = fn()
    path, = glob.glob(str(tmp_path / "plugins/profile/*/*.xplane.pb"))
    events = []
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(tracing.PREFIX):
                    events.append((e.name[len(tracing.PREFIX):], e.start_ns,
                                   e.start_ns + e.duration_ns,
                                   dict(e.stats)))
    return out, sorted(events, key=lambda x: x[1])


def inside(events, name, outer):
    """Events called ``name`` within some event called ``outer``."""
    spans = [(s, e) for n, s, e, _ in events if n == outer]
    return [x for x in events
            if x[0] == name and any(a <= x[1] and x[2] <= z
                                    for a, z in spans)]


@pytest.fixture(scope="module")
def smoke_model():
    from repro.configs.base import get_smoke_config
    from repro.models.registry import build_model
    model = build_model(get_smoke_config("qwen2-0.5b"))
    return model, model.init(jax.random.PRNGKey(0))


def test_python_engine_spans_match_its_loop(smoke_model, tmp_path):
    from repro.rl.rollout import RolloutEngine
    model, params = smoke_model
    eng = RolloutEngine(model, Endless(), max_turns=TURNS,
                        max_turn_tokens=TOKENS, max_context=32)
    rng = jax.random.PRNGKey(3)
    eng.run(params, rng, B, ref_params=params)          # compile first
    (exp, stats), ev = profiled(
        tmp_path, lambda: eng.run(params, rng, B, ref_params=params))
    count = Counter(n for n, *_ in ev)

    # a turn samples until every row has emitted an action token or the
    # turn's budget is spent; each sampled token is one decode call, and
    # the one observation between the turns is fed a token per call
    tl = stats.turn_lengths                        # (B, TURNS)
    sampled = int(tl.max(axis=0).sum())
    assert 1 <= tl.min() and tl.max() <= TOKENS
    assert count["rollout.reset"] == count["rollout.prefill"] == 1
    assert count["rollout.generate"] == count["rollout.env"] == TURNS
    assert count["rollout.sample"] == sampled
    assert count["rollout.observe"] == TURNS - 1
    assert count["rollout.decode"] == sampled + OBS * (TURNS - 1)
    assert count["ref"] == 1
    # reads: the first observation; per sampled token the token, its
    # log-prob and the action mask; per turn the actions, the observation
    # and the done flags; then the rewards and the reference log-probs
    assert count["sync"] == 1 + 3 * sampled + 3 * TURNS + 2
    whats = Counter(st["what"] for n, *_, st in ev if n == "sync")
    assert whats["sampled"] == whats["logprob"] == sampled
    assert whats["ref_logprob"] == whats["reward"] == 1

    decode = [st for n, *_, st in ev if n == "rollout.decode"]
    assert all(st["rows"] == B for st in decode)
    # live rows: every generated token, then every row for each fed
    # observation token
    assert sum(st["live"] for st in decode) == tl.sum() + OBS * B
    assert [st["turn"] for n, *_, st in ev if n == "rollout.generate"] \
        == list(range(TURNS))
    assert all(st["tokens"] == OBS
               for n, *_, st in ev if n == "rollout.observe")
    # sampling and decode calls sit inside their turn's generate span
    assert len(inside(ev, "rollout.sample", "rollout.generate")) == sampled
    assert len(inside(ev, "rollout.decode", "rollout.observe")) == \
        OBS * (TURNS - 1)


def test_python_engine_names_its_programs(smoke_model):
    from repro.rl.rollout import RolloutEngine
    model, params = smoke_model
    eng = RolloutEngine(model, Endless(), max_turns=1, max_turn_tokens=1,
                        max_context=16)
    cache = model.init_cache(B, 16)
    toks = jnp.zeros((B, OBS), jnp.int32)
    assert "@jit_rollout_prefill" in \
        eng._prefill.lower(params, toks, cache).as_text()
    assert "@jit_rollout_decode" in eng._decode.lower(
        params, toks[:, 0], cache, jnp.ones((B,), bool)).as_text()
    rng = jax.random.PRNGKey(0)
    rows = jnp.ones((B,), bool)
    assert "@jit_rollout_env" in eng._env_step.lower(
        eng.env.reset(rng, B), toks[:, 0], toks[:, 0], rows, ~rows,
        rng).as_text()
    logits = jnp.zeros((B, model.cfg.vocab_size))
    assert "@jit_rollout_sample" in \
        eng._sample.lower(logits, rng, 0).as_text()


@pytest.mark.parametrize("jit_safe", [True, False])
def test_python_engine_env_program_follows_jit_safe(smoke_model, tmp_path,
                                                    jit_safe):
    from repro.rl.rollout import RolloutEngine
    model, params = smoke_model
    env = type("Declared", (Endless,), {"jit_safe": jit_safe})()
    eng = RolloutEngine(model, env, max_turns=TURNS, max_turn_tokens=TOKENS,
                        max_context=32)
    rng = jax.random.PRNGKey(3)
    eng.run(params, rng, B)                              # compile first
    (_, stats), ev = profiled(tmp_path, lambda: eng.run(params, rng, B))
    env_spans = [st for n, *_, st in ev if n == "rollout.env"]
    sample_spans = [st for n, *_, st in ev if n == "rollout.sample"]
    assert [st["compiled"] for st in env_spans] == [int(jit_safe)] * TURNS
    assert sample_spans and all(st["compiled"] == 1 for st in sample_spans)
    # either way the env transition reads the same three things a turn
    sampled = int(stats.turn_lengths.max(axis=0).sum())
    assert Counter(n for n, *_ in ev)["sync"] == 1 + 3 * sampled \
        + 3 * TURNS + 1


def test_compiled_engine_reads_returned_once_per_macro_step(smoke_model,
                                                            tmp_path):
    from repro.rl.engine import CompiledRolloutEngine
    from repro.rl.envs import make_env
    model, params = smoke_model
    eng = CompiledRolloutEngine(model, make_env("tictactoe"),
                                max_turns=TURNS, max_turn_tokens=TOKENS,
                                max_context=64)
    rng = jax.random.PRNGKey(1)
    eng.run(params, rng, B)                              # compile first
    _, ev = profiled(tmp_path, lambda: eng.run(params, rng, B))
    count = Counter(n for n, *_ in ev)
    returned = [x for x in ev
                if x[0] == "sync" and x[3]["what"] == "returned"]
    turns = [x for x in ev if x[0] == "rollout.turn"]
    assert count["rollout.init"] == 1
    assert 1 <= len(turns) == len(returned)
    assert [st["turn"] for *_, st in turns] == list(range(len(turns)))
    # each read follows its own macro-step's dispatch
    for (_, _, turn_end, _), (_, read_start, _, _) in zip(turns, returned):
        assert read_start >= turn_end
    whats = Counter(x[3]["what"] for x in ev if x[0] == "sync")
    assert set(whats) == {"returned", "stats"} and whats["stats"] == 1


def test_trainer_stages_carry_their_step(smoke_model, tmp_path):
    from repro.core.stages import EarlTrainer
    model, params = smoke_model
    tr = EarlTrainer(model, Endless(), batch_size=B, max_turns=TURNS,
                     max_turn_tokens=TOKENS, max_context=32, kl_coef=0.1)
    _, opt_state, ref = tr.init_state()
    params, opt_state, _ = tr.run_step(0, params, opt_state, ref)
    (_, _, rec), ev = profiled(
        tmp_path, lambda: tr.run_step(1, params, opt_state, ref))
    assert rec.wall_time_s > 0
    for name in ("step", "rollout", "expprep", "dispatch", "update"):
        got = [st for n, *_, st in ev if n == name]
        assert got == [{"step": 1}], name
        assert len(inside(ev, name, "step")) == 1
    # the record's reads of the update's metrics end the step, after the
    # update's dispatch
    update_end = next(e for n, _, e, _ in ev if n == "update")
    reads = [st["what"] for n, s, _, st in inside(ev, "sync", "step")
             if s >= update_end]
    assert reads == ["loss", "kl", "is_weight_mean"]
    assert not hasattr(rec, "rollout_wall_s")
    assert not hasattr(rec, "update_wall_s")


def test_gc_spans(tmp_path):
    tracing.watch_gc()
    tracing.watch_gc()
    assert gc.callbacks.count(tracing._on_gc) == 1
    _, ev = profiled(tmp_path, lambda: gc.collect(2))
    assert [st for n, *_, st in ev if n == "gc"][-1] == {"generation": 2}
    assert not tracing._open_gc


def test_to_host_reads_inside_a_sync_span(tmp_path):
    x, ev = profiled(tmp_path, lambda: tracing.to_host(
        jnp.arange(3) * 2, "probe", np.float32))
    assert x.dtype == np.float32 and x.tolist() == [0.0, 2.0, 4.0]
    assert [st for n, *_, st in ev if n == "sync"] == [{"what": "probe"}]
