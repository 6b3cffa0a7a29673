"""Vectorized Connect-Four, the paper's evaluation task (a copy of
``repro.rl.envs.connect_four``, kept as the benchmark's traffic
generator), with seeded openings.

``opening_plies`` > 0 starts each episode from a position of the kind
that John Tromp's Connect-4 data set holds (UCI Machine Learning
Repository, "Connect-4", 1995: legal positions in which neither player
has won and the next move is not forced; the data set's are 8-ply):
per row, the first of ``OPENING_TRIES`` games of random legal plies,
agent (piece 1) and opponent (piece 2) alternating, in which neither has
four in a row and neither can complete four with one drop; a row where
no try qualifies (under 1e-6 at 8 or 10 plies) starts from the empty
board. The openings are drawn from the reset key in one compiled call.
Within four moves of a random player a game is decided in about 2% of
episodes from the empty board, 17% from such 8-ply positions and 21%
from 10-ply ones. The observation stays 45 tokens: BOS, 42 cells, a
result token and a turn marker.
"""
from __future__ import annotations

from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp

from bench.traffic.envbase import (StepResult, TOK_BOS, TOK_DRAW,
                                   TOK_ILLEGAL, TOK_LOSS, TOK_OBS_BASE,
                                   TOK_TURN, TOK_WIN, default_reset_rows)

ROWS, COLS = 6, 7


def _wins(board, piece):
    """board: (B, 6, 7). Four in a row in any direction."""
    b = (board == piece)
    win = jnp.zeros(board.shape[0], bool)
    for dr, dc in ((0, 1), (1, 0), (1, 1), (1, -1)):
        r_span = ROWS - 3 * abs(dr)
        c0 = 3 if dc < 0 else 0
        c_span = COLS - 3 * abs(dc)
        acc = jnp.ones((board.shape[0], r_span, c_span), bool)
        for i in range(4):
            r = i * dr
            c = c0 + i * dc
            acc &= b[:, r:r + r_span, c:c + c_span]
        win |= jnp.any(acc, axis=(1, 2))
    return win


class C4State(NamedTuple):
    board: jax.Array     # (B, 6, 7) int32; row 0 = top, row 5 = bottom
    done: jax.Array
    reward: jax.Array


def _drop(board, col, piece, active):
    """Drop ``piece`` into ``col`` where ``active``; returns (board, legal)."""
    B = board.shape[0]
    colvals = jnp.take_along_axis(
        board, col[:, None, None].repeat(ROWS, 1), axis=2)[:, :, 0]
    n_empty = jnp.sum(colvals == 0, axis=1)
    legal = n_empty > 0
    row = jnp.clip(n_empty - 1, 0, ROWS - 1)
    do = active & legal
    updated = board.at[jnp.arange(B), row, col].set(
        jnp.where(do, piece, board[jnp.arange(B), row, col]))
    return jnp.where(do[:, None, None], updated, board), legal


def _random_col(rng, board):
    free = board[:, 0, :] == 0
    g = jax.random.gumbel(rng, (board.shape[0], COLS))
    return jnp.argmax(jnp.where(free, g, -jnp.inf), axis=-1)


def _threatens(board, piece):
    """(B,) whether ``piece`` completes four with one drop somewhere."""
    B = board.shape[0]
    hit = jnp.zeros(B, bool)
    for c in range(COLS):
        nb, legal = _drop(board, jnp.full(B, c), piece, jnp.ones(B, bool))
        hit |= legal & _wins(nb, piece)
    return hit


OPENING_TRIES = 16


@partial(jax.jit, static_argnums=(1, 2))
def _openings(rng, batch, plies):
    """(batch, 6, 7) boards: per row the first of ``OPENING_TRIES``
    random games of ``plies`` plies that no one has won and in which the
    next move is not forced; the empty board where none is."""
    empty = jnp.zeros((batch, ROWS, COLS), jnp.int32)
    every = jnp.ones(batch, bool)

    def attempt(k, carry):
        board, found = carry
        key = jax.random.fold_in(rng, k)

        def ply(i, b):
            col = _random_col(jax.random.fold_in(key, i), b)
            return _drop(b, col, 1 + i % 2, every)[0]
        cand = jax.lax.fori_loop(0, plies, ply, empty)
        ok = ~(_wins(cand, 1) | _wins(cand, 2)
               | _threatens(cand, 1) | _threatens(cand, 2))
        board = jnp.where((ok & ~found)[:, None, None], cand, board)
        return board, found | ok

    return jax.lax.fori_loop(0, OPENING_TRIES, attempt,
                             (empty, jnp.zeros(batch, bool)))[0]


class ConnectFour:
    n_actions = COLS
    jit_safe = True

    def __init__(self, opening_plies: int = 0):
        self.opening_plies = opening_plies
        self.obs_len = 3 + ROWS * COLS
        # BOS alone is common to every episode once openings differ
        self.prompt_prefix_len = 1 if opening_plies else self.obs_len

    def reset(self, rng, batch: int) -> C4State:
        board = (_openings(rng, batch, self.opening_plies)
                 if self.opening_plies else
                 jnp.zeros((batch, ROWS, COLS), jnp.int32))
        return C4State(board=board, done=jnp.zeros((batch,), bool),
                       reward=jnp.zeros((batch,), jnp.float32))

    def reset_rows(self, rng, state: C4State, mask) -> C4State:
        return default_reset_rows(self, rng, state, mask)

    def legal_mask(self, state: C4State):
        return state.board[:, 0, :] == 0

    def encode_obs(self, state: C4State, result_tok=None):
        B = state.board.shape[0]
        cells = (TOK_OBS_BASE + state.board).reshape(B, ROWS * COLS)
        bos = jnp.full((B, 1), TOK_BOS, jnp.int32)
        res = (jnp.full((B, 1), TOK_TURN, jnp.int32)
               if result_tok is None else result_tok[:, None])
        turn = jnp.full((B, 1), TOK_TURN, jnp.int32)
        return jnp.concatenate([bos, cells, res, turn], axis=1)

    def step(self, state: C4State, actions, rng) -> tuple:
        board, done, reward = state.board, state.done, state.reward

        top_free = jnp.take_along_axis(
            board[:, 0, :], actions[:, None], 1)[:, 0] == 0
        illegal_now = (~top_free) & (~done)
        play = (~done) & top_free

        board1, _ = _drop(board, actions, 1, play)
        agent_win = _wins(board1, 1) & play
        draw1 = jnp.all(board1[:, 0, :] != 0, axis=1) & play & ~agent_win

        cont = play & ~agent_win & ~draw1
        board2, _ = _drop(board1, _random_col(rng, board1), 2, cont)
        opp_win = _wins(board2, 2) & cont
        draw2 = jnp.all(board2[:, 0, :] != 0, axis=1) & cont & ~opp_win

        new_done = done | illegal_now | agent_win | draw1 | opp_win | draw2
        step_reward = (jnp.where(agent_win, 1.0, 0.0)
                       + jnp.where(opp_win | illegal_now, -1.0, 0.0))
        new_reward = jnp.where(done, reward, step_reward)

        result_tok = jnp.where(
            agent_win, TOK_WIN,
            jnp.where(opp_win, TOK_LOSS,
                      jnp.where(draw1 | draw2, TOK_DRAW,
                                jnp.where(illegal_now, TOK_ILLEGAL,
                                          TOK_TURN)))).astype(jnp.int32)
        new_state = C4State(board=board2, done=new_done, reward=new_reward)
        obs = self.encode_obs(new_state, result_tok)
        edge = new_done & (~done)
        return new_state, StepResult(reward=new_reward * edge,
                                     done=new_done, obs_tokens=obs)
