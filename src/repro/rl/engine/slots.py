"""Slot-based continuous batching for the compiled rollout engine.

The device batch is a pool of ``B`` *slots*. Each live slot runs one
episode; when an episode finishes (env terminal, truncation, or turn
budget) it is *harvested* into a fixed-size ``EpisodeStore`` of ``N``
episodes and — if episodes remain to launch — a fresh episode is *reset
into the freed slot in-graph*, so the device batch stays full instead of
draining as episodes finish (the serving-style continuous batching of
``examples/serve_batched.py``, promoted into training).

Everything here is pure ``jnp`` and runs inside the compiled macro-step:

  - ``harvest``: copy finished slot rows into the store at their
    episode id, by a select over (episode, slot) pairs — no host
    round-trip, no dynamic shapes.
  - ``refill_plan``: assign the next unlaunched episode ids to freed slots
    via a cumulative count, capped at ``N``.

Episode accounting (started == returned) is a tested invariant.
"""
from __future__ import annotations

from typing import Any, NamedTuple

import jax
import jax.numpy as jnp


class EpisodeStore(NamedTuple):
    """Harvested-episode output buffers, indexed by episode id (N rows)."""
    tokens: jax.Array          # (N, T) int32
    gen_mask: jax.Array        # (N, T) bool
    logprobs: jax.Array        # (N, T) f32
    ref_logprobs: jax.Array    # (N, T) f32 (in-graph ExpPrep; 0 when off)
    rewards: jax.Array         # (N,)   f32 (0 for truncated episodes)
    context_len: jax.Array     # (N,)   int32
    truncated: jax.Array       # (N,)   bool
    n_turns: jax.Array         # (N,)   int32
    turn_lengths: jax.Array    # (N, max_turns) int32


class SlotCarry(NamedTuple):
    """Full device-side state threaded through compiled macro-steps.

    Invariant between macro-steps: every live slot's observation is
    already fed (its ``logits`` are the next-token distribution), so a
    macro-step starts generating immediately — fresh episodes get their
    observation fed by the *combined* end-of-step feed scan (continuing
    rows' env observation and refilled rows' reset observation share one
    scan over ``obs_len`` decode steps).
    """
    cache: Any                 # model decode cache (exposes .pos (B,))
    logits: jax.Array          # (B, V) last decode logits per slot
    env_state: Any             # env state pytree, batch-B leaves
    tokens: jax.Array          # (B, T) int32 episode context buffer
    gen_mask: jax.Array        # (B, T) bool
    logprobs: jax.Array        # (B, T) f32
    pos: jax.Array             # (B,) int32 per-row write pointer
    live: jax.Array            # (B,) bool — slot holds a running episode
    truncated: jax.Array       # (B,) bool — live episode hit the ctx limit
    n_turns: jax.Array         # (B,) int32
    turn_lengths: jax.Array    # (B, max_turns) int32
    episode: jax.Array         # (B,) int32 episode id in [0, N); N = idle
    launched: jax.Array        # () int32 — episodes started (reset into slots)
    returned: jax.Array        # () int32 — episodes harvested
    store: EpisodeStore
    # in-graph experience preparation (None/zeros when no ref model): the
    # frozen reference model decodes the same token stream as the policy
    # inside the macro-step, so ExpPrep never re-runs a full-context
    # forward pass after the rollout (ROADMAP "in-graph ExpPrep")
    ref_cache: Any = None      # reference-model decode cache (dense)
    ref_logits: Any = None     # (B, V) ref logits (next-token distribution)
    ref_logprobs: Any = None   # (B, T) f32 ref log-probs of fed tokens
    # paged-pool telemetry (scalars; zeros for dense layouts)
    pages_peak: Any = None     # () int32 peak pool occupancy
    kv_dropped: Any = None     # () int32 cumulative dropped KV writes
    kv_shortfall: Any = None   # (B,) int32 current per-slot dropped tokens
    # shared-prefix run (None when share_prefix is off): the pinned pool
    # pages holding the common prompt's full pages, prefilled once at
    # init and forked into every refilled slot (``engine/paging.
    # fork_prefix``). The engine holds one reference on each so the run
    # survives all its episode owners.
    prefix_pages: Any = None   # (shared_pages,) int32 pool page indices
    # preemption bookkeeping (None unless on_exhaust="preempt"): a slot
    # evicted by the memory-pressure governor releases its pages, its
    # episode id enters the ``requeue`` bitmap, and the admission planner
    # (``admission_plan``) re-launches it from scratch once the pool has
    # headroom again — every preempted episode is eventually re-run, so
    # an undersized pool degrades to *slower*, never to *lost context*.
    preempted: Any = None      # () int32 cumulative slot preemptions
    requeue: Any = None        # (N,) bool — episodes awaiting re-admission
    requeue_peak: Any = None   # () int32 peak requeue depth
    # in-graph speculative decoding (None unless speculation is on): the
    # draft model's dense decode cache rides the carry next to the
    # policy's paged cache — its fill line is rolled back to the
    # committed position after every verify round, so it only ever holds
    # committed-token K/V (plus invisible entries above the fill line)
    draft_cache: Any = None    # draft-model decode cache (dense)
    spec_proposed: Any = None  # () int32 draft tokens proposed
    spec_accepted: Any = None  # () int32 draft tokens accepted
    spec_rounds: Any = None    # () int32 verify rounds (row-iterations)


def init_store(n_episodes: int, max_context: int,
               max_turns: int) -> EpisodeStore:
    N, T = n_episodes, max_context
    return EpisodeStore(
        tokens=jnp.zeros((N, T), jnp.int32),
        gen_mask=jnp.zeros((N, T), bool),
        logprobs=jnp.zeros((N, T), jnp.float32),
        ref_logprobs=jnp.zeros((N, T), jnp.float32),
        rewards=jnp.zeros((N,), jnp.float32),
        context_len=jnp.zeros((N,), jnp.int32),
        truncated=jnp.zeros((N,), bool),
        n_turns=jnp.zeros((N,), jnp.int32),
        turn_lengths=jnp.zeros((N, max_turns), jnp.int32),
    )


def harvest(store: EpisodeStore, *, finished, episode, tokens, gen_mask,
            logprobs, rewards, pos, truncated, n_turns,
            turn_lengths, ref_logprobs=None) -> EpisodeStore:
    """Copy finished slot rows into the store at their episode id.

    Store row ``n`` takes the row of the finished slot running episode
    ``n`` and keeps its own otherwise: a select and a sum over the slot
    axis, with no host sync. It is not written as a (B -> N) scatter with
    out-of-bounds drops: with the store's rows sharded over the data axis
    of a dp=4 mesh of TPU v5e chips, that scatter put rows in the wrong
    episodes.
    """
    N = store.tokens.shape[0]
    match = finished[None, :] & (
        episode[None, :] == jnp.arange(N, dtype=episode.dtype)[:, None])
    hit = jnp.any(match, axis=1)                              # (N,)

    def put(buf, row):
        m = match.reshape(match.shape + (1,) * (row.ndim - 1))  # (N, B, …)
        picked = jnp.where(m, row[None], jnp.zeros((), row.dtype))
        # at most one slot matches a row, so the sum is that slot's value
        picked = (jnp.any(picked, axis=1) if row.dtype == jnp.bool_
                  else jnp.sum(picked, axis=1, dtype=row.dtype))
        return jnp.where(hit.reshape((N,) + (1,) * (buf.ndim - 1)),
                         picked, buf)

    return EpisodeStore(
        tokens=put(store.tokens, tokens),
        gen_mask=put(store.gen_mask, gen_mask),
        logprobs=put(store.logprobs, logprobs),
        ref_logprobs=(put(store.ref_logprobs, ref_logprobs)
                      if ref_logprobs is not None else store.ref_logprobs),
        rewards=put(store.rewards, rewards),
        context_len=put(store.context_len, pos),
        truncated=put(store.truncated, truncated),
        n_turns=put(store.n_turns, n_turns),
        turn_lengths=put(store.turn_lengths, turn_lengths),
    )


def refill_plan(finished, launched, n_episodes: int):
    """Assign fresh episode ids to freed slots.

    Returns (refill_mask, new_ids, launched') where ``refill_mask`` marks
    slots that receive a new episode, ``new_ids`` are their episode ids
    (0 where unused), and ``launched'`` is the updated launch counter.
    Finished slots beyond the remaining-episode budget go idle.
    """
    finished = jnp.asarray(finished)
    order = jnp.cumsum(finished.astype(jnp.int32)) - 1      # rank among freed
    new_ids = launched + order
    refill = finished & (new_ids < n_episodes)
    launched = launched + jnp.sum(refill.astype(jnp.int32))
    return refill, jnp.where(refill, new_ids, 0), launched


def admission_plan(free_slots, requeue, launched, n_episodes: int, quota):
    """Watermark-gated refill for ``on_exhaust="preempt"``.

    Like ``refill_plan``, but (a) slots freed by preemption or earlier
    admission throttling are candidates too (``free_slots``, not just
    this turn's finished set), (b) *re-queued* episodes — preempted
    earlier, awaiting a restart — are admitted FIRST, in ascending
    episode-id order, before any fresh id is launched, and (c) at most
    ``quota`` episodes are admitted this turn (the pressure governor
    computes the quota from the pool's free-page headroom above the
    low-watermark, so admission never re-creates the exhaustion that
    caused the preemption).

    free_slots: (B,) bool; requeue: (N,) bool; quota: () int32.
    Returns ``(admit, new_ids, launched', requeue')``. ``launched`` only
    advances for fresh ids — a re-admitted episode was already counted
    at its first launch, preserving the started == returned invariant.
    """
    free_slots = jnp.asarray(free_slots)
    requeue = jnp.asarray(requeue)
    quota = jnp.asarray(quota, jnp.int32)
    B = free_slots.shape[0]
    N = requeue.shape[0]
    rank = jnp.cumsum(free_slots.astype(jnp.int32)) - 1      # admission rank
    n_rq = jnp.sum(requeue.astype(jnp.int32))
    # rank-match requeued ids: the r-th admission takes the r-th (lowest)
    # requeued episode id — same cumsum scatter as the page allocator
    rq_rank = jnp.cumsum(requeue.astype(jnp.int32)) - 1      # (N,)
    slot_of = jnp.where(requeue & (rq_rank < B), rq_rank, B)
    rank_to_eid = jnp.full((B,), N, jnp.int32).at[slot_of].set(
        jnp.arange(N, dtype=jnp.int32), mode="drop")
    from_rq = free_slots & (rank < n_rq)
    fresh_id = launched + (rank - n_rq)                      # ranks >= n_rq
    eid = jnp.where(from_rq, rank_to_eid[jnp.clip(rank, 0, B - 1)],
                    fresh_id).astype(jnp.int32)
    have = free_slots & (from_rq
                         | ((rank >= n_rq) & (fresh_id < n_episodes)))
    admit = have & (rank < quota)
    launched = launched + jnp.sum((admit & ~from_rq).astype(jnp.int32))
    requeue = requeue.at[jnp.where(admit & from_rq, eid, N)].set(
        False, mode="drop")
    return admit, jnp.where(admit, eid, 0), launched, requeue
