"""Pipeline schedules over the EARL stage graph (Fig. 2, pipelined).

``PipelineSchedule`` runs ``EarlTrainer``'s four stages under one of two
schedules:

  - ``mode="sync"`` — the paper's baseline loop: Rollout → ExpPrep →
    Dispatch → Update, strictly ordered, one step at a time. The trainer
    mesh idles during decode and the rollout mesh idles during the
    gradient step.

  - ``mode="async"`` — one-step-off software pipelining (AgentRL /
    AReaL-style): the Update stage for step k runs on a dedicated worker
    thread (the trainer mesh) while the main thread rolls out step k+1
    on the rollout mesh with *stale* params. Staleness is bounded by
    ``max_policy_lag`` (L): Rollout(k) samples with params version
    ``max(0, k - L)``, deterministically — fresher params are NOT picked
    up opportunistically, so a run is reproducible and ``L = 0`` degrades
    to the synchronous ordering bit-for-bit (tested) while still
    exercising the pipeline machinery. The in-flight update queue depth
    is bounded by the same L (the bounded staleness queue).

Why a thread, not a second jax process: stage programs are dispatched
asynchronously by XLA, so the worker's update execution and the main
thread's rollout dispatch genuinely overlap — on a multi-host/submesh
deployment each side drives its own device set (``rollout_trainer_split``
places them on disjoint submeshes via ``MeshConfig.device_offset``), on
the CPU smoke container they overlap host-side python with device
compute. No ``jax.block_until_ready`` separates the stages: the handoff
is the dispatcher's async entry point (the consumer is enqueued against
the in-flight transfer) and the only host syncs are the rollout engine's
per-turn scalar read and the deferred metrics read when a step's record
is finalized.

Off-policy correction: rolling out with stale params makes the sampled
experience off-policy by up to L updates. Configure the trainer with
``is_rho_max > 0`` so the Update stage reweights each token by the
truncated importance-sampling ratio between current and behavior
log-probs (``rl.algo.truncated_importance_weights``) — the recorded
``StepRecord.is_weight_mean``/``policy_lag`` make the correction
observable.

Fault tolerance (both modes): ``max_retries`` arms step-level retry with
exponential backoff (``retry_backoff_s * 2**attempt``), and
``checkpoint_dir``/``checkpoint_every`` persist ``{params, opt_state,
rng}`` through ``checkpoint.save_checkpoint`` every N completed steps.

  - **sync** retries the failed step in place: a sync step that raised
    never applied its update (the injected faults fire at stage
    boundaries, before the jitted update runs), so params/opt_state are
    still the pre-step state.
  - **async** cannot retry in place — the worker owns the live
    (params, opt_state) and the update program *donates* opt_state, so
    a crash mid-pipeline leaves no trustworthy in-memory state. Instead
    the whole pipeline restarts from the latest on-disk checkpoint
    (params, opt_state AND the trainer rng, so the resumed rollouts
    draw the keys the uninterrupted run would have drawn), re-running
    the steps after it; with no checkpoint available the error
    propagates. Shutdown is exception-safe either way: the executor is
    torn down in a ``finally`` with queued futures cancelled and
    completed ones drained, so a failed step never leaves the worker
    thread or an in-flight update dangling.

A checkpoint saved at step ``s`` means "``s`` steps completed"; resume
(``resume=True`` or the crash-restart path) continues at step ``s``.
The checkpoint is written inside the worker right after the update
commits — the single-worker executor serializes it with the next
update, so the saved (params, opt_state) pair is always consistent.
"""
from __future__ import annotations

import time
from concurrent.futures import ThreadPoolExecutor
from dataclasses import dataclass
from typing import Any, Dict, Optional

from repro.checkpoint.checkpoint import (latest_step, restore_checkpoint,
                                         save_checkpoint)


def _print_record(rec) -> None:
    print(f"step {rec.step:4d}  return {rec.mean_return:+.3f}  "
          f"ctx {rec.mean_context_len:6.1f}  "
          f"trunc {rec.truncated_frac:.2f}  "
          f"loss {rec.loss:+.4f}  lag {rec.policy_lag}")


@dataclass
class PipelineSchedule:
    """Runs the trainer's stage graph under a sync or async schedule."""

    trainer: Any                      # EarlTrainer (stage container)
    mode: str = "sync"                # "sync" | "async"
    max_policy_lag: int = 1           # async: bounded staleness (L)
    max_retries: int = 0              # step retries / pipeline restarts
    retry_backoff_s: float = 0.05     # base backoff (doubles per attempt)
    checkpoint_dir: Optional[str] = None
    checkpoint_every: int = 0         # save every N completed steps (0=off)
    resume: bool = False              # start from latest_step(checkpoint_dir)

    def run(self, n_steps: int, *, params, opt_state, ref_params=None,
            dst_shardings=None, verbose: bool = False):
        """Execute ``n_steps`` full pipeline iterations. Returns
        ``(params, opt_state, history)`` like the original loop."""
        start = 0
        if self.resume and self.checkpoint_dir:
            s = latest_step(self.checkpoint_dir)
            if s is not None:
                params, opt_state, start = self._restore(s, params,
                                                         opt_state)
        if start >= n_steps:
            return params, opt_state, self.trainer.history
        if self.mode == "sync":
            return self._run_sync(n_steps, start, params, opt_state,
                                  ref_params, dst_shardings, verbose)
        if self.mode == "async":
            return self._run_async(n_steps, start, params, opt_state,
                                   ref_params, dst_shardings, verbose)
        raise ValueError(f"unknown pipeline mode {self.mode!r}")

    # -- checkpoint plumbing ------------------------------------------------
    def _ckpt_tree(self, params, opt_state, rng):
        return {"params": params, "opt_state": opt_state, "rng": rng}

    def _maybe_save(self, done: int, params, opt_state, rng) -> None:
        """Persist state after ``done`` completed steps when due."""
        if (self.checkpoint_dir and self.checkpoint_every > 0
                and done > 0 and done % self.checkpoint_every == 0):
            save_checkpoint(self.checkpoint_dir, done,
                            self._ckpt_tree(params, opt_state, rng))

    def _restore(self, step: int, params, opt_state):
        """Load checkpoint ``step``; ``like`` trees are structure-only,
        so donated opt_state buffers from a crashed attempt are fine."""
        tr = self.trainer
        st = restore_checkpoint(
            self.checkpoint_dir, step,
            self._ckpt_tree(params, opt_state, tr._rng))
        tr._rng = st["rng"]
        return st["params"], st["opt_state"], step

    # -- synchronous (Fig. 2 baseline) --------------------------------------
    def _run_sync(self, n_steps, start, params, opt_state, ref_params,
                  dst_shardings, verbose):
        tr = self.trainer
        for step in range(start, n_steps):
            for attempt in range(self.max_retries + 1):
                try:
                    params, opt_state, rec = tr.run_step(
                        step, params, opt_state, ref_params,
                        dst_shardings=dst_shardings)
                    break
                except Exception:
                    if attempt >= self.max_retries:
                        raise
                    time.sleep(self.retry_backoff_s * (2 ** attempt))
            if verbose:
                _print_record(rec)
            self._maybe_save(step + 1, params, opt_state, tr._rng)
        return params, opt_state, tr.history

    # -- asynchronous one-step-off pipeline ---------------------------------
    def _run_async(self, n_steps, start, params, opt_state, ref_params,
                   dst_shardings, verbose):
        tr = self.trainer
        for attempt in range(self.max_retries + 1):
            try:
                return self._run_async_once(n_steps, start, params,
                                            opt_state, ref_params,
                                            dst_shardings, verbose)
            except Exception:
                s = (latest_step(self.checkpoint_dir)
                     if self.checkpoint_dir else None)
                if attempt >= self.max_retries or s is None:
                    raise
                # restart the pipeline from the last durable state; the
                # in-memory (params, opt_state) is untrustworthy (the
                # worker may have died mid-update, opt_state donated)
                params, opt_state, start = self._restore(s, params,
                                                         opt_state)
                # drop the aborted attempt's records for steps the
                # restart will re-run (it re-appends them)
                tr.history[:] = [r for r in tr.history if r.step < start]
                time.sleep(self.retry_backoff_s * (2 ** attempt))

    def _run_async_once(self, n_steps, start, params, opt_state,
                        ref_params, dst_shardings, verbose):
        tr = self.trainer
        L = max(0, int(self.max_policy_lag))
        versions: Dict[int, Any] = {start: params}  # update count -> params
        futures: Dict[int, Any] = {}             # step -> in-flight update
        pending: Dict[int, dict] = {}            # step -> rollout-side row
        # the worker owns the live (params, opt_state); single worker =>
        # updates apply strictly in step order
        state = {"params": params, "opt_state": opt_state}

        def submit(pool, k, exp, src_shardings):
            # rng snapshot AT SUBMIT TIME: step k's rollout has consumed
            # its key, step k+1's has not — exactly the stream position a
            # resume at step k+1 must restart from. (Captured here, not
            # in the worker: by the time the worker runs, the main
            # thread may have advanced the trainer rng further.)
            rng_after_k = tr._rng

            def work():
                handle = None
                tr.check_fault("dispatch", k)
                if dst_shardings is not None:
                    exp_d, handle = tr.dispatch_stage(
                        exp, dst_shardings, src_shardings=src_shardings,
                        asynchronous=True, step=k)
                else:
                    exp_d = exp
                tr.check_fault("update", k)
                p, o = state["params"], state["opt_state"]
                p2, o2, metrics = tr.update_stage(p, o, exp_d, step=k)
                state["params"], state["opt_state"] = p2, o2
                # checkpoint inside the worker: the single-worker pool
                # serializes this with the NEXT update, so the saved
                # pair is the consistent post-step-k state
                self._maybe_save(k + 1, p2, o2, rng_after_k)
                dispatch_row = None
                if handle is not None:
                    # the update is enqueued against the in-flight
                    # transfer; resolving the handle NOW (before the
                    # update's own sync) stamps a wall time that covers
                    # the transfer alone, not the overlapped compute
                    _, rep = handle.result()
                    dispatch_row = rep.row()
                return p2, o2, metrics, dispatch_row
            futures[k] = pool.submit(work)

        def resolve(k):
            """Finalize step k: wait for its update, publish the new
            params version, record the step."""
            p2, _, metrics, dispatch_row = futures.pop(k).result()
            versions[k + 1] = p2
            row = pending.pop(k)
            rec = tr.make_record(
                k, row["stats"], metrics, switch=row["switch"],
                dispatch_row=dispatch_row,
                wall_time_s=time.perf_counter() - row["t0"],
                policy_lag=row["policy_lag"])
            if verbose:
                _print_record(rec)

        pool = ThreadPoolExecutor(max_workers=1,
                                  thread_name_prefix="earl-update")
        try:
            for k in range(start, n_steps):
                v = max(start, k - L)        # behavior params version
                # bounded staleness: wait for updates up to v-1 so the
                # required version exists (in-flight queue depth <= L)
                while v not in versions:
                    resolve(min(futures))
                behavior = versions[v]
                # versions older than any future rollout can need are dead
                for old in [x for x in versions if x < v]:
                    del versions[old]

                t0 = time.perf_counter()
                tr.check_fault("rollout", k)
                tr._maybe_warn_ref_fallback(ref_params)
                exp, stats, switch = tr.rollout_stage(
                    k, behavior, tr._next_rng(), tr.batch_size,
                    n_episodes=tr.rollout_episodes,
                    ref_params=(ref_params if tr.ref_folded else None),
                    params_version=v)
                exp = tr.expprep_stage(
                    exp, ref_params=ref_params, ref_folded=tr.ref_folded,
                    # lag-1 fast path: the reference IS the behavior
                    # snapshot that generated this batch, and sampling
                    # recorded unbiased model log-probs
                    reuse_behavior_lp=(
                        ref_params is behavior and tr.top_p == 1.0
                        and (tr.temperature <= 0.0
                             or tr.temperature == 1.0)), step=k)
                # capture the engine-reported source layout NOW — the
                # next rollout overwrites it before the worker runs
                src = (tr.dispatch_stage.source_shardings(exp)
                       if dst_shardings is not None else None)
                # update-stage selector hook: its config is *tracked*
                # independently of the rollout stage's (both live at
                # once); bookkeeping/switch-log only until the update
                # program is rebound per MeshConfig (see run_step)
                if tr.selector is not None and tr.selector.policy is not None:
                    tr.selector.maybe_switch(k, stage="update")
                pending[k] = {
                    "stats": stats, "switch": switch, "t0": t0,
                    "policy_lag": k - v,
                }
                submit(pool, k, exp, src)

            while futures:                   # drain the pipeline
                resolve(min(futures))
        finally:
            # exception-safe teardown: never leave the worker thread or
            # an in-flight update dangling. Cancel whatever has not
            # started, wait out whatever has (a jitted step cannot be
            # interrupted mid-flight anyway), and drain completed
            # futures' exceptions so nothing warns at interpreter exit.
            for f in futures.values():
                f.cancel()
            pool.shutdown(wait=True, cancel_futures=True)
            for f in futures.values():
                if f.done() and not f.cancelled():
                    f.exception()

        return state["params"], state["opt_state"], tr.history
