"""The EARL RL stage graph (paper Fig. 2), as explicit stage objects.

Synchronous schedule (``pipeline="sync"``, the paper's baseline loop):

    ┌─► [selector hook ①] Rollout (policy decode, multi-turn env loop)
    │        │ experience batch (tokens, logprobs, REF logprobs, rewards)
    │        │   — the reference pass is folded INTO the rollout
    │        │     macro-step (in-graph ExpPrep, §3.3)
    │   [selector hook ②] Experience Preparation (advantage estimation)
    │   [dispatcher ③④⑤]  layout-aware move to the Update layout
    │        ▼
    └── Model Update (policy-gradient step)

Asynchronous schedule (``pipeline="async"``, ``core/scheduler.py``):
Rollout(k+1) on the rollout mesh overlaps Update(k) on the trainer mesh,
one-step-off (bounded by ``max_policy_lag``):

    rollout mesh:  RO(0)→EP(0) │ RO(1)→EP(1) │ RO(2)→EP(2) │ ...
                        └─③④⑤──┐     └─③④⑤──┐     └─③④⑤──┐
    trainer mesh:          UP(0)   │    UP(1)   │    UP(2) ...
    params:        v0     v0 stale─┘   v1 stale─┘   v2 ...

RO(k) samples with params version max(0, k - max_policy_lag) — stale by
up to ``max_policy_lag`` updates — and the Update stage compensates with
a truncated importance-sampling correction against the behavior
log-probs (``rl.algo.truncated_importance_weights``, ``is_rho_max``).
``max_policy_lag=0`` degenerates to the synchronous ordering (bitwise-
identical training, tested), still exercising the pipeline machinery.

The four stages are standalone callables (``RolloutStage``,
``ExpPrepStage``, ``DispatchStage``, ``UpdateStage``) so a schedule can
place them on different meshes/threads; ``EarlTrainer`` wires the
substrate (model, env, rollout engine, optimizer) into them and remains
the user-facing driver. Every stage transition stays observable: per-step
``StepRecord`` captures context-length growth (Fig. 1), selector switches
(Fig. 3), dispatch reports (Fig. 4), policy lag and paged-pool telemetry;
under a profiler session each step and each stage call is an ``earl.*``
span carrying its step (``utils/tracing.py``).
"""
from __future__ import annotations

import logging
import time
import warnings
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.data_dispatcher import DataDispatcher, DispatchReport
from repro.core.parallelism_selector import ParallelismSelector
from repro.core.train_step import make_ref_logprob_step, make_rl_train_step
from repro.optim.adamw import Optimizer, adamw
from repro.rl.algo import reinforce_advantages, group_relative_advantages
from repro.rl.engine import CompiledRolloutEngine
from repro.rl.experience import ExperienceBatch
from repro.rl.rollout import RolloutEngine, RolloutStats
from repro.utils.tracing import span, to_host, watch_gc


@dataclass
class StepRecord:
    step: int
    mean_return: float
    mean_context_len: float
    mean_turn_len: float
    truncated_frac: float
    loss: float
    kl: float = 0.0
    selector_switch: Optional[dict] = None
    dispatch: Optional[dict] = None
    wall_time_s: float = 0.0
    # async pipeline accounting: which params version generated the batch
    # and how stale it was relative to the synchronous schedule
    params_version: int = -1
    policy_lag: int = 0
    is_weight_mean: float = 0.0          # truncated-IS mean (1.0 on-policy)
    # paged-pool telemetry (ROADMAP: exhaustion must not be silent)
    pages_in_use: int = 0
    page_capacity: int = 0
    kv_dropped_writes: int = 0
    # graceful-degradation telemetry (0 unless the mode is armed):
    # pressure-governor evictions, peak re-admission queue depth, and
    # host-side pool growth events this step
    preemptions: int = 0
    requeue_depth: int = 0
    pool_grows: int = 0
    # speculative-decoding acceptance telemetry (0 unless speculation is
    # on): draft tokens proposed / accepted and verify rounds run — mean
    # accepted length per round is (spec_accepted + spec_rounds) /
    # spec_rounds (the +1 is the always-committed exact token)
    spec_proposed: int = 0
    spec_accepted: int = 0
    spec_rounds: int = 0


# ---------------------------------------------------------------------------
# Stage implementations
# ---------------------------------------------------------------------------

class RolloutStage:
    """Fig. 2 ① (+ the folded reference pass of ②) on the rollout mesh.

    Runs the selector's rollout-stage hook, binds the compiled engine to
    the stage's current MeshConfig, rolls out, and feeds the context-
    length monitor. Returns ``(exp, stats, switch_row)``.
    """

    def __init__(self, engine, selector: Optional[ParallelismSelector] =
                 None):
        self.engine = engine
        self.selector = selector

    def __call__(self, step: int, params, rng, batch: int, *,
                 n_episodes: Optional[int] = None, ref_params=None,
                 params_version: int = -1):
        switch = None
        sel = self.selector
        if sel is not None and sel.policy is not None:
            sw = sel.maybe_switch(step, stage="rollout")
            if sw is not None:
                switch = {"from": sw[0].name, "to": sw[1].name,
                          "ema_context": sel.ema_context}
            # compiled engine: keep the generation program bound to the
            # stage's current mesh. Checking every step (not just on a
            # switch event) also covers selectors profiled *after* trainer
            # construction; the compile cache is keyed by MeshConfig, so
            # revisited configs reuse their program.
            cur = sel.current_for("rollout")
            if (hasattr(self.engine, "bind_mesh")
                    and self.engine.mesh_config != cur):
                self.engine.bind_mesh(cur)

        with span("rollout", step=step):
            exp, stats = self.engine.run(params, rng, batch,
                                         n_episodes=n_episodes,
                                         ref_params=ref_params,
                                         params_version=params_version)
        if sel is not None:
            sel.observe(stats.mean_context_len)
        return exp, stats, switch


class ExpPrepStage:
    """Fig. 2 ②: advantage estimation (+ reference-model fallback).

    Both engines fold the reference log-prob pass into the rollout itself
    (the ROADMAP "in-graph experience preparation" — the logits are
    already on device during decode), so normally this stage is a cheap
    advantage computation. The standalone ``make_ref_logprob_step``
    program remains as a fallback for engines that did not fold it
    (``ref_folded=False``).
    """

    def __init__(self, model, *, advantage: str = "reinforce",
                 group_size: int = 4):
        self.advantage = advantage
        self.group_size = group_size
        self._ref_step = jax.jit(make_ref_logprob_step(model))
        self._logged_lp_reuse = False

    def __call__(self, exp: ExperienceBatch, *, ref_params=None,
                 ref_folded: bool = True, reuse_behavior_lp: bool = False,
                 step: int = -1) -> ExperienceBatch:
        with span("expprep", step=step):
            if ref_params is not None and not ref_folded:
                if reuse_behavior_lp:
                    # fast path: the reference IS the params that generated
                    # the rollout (lag-1 snapshot) and sampling was unbiased,
                    # so the behavior log-probs the engine already recorded
                    # ARE the reference log-probs at every loss position
                    # (loss_mask == gen_mask; obs positions are never read by
                    # the KL term) — skip the second full-model evaluation
                    if not self._logged_lp_reuse:
                        self._logged_lp_reuse = True
                        logging.getLogger(__name__).info(
                            "ExpPrepStage: reference == behavior params — "
                            "reusing rollout log-probs for the ref pass "
                            "(standalone ref forward pass skipped)")
                    exp = exp.with_(ref_logprobs=jnp.where(
                        exp.gen_mask, exp.logprobs, 0.0))
                else:
                    with span("ref"):
                        exp = exp.with_(ref_logprobs=self._ref_step(
                            ref_params, exp.tokens))
            if self.advantage == "group":
                adv = group_relative_advantages(exp.rewards, self.group_size)
            else:
                adv = reinforce_advantages(exp.rewards)
            return exp.with_(advantages=adv)


class DispatchStage:
    """Fig. 2 ③④⑤: layout-aware move to the Update layout.

    The compiled engine reports the true device layout of the harvested
    batch (``experience_shardings``), so the movement plan starts from
    real src_shardings instead of inferring them. ``asynchronous=True``
    uses the dispatcher's async handoff: the transfer is enqueued and the
    returned batch can feed the Update program immediately while the host
    launches the next rollout (the report handle is resolved later).
    """

    def __init__(self, dispatcher: DataDispatcher, engine=None, *,
                 strategy: str = "direct"):
        self.dispatcher = dispatcher
        self.engine = engine
        self.strategy = strategy

    def source_shardings(self, exp: ExperienceBatch):
        """Engine-reported layout, refreshed for the leaves ExpPrep
        replaced after the engine recorded the rollout layout."""
        src = getattr(self.engine, "experience_shardings", None)
        if src is None:
            return None
        return src._replace(ref_logprobs=exp.ref_logprobs.sharding,
                            advantages=exp.advantages.sharding)

    def __call__(self, exp: ExperienceBatch, dst_shardings, *,
                 src_shardings=None, asynchronous: bool = False,
                 step: int = -1):
        """Returns ``(exp, report_row_or_handle)``; (exp, None) when no
        dst_shardings were requested."""
        with span("dispatch", step=step):
            if dst_shardings is None:
                return exp, None
            if src_shardings is None:
                src_shardings = self.source_shardings(exp)
            if asynchronous:
                handle = self.dispatcher.dispatch_async(
                    exp, dst_shardings, strategy=self.strategy,
                    src_shardings=src_shardings)
                return handle.batch, handle
            exp, rep = self.dispatcher.dispatch(
                exp, dst_shardings, strategy=self.strategy,
                src_shardings=src_shardings)
            return exp, rep.row()


class UpdateStage:
    """Fig. 2 Model Update: the policy-gradient step on the trainer mesh.

    The jitted program donates ``opt_state`` (dead after the step — the
    donated in-flight buffer of the pipeline). ``params`` is deliberately
    NOT donated: under the async schedule the rollout mesh is still
    reading the same buffers as the behavior policy while the update
    runs. ``is_rho_max > 0`` arms the truncated importance-sampling
    correction for stale-params experience.
    """

    def __init__(self, model, optimizer: Optimizer, *,
                 clip_eps: float = 0.0, kl_coef: float = 0.0,
                 is_rho_max: float = 0.0):
        self._step = jax.jit(
            make_rl_train_step(model, optimizer, clip_eps=clip_eps,
                               kl_coef=kl_coef, is_rho_max=is_rho_max),
            donate_argnums=(1,))

    def __call__(self, params, opt_state, exp: ExperienceBatch, *,
                 step: int = -1):
        with span("update", step=step):
            return self._step(params, opt_state, exp)


# ---------------------------------------------------------------------------
# Driver
# ---------------------------------------------------------------------------

@dataclass
class EarlTrainer:
    """End-to-end agentic RL driver wiring the Fig. 2 stage graph.

    ``pipeline="sync"`` runs the stages back-to-back per step;
    ``pipeline="async"`` hands them to ``core.scheduler.PipelineSchedule``
    which overlaps Rollout(k+1) with Update(k) under ``max_policy_lag``.
    """

    model: Any                              # repro.models.Model
    env: Any
    optimizer: Optional[Optimizer] = None
    selector: Optional[ParallelismSelector] = None
    dispatcher: Optional[DataDispatcher] = None
    dispatch_strategy: str = "direct"
    batch_size: int = 8
    max_turns: int = 3
    max_turn_tokens: int = 6
    max_context: int = 192
    kl_coef: float = 0.0
    clip_eps: float = 0.0
    advantage: str = "reinforce"            # "reinforce" | "group"
    group_size: int = 4
    temperature: float = 1.0
    top_p: float = 1.0                      # nucleus sampling (1.0 = off)
    sampling: str = "reference"             # compiled: | "fused" (one-pass
                                            # sample-and-write kernel)
    rollout_backend: str = "python"         # "python" | "compiled"
    rollout_episodes: Optional[int] = None  # compiled: episodes per rollout
    cache_layout: str = "dense"             # compiled: "dense" | "paged"
    page_size: int = 16                     # paged: tokens per KV page
    cache_pages: Optional[int] = None       # paged: pool size (None = full)
    kv_dtype: str = "bf16"                  # "fp32"|"bf16"|"int8" (paged)
    share_prefix: bool = False              # paged: fork shared-prompt pages
    prefix_len: Optional[int] = None        # None = env.prompt_prefix_len
    on_exhaust: str = "count"               # "count"|"raise"|"preempt"
    pool_growth: str = "off"                # paged: "off" | "double"
    pool_growth_max: Optional[int] = None   # growth cap (None = full)
    admit_watermark: Optional[int] = None   # preempt: free-page watermark
    speculation: str = "off"                # compiled+paged: |"self"|"draft"
    spec_k: int = 4                         # speculative chunk length
    draft_layers: Optional[int] = None      # "self": draft depth (None=L/2)
    pipeline: str = "sync"                  # "sync" | "async"
    max_policy_lag: int = 1                 # async: bounded staleness
    is_rho_max: float = 0.0                 # truncated-IS cap (0 = off)
    # fault tolerance (core/scheduler.py): step retry w/ backoff +
    # periodic checkpoint / auto-resume through checkpoint/checkpoint.py
    max_retries: int = 0
    retry_backoff_s: float = 0.05
    checkpoint_dir: Optional[str] = None
    checkpoint_every: int = 0
    resume: bool = False
    # deterministic fault injection (utils/faults.FaultInjector): stage
    # exceptions at chosen steps + pool-pressure undersizing
    faults: Optional[Any] = None
    seed: int = 0

    history: List[StepRecord] = field(default_factory=list)

    def __post_init__(self):
        self.optimizer = self.optimizer or adamw(3e-4, weight_decay=0.0)
        self.dispatcher = self.dispatcher or DataDispatcher()
        assert self.pipeline in ("sync", "async"), self.pipeline
        kw = dict(max_turns=self.max_turns,
                  max_turn_tokens=self.max_turn_tokens,
                  max_context=self.max_context,
                  temperature=self.temperature, top_p=self.top_p)
        if self.rollout_backend == "compiled":
            # generation programs compile per MeshConfig; start on the
            # selector's current config when it is already profiled
            mesh_cfg = (self.selector.current
                        if self.selector is not None
                        and self.selector.policy is not None else None)
            self.rollout = CompiledRolloutEngine(
                self.model, self.env, mesh_config=mesh_cfg,
                cache_layout=self.cache_layout, page_size=self.page_size,
                cache_pages=self.cache_pages, kv_dtype=self.kv_dtype,
                sampling=self.sampling,
                share_prefix=self.share_prefix, prefix_len=self.prefix_len,
                on_exhaust=self.on_exhaust, pool_growth=self.pool_growth,
                pool_growth_max=self.pool_growth_max,
                admit_watermark=self.admit_watermark,
                speculation=self.speculation, spec_k=self.spec_k,
                draft_layers=self.draft_layers, **kw)
        elif self.rollout_backend == "python":
            if self.rollout_episodes is not None:
                raise ValueError(
                    "rollout_episodes requires rollout_backend='compiled' "
                    "(the python reference engine has no slot refill)")
            if self.cache_layout != "dense":
                raise ValueError(
                    "cache_layout='paged' requires "
                    "rollout_backend='compiled' (the paged pool and its "
                    "in-graph allocator live in the compiled macro-step)")
            if self.share_prefix:
                raise ValueError(
                    "share_prefix requires rollout_backend='compiled' "
                    "with cache_layout='paged' (prefix sharing forks "
                    "pool pages inside the compiled macro-step)")
            if self.kv_dtype != "bf16":
                raise ValueError(
                    "kv_dtype requires rollout_backend='compiled' (the "
                    "python reference engine always decodes against the "
                    "default bf16 dense cache)")
            if self.sampling != "reference":
                raise ValueError(
                    "sampling='fused' requires rollout_backend='compiled' "
                    "(the fused sample-and-write step lives in the "
                    "compiled decode scan)")
            if self.on_exhaust == "preempt" or self.pool_growth != "off":
                raise ValueError(
                    "on_exhaust='preempt' / pool_growth require "
                    "rollout_backend='compiled' with cache_layout='paged' "
                    "(the pressure governor and pool growth act on the "
                    "paged pool inside the compiled macro-step)")
            if self.speculation != "off":
                raise ValueError(
                    "speculation requires rollout_backend='compiled' "
                    "with cache_layout='paged' (the draft-propose / "
                    "batch-verify rounds live in the compiled macro-"
                    "step's generation loop)")
            self.rollout = RolloutEngine(self.model, self.env, **kw)
        else:
            raise ValueError(
                f"unknown rollout_backend {self.rollout_backend!r}")

        # prefix sharing forks only the POLICY's paged pool; the in-graph
        # reference pass keeps a dense cache and cannot skip the shared
        # columns. Speculation likewise unfolds the ref pass: the folded
        # ref decode consumes tokens one scan step at a time and cannot
        # consume drafted chunks. Either way the trainer falls back to
        # the standalone ExpPrep ref program instead of folding the ref
        # into the rollout (announced once via _maybe_warn_ref_fallback
        # when it first bites).
        self.ref_folded = (
            not getattr(self.rollout, "shared_pages", 0)
            and getattr(self.rollout, "speculation", "off") == "off")
        self._warned_ref_fallback = False
        self.rollout_stage = RolloutStage(self.rollout, self.selector)
        self.expprep_stage = ExpPrepStage(
            self.model, advantage=self.advantage,
            group_size=self.group_size)
        self.dispatch_stage = DispatchStage(
            self.dispatcher, self.rollout, strategy=self.dispatch_strategy)
        self.update_stage = UpdateStage(
            self.model, self.optimizer, clip_eps=self.clip_eps,
            kl_coef=self.kl_coef, is_rho_max=self.is_rho_max)
        self._rng = jax.random.PRNGKey(self.seed)
        watch_gc()

        # injected pool pressure: undersize the paged pool to a fraction
        # of the exhaustion-free provisioning, clamped to the preemption
        # governor's minimum viable pool so the pressure stays
        # *recoverable* (utils/faults.undersize_pool)
        if self.faults is not None \
                and getattr(self.faults, "pool_pressure", 0) > 0:
            if self.rollout_backend != "compiled" \
                    or self.cache_layout != "paged":
                raise ValueError(
                    "pool_pressure fault injection requires "
                    "rollout_backend='compiled' with cache_layout='paged'")
            from repro.models.paging import (pool_pages_needed,
                                             pool_pages_needed_shared)
            from repro.utils.faults import undersize_pool
            eng = self.rollout
            if eng.shared_pages > 0:
                full = pool_pages_needed_shared(
                    self.batch_size, self.max_context, eng.shared_len,
                    self.page_size)
            else:
                full = pool_pages_needed(self.batch_size,
                                         self.max_context, self.page_size)
            floor = (eng.min_pool_pages(self.batch_size)
                     if self.on_exhaust == "preempt" else 1)
            eng.cache_pages = undersize_pool(
                full, self.faults.pool_pressure, floor)

    def check_fault(self, site: str, step: int) -> None:
        """Stage-boundary hook for deterministic fault injection; no-op
        without an armed injector (utils/faults.FaultInjector)."""
        if self.faults is not None:
            self.faults.check(site, step)

    # ------------------------------------------------------------------
    def init_state(self, rng=None):
        rng = rng if rng is not None else jax.random.PRNGKey(self.seed)
        params = self.model.init(rng)
        opt_state = self.optimizer.init(params)
        ref_params = params if self.kl_coef > 0 else None
        return params, opt_state, ref_params

    def _next_rng(self):
        self._rng, sub = jax.random.split(self._rng)
        return sub

    def make_record(self, step: int, stats: RolloutStats, metrics,
                    *, switch=None, dispatch_row=None, wall_time_s=0.0,
                    policy_lag: int = 0) -> StepRecord:
        """Assemble the per-step observability row (shared by the sync
        path and the async scheduler)."""
        rec = StepRecord(
            step=step,
            mean_return=stats.mean_return,
            mean_context_len=stats.mean_context_len,
            mean_turn_len=stats.mean_turn_len,
            truncated_frac=float(np.mean(stats.truncated)),
            loss=float(to_host(metrics["loss"], "loss")),
            kl=float(to_host(metrics.get("kl", 0.0), "kl")),
            selector_switch=switch,
            dispatch=dispatch_row,
            wall_time_s=wall_time_s,
            params_version=stats.params_version,
            policy_lag=policy_lag,
            is_weight_mean=float(to_host(
                metrics.get("is_weight_mean", 0.0), "is_weight_mean")),
            pages_in_use=stats.pages_in_use,
            page_capacity=stats.page_capacity,
            kv_dropped_writes=stats.kv_dropped_writes,
            preemptions=getattr(stats, "preemptions", 0),
            requeue_depth=getattr(stats, "requeue_depth", 0),
            pool_grows=getattr(stats, "pool_grows", 0),
            spec_proposed=getattr(stats, "spec_proposed", 0),
            spec_accepted=getattr(stats, "spec_accepted", 0),
            spec_rounds=getattr(stats, "spec_rounds", 0),
        )
        self.history.append(rec)
        return rec

    def _maybe_warn_ref_fallback(self, ref_params) -> None:
        """One-time structured warning when a reference pass is requested
        but the in-graph fold is unavailable: the silent switch to the
        standalone ExpPrep ref program (share_prefix leftover) must name
        its reason instead of just happening."""
        if ref_params is None or self.ref_folded \
                or self._warned_ref_fallback:
            return
        self._warned_ref_fallback = True
        if getattr(self.rollout, "speculation", "off") != "off":
            reason = (
                f"speculation={self.rollout.speculation!r} — the folded "
                "reference pass consumes tokens one decode step at a "
                "time and cannot consume the drafted chunks the "
                "speculative generation loop commits")
        else:
            reason = (
                "share_prefix=True — the reference model's dense cache "
                f"cannot fork the {self.rollout.shared_len}-token "
                "shared prefix run")
        warnings.warn(
            "EarlTrainer: reference log-probs will come from the "
            "STANDALONE ExpPrep program, not the in-graph rollout fold "
            f"(reason: {reason}, so folding ref_params into the "
            "compiled macro-step is unsupported; see "
            "rl/engine/README.md). The ref pass re-decodes each "
            "harvested context in a separate program per step.",
            RuntimeWarning, stacklevel=3)

    # ------------------------------------------------------------------
    def run_step(self, step: int, params, opt_state, ref_params=None,
                 dst_shardings=None):
        """One full Fig. 2 iteration, synchronously: Rollout → ExpPrep →
        Dispatch → Update. Returns (params, opt_state, record)."""
        with span("step", step=step):
            self._maybe_warn_ref_fallback(ref_params)
            t0 = time.perf_counter()

            # ① Rollout (+ folded ref pass). Both engines share the run
            # signature; n_episodes > batch_size engages slot refill.
            self.check_fault("rollout", step)
            exp, stats, switch = self.rollout_stage(
                step, params, self._next_rng(), self.batch_size,
                n_episodes=self.rollout_episodes,
                ref_params=ref_params if self.ref_folded else None,
                params_version=step)

            # ② Experience Preparation (advantages; ref folded into the
            # rollout unless prefix sharing / speculation forced the
            # standalone fallback — which itself is skipped when the
            # reference IS the behavior params and sampling recorded
            # unbiased model log-probs: temperature 1 or greedy, top_p off)
            reuse_lp = (ref_params is params and self.top_p == 1.0
                        and (self.temperature <= 0.0
                             or self.temperature == 1.0))
            exp = self.expprep_stage(exp, ref_params=ref_params,
                                     ref_folded=self.ref_folded,
                                     reuse_behavior_lp=reuse_lp, step=step)

            # ③④⑤ Dispatch to the Update layout
            self.check_fault("dispatch", step)
            exp, dispatch_row = self.dispatch_stage(exp, dst_shardings,
                                                    step=step)

            # Model Update. The selector's update-stage config is *tracked*
            # independently of the rollout stage's (the async schedule needs
            # both alive at once); today it is bookkeeping/switch-log only —
            # the update program is a single jit that GSPMD places from its
            # input shardings, and rebinding it per MeshConfig is the
            # ROADMAP submesh-split follow-on.
            if self.selector is not None and self.selector.policy is not None:
                self.selector.maybe_switch(step, stage="update")
            self.check_fault("update", step)
            params, opt_state, metrics = self.update_stage(params, opt_state,
                                                           exp, step=step)
            # the record's reads of the metrics block: sync schedule
            rec = self.make_record(step, stats, metrics, switch=switch,
                                   dispatch_row=dispatch_row, policy_lag=0)
            rec.wall_time_s = time.perf_counter() - t0
            return params, opt_state, rec

    # ------------------------------------------------------------------
    def train(self, n_steps: int, *, params=None, opt_state=None,
              ref_params=None, dst_shardings=None, verbose: bool = False):
        """Train for ``n_steps`` under the configured pipeline schedule.

        ``dst_shardings`` (an ``ExperienceBatch`` of ``NamedSharding``)
        routes every step's batch through the Data Dispatcher to the
        Update layout — threaded through to ``run_step``/the scheduler so
        the dispatcher path is reachable from the public entry point.
        """
        from repro.core.scheduler import PipelineSchedule
        if params is None:
            params, opt_state, ref_params = self.init_state()
        sched = PipelineSchedule(self, mode=self.pipeline,
                                 max_policy_lag=self.max_policy_lag,
                                 max_retries=self.max_retries,
                                 retry_backoff_s=self.retry_backoff_s,
                                 checkpoint_dir=self.checkpoint_dir,
                                 checkpoint_every=self.checkpoint_every,
                                 resume=self.resume)
        return sched.run(n_steps, params=params, opt_state=opt_state,
                         ref_params=ref_params, dst_shardings=dst_shardings,
                         verbose=verbose)
