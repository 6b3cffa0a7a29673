"""Parallelism Selector — EARL contribution #1 (paper §2, Fig. 2 ①②).

The optimal model/TP degree for the Rollout and Experience-Preparation
stages depends on the *current* context length, which grows during agentic
RL training (paper Fig. 1). The selector:

  1. **profiles** at the start of training: for each candidate
     ``MeshConfig`` × context-length bucket it scores tokens-per-GPU-per-
     second (TGS) and feasibility (OOM detection), building a policy table
     — exactly the paper's "measures the throughput under various
     parallelism configurations and context lengths, then maintains the
     optimal configuration for each context length range";
  2. **monitors** the running (EMA) context length during training;
  3. **switches** the parallelism configuration before the next Rollout
     stage whenever the EMA enters a new bucket (the Fig. 2 ① hook), and
     before Experience Preparation (hook ②).

Stage-keyed configs: the async pipeline schedule (``core/scheduler.py``)
overlaps Rollout(k+1) on the rollout mesh with Update(k) on the trainer
mesh, so the selector holds one *current* config **per stage**
(``current_for("rollout")`` / ``current_for("update")``) simultaneously
instead of switching a single config in place — a switch decision for one
stage must not yank the mesh out from under the other stage's in-flight
program. ``current`` remains the rollout stage's config (the original
single-stage API).

On-hardware, TGS comes from wall-clock timing. On this CPU container the
default ``measure`` path is the *compiled cost model*: the stage program is
lowered+compiled for the candidate mesh and scored with the TPU-v5e
roofline (``repro.utils.roofline``); ``compiled.memory_analysis()`` against
HBM capacity reproduces the paper's OOM cell (Fig. 3, TP4 × 32K × 128
responses) analytically. Both paths share this class — only ``measure_fn``
differs (DESIGN.md §2).
"""
from __future__ import annotations

import bisect
from dataclasses import dataclass, field
from typing import Callable, Dict, List, Optional, Sequence, Tuple

from repro.core.resharding import MeshConfig


@dataclass(frozen=True)
class ContextBuckets:
    """Half-open context-length ranges [0,b0), [b0,b1), ..., [b_last, inf)."""

    boundaries: Tuple[int, ...] = (4096, 8192, 16384, 32768)

    def bucket(self, context_len: float) -> int:
        return bisect.bisect_right(self.boundaries, context_len)

    @property
    def n_buckets(self) -> int:
        return len(self.boundaries) + 1

    def representative(self, idx: int) -> int:
        """Context length used to profile bucket ``idx`` (its upper edge;
        the last bucket profiles at 2x the final boundary)."""
        if idx < len(self.boundaries):
            return self.boundaries[idx]
        return self.boundaries[-1] * 2

    def label(self, idx: int) -> str:
        lo = 0 if idx == 0 else self.boundaries[idx - 1]
        hi = "inf" if idx == len(self.boundaries) else self.boundaries[idx]
        return f"[{lo},{hi})"


@dataclass
class ProfileEntry:
    config: MeshConfig
    context_len: int
    tgs: float                  # tokens / chip / second (cost-model or wall)
    feasible: bool              # False = OOM (memory_analysis > HBM)
    peak_bytes: float = 0.0
    step_time_s: float = 0.0


@dataclass
class SelectorPolicy:
    """The profiling result: per-bucket best config + the full score grid."""

    buckets: ContextBuckets
    table: Dict[int, MeshConfig]                 # bucket -> best config
    entries: List[ProfileEntry] = field(default_factory=list)

    def best(self, context_len: float) -> MeshConfig:
        return self.table[self.buckets.bucket(context_len)]

    def grid(self) -> Dict[Tuple[str, int], ProfileEntry]:
        return {(e.config.name, e.context_len): e for e in self.entries}

    def speedup_pct(self, a: str, b: str, context_len: int) -> float:
        """Paper Eq. 1: relative TGS speedup switching config a -> b."""
        g = self.grid()
        ea, eb = g[(a, context_len)], g[(b, context_len)]
        if not ea.feasible:
            return float("inf") if eb.feasible else float("nan")
        if not eb.feasible:
            return float("-inf")
        return (eb.tgs - ea.tgs) / ea.tgs * 100.0


# measure_fn(config, context_len) -> ProfileEntry
MeasureFn = Callable[[MeshConfig, int], ProfileEntry]


class ParallelismSelector:
    """Runtime half: EMA context monitor + bucket-crossing switch logic."""

    #: stages that hold an independent *current* config (async pipeline:
    #: both live simultaneously on disjoint submeshes)
    STAGES = ("rollout", "update")

    def __init__(self, candidates: Sequence[MeshConfig],
                 measure_fn: MeasureFn,
                 buckets: Optional[ContextBuckets] = None,
                 *, ema_alpha: float = 0.5):
        assert candidates, "need at least one candidate MeshConfig"
        self.candidates = list(candidates)
        self.measure_fn = measure_fn
        self.buckets = buckets or ContextBuckets()
        self.ema_alpha = ema_alpha
        self.policy: Optional[SelectorPolicy] = None
        self._ema: Optional[float] = None
        self._current: Dict[str, MeshConfig] = {}
        self.switch_log: List[dict] = []

    # -- profiling pass (paper: "at the start of the training process") ----
    def profile(self) -> SelectorPolicy:
        entries: List[ProfileEntry] = []
        table: Dict[int, MeshConfig] = {}
        for b in range(self.buckets.n_buckets):
            ctx = self.buckets.representative(b)
            best: Optional[ProfileEntry] = None
            for cfg in self.candidates:
                e = self.measure_fn(cfg, ctx)
                entries.append(e)
                if not e.feasible:
                    continue
                if best is None or e.tgs > best.tgs:
                    best = e
            if best is None:
                raise RuntimeError(
                    f"no feasible parallelism config for context bucket "
                    f"{self.buckets.label(b)} (all candidates OOM)")
            table[b] = best.config
        self.policy = SelectorPolicy(self.buckets, table, entries)
        self._current = {s: self.policy.table[0] for s in self.STAGES}
        return self.policy

    # -- runtime monitor ----------------------------------------------------
    @property
    def current(self) -> MeshConfig:
        """The Rollout stage's current config (single-stage API)."""
        return self.current_for("rollout")

    def current_for(self, stage: str) -> MeshConfig:
        assert self._current, "profile() first"
        assert stage in self._current, (stage, tuple(self._current))
        return self._current[stage]

    @property
    def ema_context(self) -> float:
        return self._ema if self._ema is not None else 0.0

    def observe(self, mean_context_len: float) -> None:
        """Feed the averaged context length of the last Rollout stage."""
        if self._ema is None:
            self._ema = float(mean_context_len)
        else:
            a = self.ema_alpha
            self._ema = a * float(mean_context_len) + (1 - a) * self._ema

    def maybe_switch(self, step: int = -1, stage: str = "rollout"
                     ) -> Optional[Tuple[MeshConfig, MeshConfig]]:
        """Hook ① / ②: called before a stage launches. If the EMA context
        length has entered a bucket whose best config differs from the
        stage's current one, switch *that stage's* config and return
        (old, new); else None. Other stages keep their config — in the
        async schedule their previous step may still be running on it."""
        assert self.policy is not None, "profile() first"
        if self._ema is None:
            return None
        target = self.policy.best(self._ema)
        if target == self._current[stage]:
            return None
        old = self._current[stage]
        self._current[stage] = target
        self.switch_log.append({
            "step": step,
            "stage": stage,
            "ema_context": self._ema,
            "bucket": self.buckets.label(self.buckets.bucket(self._ema)),
            "from": old.name,
            "to": target.name,
        })
        return old, target


# ---------------------------------------------------------------------------
# Cost-model measure function (the CPU-container profiling path)
# ---------------------------------------------------------------------------

def make_cost_model_measure(lower_fn: Callable[[MeshConfig, int], object],
                            *, hw, seq_tokens_fn: Callable[[int], float] =
                            None) -> MeasureFn:
    """Build a MeasureFn from a ``lower_fn(config, context_len) ->
    jax.stages.Lowered``. Compiles the stage program and scores TGS with
    the roofline of ``hw`` (a ``utils.roofline.HardwareSpec``); marks the
    config infeasible when the compiled per-device footprint exceeds
    ``hw.hbm_bytes`` (the paper's OOM case).

    seq_tokens_fn(context_len) -> tokens processed per step (global); the
    TGS denominator. Defaults to context_len (decode: one step covers the
    whole context's worth of per-token work amortized).
    """
    from repro.utils import hlo as hlo_utils
    from repro.utils import roofline

    def measure(config: MeshConfig, context_len: int) -> ProfileEntry:
        try:
            lowered = lower_fn(config, context_len)
            compiled = lowered.compile()
        except Exception:                      # unshardable / lowering error
            return ProfileEntry(config, context_len, 0.0, False)
        mem = compiled.memory_analysis()
        peak = _peak_bytes(mem)
        fc = hlo_utils.full_cost(compiled.as_text())   # trip-count aware
        # collective latency floor: each op serializes ~tp ring hops
        rep = roofline.analyze(
            f"{config.name}@{context_len}", chips=config.n_devices,
            cost_analysis={"flops": fc.flops,
                           "bytes accessed": fc.bytes_accessed},
            collective_bytes=fc.collective_bytes, model_flops=0.0,
            collective_count=fc.collective_count, ring_size=config.tp,
            hw=hw, peak_memory_bytes=peak)
        t = rep.step_time_s
        tokens = (seq_tokens_fn(context_len) if seq_tokens_fn
                  else float(context_len))
        tgs = tokens / max(config.n_devices, 1) / max(t, 1e-12)
        return ProfileEntry(config, context_len, tgs,
                            feasible=peak <= hw.hbm_bytes, peak_bytes=peak,
                            step_time_s=t)

    return measure


def _peak_bytes(mem) -> float:
    """Per-device peak bytes from ``compiled.memory_analysis()`` (fields
    vary across backends; fall back progressively)."""
    for attr in ("temp_size_in_bytes",):
        if hasattr(mem, attr):
            total = (getattr(mem, "argument_size_in_bytes", 0)
                     + getattr(mem, "output_size_in_bytes", 0)
                     + getattr(mem, "temp_size_in_bytes", 0)
                     - getattr(mem, "alias_size_in_bytes", 0))
            return float(total)
    if isinstance(mem, dict):
        return float(mem.get("bytes", 0.0))
    return 0.0
