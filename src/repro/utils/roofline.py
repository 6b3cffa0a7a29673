"""TPU v5e roofline model.

Three terms per compiled program (see EXPERIMENTS.md §Roofline):

    compute    = HLO_FLOPs       / (chips * PEAK_FLOPS)
    memory     = HLO_bytes       / (chips * HBM_BW)
    collective = collective_bytes/ (chips * ICI_BW)

All terms are *seconds*; the max is the roofline-predicted step time and the
argmax is the bottleneck the §Perf loop iterates on.

``cost_analysis()`` FLOPs/bytes are whole-program totals (already summed over
the SPMD program that runs on EVERY chip, i.e. per-chip work for a sharded
program), so the per-chip time divides by 1 — but XLA reports the *global*
module cost for the lowered module on one device view. Empirically (and per
jax docs) ``cost_analysis`` on an SPMD-partitioned executable reports
per-device numbers; we treat them as per-chip and do NOT divide by chips
again. The ``chips`` field is retained for the analytic MODEL_FLOPS ratio.
"""
from __future__ import annotations

from dataclasses import dataclass, asdict


@dataclass(frozen=True)
class HardwareSpec:
    """Per-chip roofline constants."""

    name: str
    peak_flops: float           # bf16 FLOP/s
    hbm_bw: float               # bytes/s
    link_bw: float              # bytes/s per interconnect link
    hbm_bytes: float            # capacity (OOM threshold)
    coll_hop_latency: float     # seconds per collective per ring hop


# TPU v5e (per chip), per the assignment brief — the dry-run target.
V5E = HardwareSpec("tpu-v5e", peak_flops=197e12, hbm_bw=819e9,
                   link_bw=50e9, hbm_bytes=16 * 2**30,
                   coll_hop_latency=1e-6)
# H100-80GB SXM (the paper's testbed, §3.1) — used by the Fig. 3 bench.
# coll_hop_latency reflects measured NCCL small-payload all-reduce latency
# (~10 us/hop across NVLink/IB at 128-GPU scale).
H100 = HardwareSpec("h100-80g", peak_flops=989e12, hbm_bw=3.35e12,
                    link_bw=450e9, hbm_bytes=80e9,
                    coll_hop_latency=12e-6)


@dataclass
class RooflineReport:
    name: str
    chips: int
    hlo_flops: float            # per-chip FLOPs from cost_analysis
    hlo_bytes: float            # per-chip HBM bytes from cost_analysis
    collective_bytes: float     # per-chip collective bytes from HLO parse
    model_flops: float          # analytic 6*N*D (or 6*N_active*D) global
    hw: HardwareSpec            # the chip whose peaks score the program
    collective_count: float = 0.0   # trip-weighted collective op count
    ring_size: int = 1              # hops per collective (latency model)
    compute_s: float = 0.0
    memory_s: float = 0.0
    collective_s: float = 0.0
    bottleneck: str = ""
    useful_flops_ratio: float = 0.0
    peak_memory_bytes: float = 0.0

    def finalize(self) -> "RooflineReport":
        hw = self.hw
        self.compute_s = self.hlo_flops / hw.peak_flops
        self.memory_s = self.hlo_bytes / hw.hbm_bw
        # bandwidth term + per-op latency floor (rings serialize hops)
        self.collective_s = (self.collective_bytes / hw.link_bw
                             + self.collective_count * self.ring_size
                             * hw.coll_hop_latency)
        terms = {
            "compute": self.compute_s,
            "memory": self.memory_s,
            "collective": self.collective_s,
        }
        self.bottleneck = max(terms, key=terms.get)
        per_chip_model_flops = self.model_flops / max(self.chips, 1)
        self.useful_flops_ratio = (
            per_chip_model_flops / self.hlo_flops if self.hlo_flops else 0.0
        )
        return self

    @property
    def step_time_s(self) -> float:
        return max(self.compute_s, self.memory_s, self.collective_s)

    def row(self) -> dict:
        d = asdict(self)
        d["step_time_s"] = self.step_time_s
        return d


def analyze(name, *, chips, cost_analysis, collective_bytes, model_flops,
            hw: HardwareSpec, peak_memory_bytes=0.0, collective_count=0.0,
            ring_size=1) -> RooflineReport:
    """Build a RooflineReport from a compiled program's analyses, scored
    against the peaks of ``hw``.

    cost_analysis: the dict from ``compiled.cost_analysis()``.
    collective_bytes: from ``repro.utils.hlo.collective_bytes(...)``.
    model_flops: analytic useful FLOPs (6*N*D for training, 2*N*D forward).
    """
    flops = float(cost_analysis.get("flops", 0.0) or 0.0)
    nbytes = float(cost_analysis.get("bytes accessed", 0.0) or 0.0)
    return RooflineReport(
        name=name,
        chips=chips,
        hlo_flops=flops,
        hlo_bytes=nbytes,
        collective_bytes=float(collective_bytes),
        model_flops=float(model_flops),
        collective_count=float(collective_count),
        ring_size=int(ring_size),
        hw=hw,
        peak_memory_bytes=float(peak_memory_bytes),
    ).finalize()


def model_flops_train(n_params_active: float, n_tokens: float) -> float:
    """Classic 6*N*D for a full fwd+bwd training step."""
    return 6.0 * n_params_active * n_tokens


def model_flops_forward(n_params_active: float, n_tokens: float) -> float:
    return 2.0 * n_params_active * n_tokens


def format_table(reports, headers=None) -> str:
    """Markdown table of roofline reports."""
    cols = [
        ("pair", lambda r: r.name),
        ("chips", lambda r: str(r.chips)),
        ("compute_s", lambda r: f"{r.compute_s:.4g}"),
        ("memory_s", lambda r: f"{r.memory_s:.4g}"),
        ("coll_s", lambda r: f"{r.collective_s:.4g}"),
        ("bottleneck", lambda r: r.bottleneck),
        ("useful_ratio", lambda r: f"{r.useful_flops_ratio:.3f}"),
        ("peak_mem_GiB", lambda r: f"{r.peak_memory_bytes / 2**30:.2f}"),
    ]
    lines = ["| " + " | ".join(c for c, _ in cols) + " |",
             "|" + "|".join("---" for _ in cols) + "|"]
    for r in reports:
        lines.append("| " + " | ".join(f(r) for _, f in cols) + " |")
    return "\n".join(lines)
