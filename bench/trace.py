"""From a profiler trace to device busy and idle time, and the peaks table.

The JAX profiler writes ``<dir>/plugins/profile/<time>/<host>.xplane.pb``.
Device planes are named ``/device:TPU:<n>``; their ``XLA Ops`` line holds
one event per operation run on the chip, named here ``<module>/<op>``
after the ``XLA Modules`` event that holds it (``jit_step/%fusion.3``). The benchmark's own spans
(``jax.profiler.TraceAnnotation`` named ``bench.<stage>``) are events on
the host plane's threads. Both planes share the profiler's clock.

Busy time is the union of the operation intervals; idle share is one less
busy over the window. Each idle gap between operations is attributed to
the benchmark span that covers its midpoint.
"""
from __future__ import annotations

import bisect
import glob
import os
import re
from dataclasses import dataclass, field
from pathlib import Path

# Per-chip peaks, keyed by jax's ``device_kind``. Source: Google Cloud
# documentation, "TPU v5e" (bf16 197 TFLOP/s, int8 394 TOP/s, 16 GB HBM at
# 819 GB/s per chip).
PEAKS = {
    "TPU v5 lite": {"bf16_flops": 197e12, "int8_ops": 394e12,
                    "hbm_bytes_per_s": 819e9, "hbm_bytes": 16e9,
                    "source": "Google Cloud TPU v5e documentation"},
}

SPAN_PREFIX = "bench."
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


def peaks(device_kind: str) -> dict:
    if device_kind not in PEAKS:
        raise KeyError(f"device kind {device_kind!r} has no peaks in "
                       f"bench/trace.py (known: {sorted(PEAKS)})")
    return PEAKS[device_kind]


def find_xplane(log_dir: str) -> str:
    files = glob.glob(os.path.join(log_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return max(files, key=os.path.getmtime)


def merge(intervals):
    """Union of (start, end) intervals, sorted and disjoint."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def overlap(merged, lo, hi) -> float:
    """Length of ``merged`` intervals inside [lo, hi]."""
    return sum(max(0.0, min(e, hi) - max(s, lo)) for s, e in merged)


@dataclass
class Trace:
    """Events in nanoseconds on the profiler's clock."""
    ops: dict = field(default_factory=dict)    # device -> [(name, s, e)]
    spans: list = field(default_factory=list)  # [(name, s, e)]


def read(path: str) -> Trace:
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    tr = Trace()
    for plane in pd.planes:
        if plane.name.startswith("/device:"):
            lines = {ln.name: ln for ln in plane.lines}
            if OPS_LINE not in lines:
                continue
            mods = ([(e.start_ns, e.name.split("(")[0])
                     for e in lines[MODULES_LINE].events]
                    if MODULES_LINE in lines else [])
            starts = [s for s, _ in mods]
            ops = []
            for e in lines[OPS_LINE].events:
                i = bisect.bisect_right(starts, e.start_ns) - 1
                mod = mods[i][1] if i >= 0 else "?"
                ops.append((f"{mod}/{e.name.split(' = ')[0]}", e.start_ns,
                            e.start_ns + e.duration_ns))
            tr.ops[plane.name] = ops
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(SPAN_PREFIX):
                        tr.spans.append((e.name[len(SPAN_PREFIX):],
                                         e.start_ns,
                                         e.start_ns + e.duration_ns))
    tr.spans.sort(key=lambda x: x[1])
    return tr


@dataclass
class Reduction:
    window_s: float
    busy_s: float                 # mean over devices
    span_s: dict                  # stage -> [seconds per span]
    span_idle_s: dict             # stage -> idle seconds inside its spans
    top_ops: list                 # [[name, seconds]] summed over devices
    idle_gaps: list               # [[stage, seconds]] longest gaps
    op_s: dict = field(default_factory=dict)      # every op: seconds and
    op_calls: dict = field(default_factory=dict)  # calls, over devices


def reduce(tr: Trace, window=None, top: int = 10) -> Reduction:
    """``window``: (start_ns, end_ns); default from the first span's start
    to the last span's end."""
    if window is None:
        window = (min(s for _, s, _ in tr.spans),
                  max(e for _, _, e in tr.spans))
    lo, hi = window
    devices = sorted(tr.ops)
    if not devices:
        raise ValueError("the trace holds no device operations")
    busy = {d: merge((s, e) for _, s, e in tr.ops[d]) for d in devices}
    busy_ns = sum(overlap(busy[d], lo, hi) for d in devices) / len(devices)

    span_s, span_idle = {}, {}
    for name, s, e in tr.spans:
        span_s.setdefault(name, []).append((e - s) / 1e9)
        idle = sum((e - s) - overlap(busy[d], s, e)
                   for d in devices) / len(devices)
        span_idle[name] = span_idle.get(name, 0.0) + idle / 1e9

    per_op, calls = {}, {}
    for d in devices:
        for name, s, e in tr.ops[d]:
            if e > lo and s < hi:
                per_op[name] = per_op.get(name, 0.0) + (e - s) / 1e9
                calls[name] = calls.get(name, 0) + 1
    top_ops = sorted(per_op.items(), key=lambda x: -x[1])[:top]

    gaps = []
    b = busy[devices[0]]
    prev = lo
    for s, e in b + [(hi, hi)]:
        s, e = max(s, lo), min(e, hi)
        if s > prev:
            gaps.append((prev, s))
        prev = max(prev, e)
    labelled = []
    for s, e in sorted(gaps, key=lambda g: g[0] - g[1])[:top]:
        mid = (s + e) / 2
        stage = next((n for n, a, z in tr.spans if a <= mid <= z),
                     "between spans")
        labelled.append([stage, (e - s) / 1e9])
    return Reduction(window_s=(hi - lo) / 1e9, busy_s=busy_ns / 1e9,
                     span_s=span_s, span_idle_s=span_idle,
                     top_ops=[[n, t] for n, t in top_ops],
                     idle_gaps=labelled, op_s=per_op, op_calls=calls)


@dataclass
class Context:
    """What a per-layer metric reader reads (``bench/metrics/<name>.py``):
    the reduced trace, the traced steps' host-clock seconds, required
    FLOPs (``bench.flops.step_flops``) and live context lengths (one
    array of per-episode lengths per step), the cell's configuration and
    traffic as loaded, the chip's peaks and the number of chips, and the
    root whose ``bench/kernels/`` holds the kernel costs."""
    reduction: Reduction
    step_s: list
    step_flops: list
    peaks: dict
    chips: int
    config: dict = field(default_factory=dict)
    traffic: dict = field(default_factory=dict)
    context_lens: list = field(default_factory=list)
    root: Path = None

    @property
    def peak_flops(self) -> float:
        return self.peaks["bf16_flops"]

    def roofline(self, kernel: str):
        """Share of its roofline, in %, that kernel ``kernel`` reached in
        the traced steps: the sum over its calls of the larger of FLOPs
        over peak FLOP/s and bytes over peak bytes/s, over the device time
        of its ops. None where no op of the kernel ran."""
        from bench import common
        cost = common.kernel_cost(kernel, self.root or common.ROOT)
        red = self.reduction
        names = [n for n in red.op_s if re.search(cost.OP, n)]
        device_s = sum(red.op_s[n] for n in names)
        if not names or device_s <= 0:
            return None
        shapes = cost.calls(self, sum(red.op_calls[n] for n in names))
        ideal = sum(max(cost.flops(x) / self.peaks["bf16_flops"],
                        cost.bytes(x) / self.peaks["hbm_bytes_per_s"])
                    for x in shapes)
        return 100.0 * ideal / device_s
