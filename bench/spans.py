"""The program's own spans in a kept trace, reduced.

    python3 bench/spans.py TRACE.xplane.pb

The program brackets its work in ``earl.<name>`` spans (``earl.step``,
``earl.rollout.decode``, ``earl.sync`` ...; ``repro/utils/tracing.py``),
host events on the profiler's clock beside the benchmark's
``bench.<stage>`` spans and the device ops that ``bench.trace.read``
reads. ``bench/run.py --trace 1 --keep-trace FILE`` keeps such a trace.
This module reads those spans with their numeric stats and reduces them:
per span name, and per enclosing benchmark stage, the count, summed
seconds, summed stats and the device idle seconds inside; the longest
idle gaps, each labelled by the innermost program span over its
midpoint; and, per traced step, the Rollout's quantities listed in
``QUANTITIES``. The harness's result line does not carry them.
"""
from __future__ import annotations

import bisect
import json
import sys
from dataclasses import dataclass, field
from pathlib import Path

if __package__ in (None, ""):
    sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from bench import trace  # noqa: E402

PREFIX = "earl."
# device programs of a decode call: the python engine's named decode jit,
# or the compiled engine's macro-step
DECODE_MODULES = ("jit_rollout_decode/", "jit_turn_step/")
STAGES = ("step", "rollout", "expprep", "dispatch", "update")
QUANTITIES = ("rollout_generate_ms", "rollout_observe_ms", "ref_ms",
              "rollout_host_syncs", "rollout_decode_calls",
              "rollout_live_row_share", "decode_device_ms")


def read(path: str) -> list:
    """The program's spans: [(name, start_ns, end_ns, stats)], sorted by
    start, the prefix cut from the name."""
    from jax.profiler import ProfileData
    out = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(PREFIX):
                    out.append((e.name[len(PREFIX):], e.start_ns,
                                e.start_ns + e.duration_ns,
                                {k: v for k, v in e.stats}))
    out.sort(key=lambda x: x[1])
    return out


class Busy:
    """A device's merged busy intervals with prefix sums: busy time inside
    any interval in O(log n)."""

    def __init__(self, ops):
        self.merged = trace.merge((s, e) for _, s, e in ops)
        self.starts = [s for s, _ in self.merged]
        self.cum = [0.0]
        for s, e in self.merged:
            self.cum.append(self.cum[-1] + (e - s))

    def _upto(self, t) -> float:
        i = bisect.bisect_right(self.starts, t)
        if i == 0:
            return 0.0
        s, e = self.merged[i - 1]
        return self.cum[i - 1] + (min(e, t) - s)

    def within(self, lo, hi) -> float:
        return self._upto(hi) - self._upto(lo)


def _tally(into: dict, name, s, e, stats, idle_ns):
    t = into.setdefault(name, {"count": 0, "s": 0.0, "stats": {},
                               "idle_s": 0.0})
    t["count"] += 1
    t["s"] += (e - s) / 1e9
    t["idle_s"] += idle_ns / 1e9
    for k, v in stats.items():
        if isinstance(v, (int, float)):
            t["stats"][k] = t["stats"].get(k, 0) + v


@dataclass
class Programs:
    steps: int                          # earl.step spans in the window
    by_name: dict                       # name -> count, s, stats, idle_s
    by_stage: dict                      # bench stage -> name -> the same
    idle_gaps: list                     # [[innermost span, seconds]]
    decode_device_s: float = 0.0        # union of decode programs' ops
    step_s: list = field(default_factory=list)   # each earl.step, s
    rollout_cover: float = None         # % of the Rollout under children


def reduce(tr: trace.Trace, spans: list, window=None,
           top: int = 10) -> Programs:
    """``tr``: ``bench.trace.read`` of the same file; ``window``:
    (start_ns, end_ns), by default from the first span's start to the
    last span's end, the benchmark's and the program's alike (a step's
    ``earl.step`` span opens before its first stage span)."""
    if window is None:
        ref = tr.spans + [(n, s, e) for n, s, e, _ in spans]
        window = (min(s for _, s, _ in ref), max(e for _, _, e in ref))
    lo, hi = window
    devices = sorted(tr.ops)
    busy = [Busy(tr.ops[d]) for d in devices]

    def idle(s, e):
        if not busy:
            return 0.0
        return sum((e - s) - b.within(s, e) for b in busy) / len(busy)

    inside = [x for x in spans if x[1] >= lo and x[2] <= hi]
    by_name, by_stage = {}, {}
    for name, s, e, st in inside:
        gap = idle(s, e)
        _tally(by_name, name, s, e, st, gap)
        mid = (s + e) / 2
        stage = next((n for n, a, z in tr.spans if a <= mid <= z),
                     "between spans")
        _tally(by_stage.setdefault(stage, {}), name, s, e, st, gap)

    gaps = []
    if busy:
        prev = lo
        for s, e in busy[0].merged + [(hi, hi)]:
            s, e = max(s, lo), min(e, hi)
            if s > prev:
                gaps.append((prev, s))
            prev = max(prev, e)
    labelled = []
    for s, e in sorted(gaps, key=lambda g: g[0] - g[1])[:top]:
        mid = (s + e) / 2
        over = [x for x in inside if x[1] <= mid <= x[2]]
        label = (max(over, key=lambda x: (x[1], -x[2]))[0] if over else
                 next((n for n, a, z in tr.spans if a <= mid <= z),
                      "between spans"))
        labelled.append([label, (e - s) / 1e9])

    decode = []
    for d in devices:
        decode += [(s, e) for n, s, e in tr.ops[d]
                   if n.startswith(DECODE_MODULES) and e > lo and s < hi]
    decode_ns = sum(e - s for s, e in trace.merge(decode))
    step_s = [(e - s) / 1e9 for n, s, e, _ in inside if n == "step"]
    return Programs(steps=len(step_s), by_name=by_name, by_stage=by_stage,
                    idle_gaps=labelled,
                    decode_device_s=decode_ns / 1e9 / max(len(devices), 1),
                    step_s=step_s, rollout_cover=_cover(tr, inside))


def _cover(tr, spans):
    """Share of the Rollout's time, in %, that program spans below the
    program's own ``rollout`` span cover: the benchmark's Rollout spans
    where the trace has them, else the program's."""
    outer = ([(s, e) for n, s, e in tr.spans if n == "rollout"]
             or [(s, e) for n, s, e, _ in spans if n == "rollout"])
    total = sum(e - s for s, e in outer)
    if not total:
        return None
    kids = trace.merge((s, e) for n, s, e, _ in spans
                       if n.startswith(("rollout.", "ref", "sync", "gc")))
    covered = sum(trace.overlap(kids, s, e) for s, e in outer)
    return 100.0 * covered / total


def quantities(p: Programs) -> dict:
    """Per traced step: the sampled-token loops' and the observation
    feeds' seconds, the reference pass, the host syncs inside the
    Rollout, the decode calls, the share of decoded rows that advance,
    and the decode programs' device time; None where nothing was read."""
    n = p.steps
    if not n:
        return {q: None for q in QUANTITIES}
    name = p.by_name
    roll = p.by_stage.get("rollout", {})

    def ms(span):
        return 1e3 * name[span]["s"] / n if span in name else None

    dec = name.get("rollout.decode")
    live = (100.0 * dec["stats"]["live"] / dec["stats"]["rows"]
            if dec and dec["stats"].get("rows") else None)
    return {
        "rollout_generate_ms": ms("rollout.generate"),
        "rollout_observe_ms": ms("rollout.observe"),
        "ref_ms": ms("ref"),
        "rollout_host_syncs": (roll["sync"]["count"] / n
                               if "sync" in roll else None),
        "rollout_decode_calls": dec["count"] / n if dec else None,
        "rollout_live_row_share": live,
        "decode_device_ms": (1e3 * p.decode_device_s / n
                             if p.decode_device_s else None),
    }


def slowest_step(spans: list, top: int = 15) -> dict:
    """The longest ``earl.step`` span and the longest program spans inside
    it other than the stages: where a stalled step's time went."""
    steps = [x for x in spans if x[0] == "step"]
    if not steps:
        return {}
    _, a, z, st = max(steps, key=lambda x: x[2] - x[1])
    kids = sorted((x for x in spans if a <= x[1] and x[2] <= z
                   and x[0] not in STAGES),
                  key=lambda x: x[1] - x[2])[:top]
    return {"step": st.get("step"), "s": (z - a) / 1e9,
            "spans": [[n, (e - s) / 1e9, st] for n, s, e, st in kids]}


def gc_summary(spans: list) -> dict:
    """Garbage collections in the trace: count and seconds per generation,
    and the longest."""
    out = {}
    for n, s, e, st in spans:
        if n == "gc":
            g = out.setdefault(str(st.get("generation")), [0, 0.0, 0.0])
            g[0] += 1
            g[1] += (e - s) / 1e9
            g[2] = max(g[2], (e - s) / 1e9)
    return {g: {"count": c, "s": t, "max_s": m}
            for g, (c, t, m) in sorted(out.items())}


def summary(path: str) -> dict:
    tr = trace.read(path)
    spans = read(path)
    p = reduce(tr, spans)
    return {"steps": p.steps, "step_s": p.step_s,
            "quantities": quantities(p),
            "rollout_cover_pct": p.rollout_cover,
            "idle_gaps": p.idle_gaps, "gc": gc_summary(spans),
            "slowest_step": slowest_step(spans),
            "by_name": p.by_name, "by_stage": p.by_stage}


if __name__ == "__main__":
    print(json.dumps(summary(sys.argv[1]), indent=1))
