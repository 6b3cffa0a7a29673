"""The trace reduction on a small trace recorded on a TPU v5e chip, the
per-layer readers on it, and the peaks table.

``bench/testdata/tiny.xplane.pb`` is one traced step of the test-size
cell with one turn of two tokens (``python3 tests/bench/tinycell.py DIR
max_turns=1 max_turn_tokens=2``, then ``bench/run.py --root DIR --trace 1
--trace-steps 1 --keep-trace FILE``), pruned to what ``bench.trace.read``
reads: the device's ``XLA Ops`` and ``XLA Modules`` lines, op names cut
before " = ", and the host's ``bench.*`` spans. The reduction gives the
same numbers on the pruned and the whole trace."""
import sys
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(REPO))

from bench import common, trace  # noqa: E402

TRACE = REPO / "bench" / "testdata" / "tiny.xplane.pb"
STAGES = {"rollout", "expprep", "update"}


@pytest.fixture(scope="module")
def recorded():
    return trace.read(str(TRACE))


def test_recorded_trace_has_device_ops_and_spans(recorded):
    assert list(recorded.ops) == ["/device:TPU:0"]
    ops = recorded.ops["/device:TPU:0"]
    assert len(ops) > 100
    assert all(e >= s for _, s, e in ops)
    assert all("/" in name for name, _, _ in ops)      # module/op
    assert {n for n, _, _ in recorded.spans} == STAGES
    # the stages run in order within a step
    names = [n for n, _, _ in recorded.spans]
    assert names.index("rollout") < names.index("expprep") \
        < names.index("update")


def test_reduction(recorded):
    r = trace.reduce(recorded)
    assert 0 < r.busy_s <= r.window_s
    for name, spans in r.span_s.items():
        assert 0 <= r.span_idle_s[name] <= sum(spans) + 1e-9
    times = [t for _, t in r.top_ops]
    assert times == sorted(times, reverse=True) and len(times) <= 10
    assert len(r.idle_gaps) <= 10
    for stage, secs in r.idle_gaps:
        assert stage in STAGES | {"between spans"} and secs > 0
    # busy time inside the window never exceeds what the ops add up to
    total = sum(e - s for _, s, e in recorded.ops["/device:TPU:0"]) / 1e9
    assert r.busy_s <= total + 1e-9
    # every op in the window is kept with its time and calls, not only
    # the top ten
    lo = min(s for _, s, _ in recorded.spans)
    hi = max(e for _, _, e in recorded.spans)
    inside = [(s, e) for _, s, e in recorded.ops["/device:TPU:0"]
              if e > lo and s < hi]
    assert len(r.op_s) > 10 and set(r.op_calls) == set(r.op_s)
    assert sum(r.op_s.values()) == pytest.approx(
        sum(e - s for s, e in inside) / 1e9)
    assert sum(r.op_calls.values()) == len(inside)
    assert r.top_ops == [[n, t] for n, t in sorted(
        r.op_s.items(), key=lambda x: -x[1])[:10]]


def test_readers_on_the_recorded_trace(recorded):
    r = trace.reduce(recorded)
    ctx = trace.Context(reduction=r, step_s=[r.window_s],
                        step_flops=[{"update": 1e9, "total": 5e9}],
                        peaks=trace.peaks("TPU v5 lite"), chips=1)
    spec = common.load_benchmark(REPO)
    for m in spec["per_layer"]:
        v = common.metric_reader(m["name"], REPO).read(ctx)
        assert v is not None and v >= 0, m["name"]
        if m["unit"] == "%":
            assert v <= 100, m["name"]
    idle = common.metric_reader("device_idle_share", REPO).read(ctx)
    assert idle == pytest.approx(100 * (1 - r.busy_s / r.window_s))


def test_readers_find_nothing_without_spans():
    r = trace.Reduction(window_s=1.0, busy_s=0.5, span_s={}, span_idle_s={},
                        top_ops=[], idle_gaps=[])
    ctx = trace.Context(reduction=r, step_s=[], step_flops=[],
                        peaks=trace.peaks("TPU v5 lite"), chips=1)
    for name in ("rollout_ms", "rollout_idle_share", "update_ms",
                 "update_mfu", "step_mfu"):
        assert common.metric_reader(name, REPO).read(ctx) is None


def test_merge_and_overlap():
    m = trace.merge([(5, 7), (0, 2), (1, 3), (3, 4), (10, 12)])
    assert m == [(0, 4), (5, 7), (10, 12)]
    assert trace.overlap(m, 1, 11) == 3 + 2 + 1


def test_peaks_table():
    assert trace.peaks("TPU v5 lite")["bf16_flops"] == 197e12
    with pytest.raises(KeyError):
        trace.peaks("TPU v9 imaginary")
