"""The program-span reduction (``bench/spans.py``): on a hand-made trace
whose every number is known, and on a small trace recorded on a TPU v5e
chip with the program's spans in it, where the benchmark's own reduction
and readers must read what they read without them.

``bench/testdata/tiny.spans.xplane.pb`` is one traced step of the
test-size cell at 2 turns of 2 tokens (``python3 tests/bench/tinycell.py
DIR max_turns=2 max_turn_tokens=2``, then ``bench/run.py --root DIR
--trace 1 --trace-steps 1 --keep-trace FILE``), pruned as
``tiny.xplane.pb`` was, with the host's ``earl.*`` spans and their stats
kept beside the ``bench.*`` ones."""
import sys
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(REPO))

from bench import spans, trace  # noqa: E402

MS = 1_000_000      # ns


def made():
    """Two steps of 100 ms: a Rollout of 60 ms holding one turn (a
    sampled token and its decode call) and a three-token observation
    feed, then an Update; device ops only in decode calls and the
    Update."""
    tr = trace.Trace()
    ops, bench, prog = [], [], []
    for k in range(2):
        t = k * 100 * MS
        bench += [("rollout", t, t + 60 * MS),
                  ("update", t + 60 * MS, t + 100 * MS)]
        prog += [("step", t, t + 100 * MS, {"step": k}),
                 ("rollout", t, t + 60 * MS, {"step": k}),
                 ("rollout.prefill", t, t + 5 * MS, {"rows": 4}),
                 ("rollout.generate", t + 5 * MS, t + 25 * MS,
                  {"turn": 0, "rows": 4}),
                 ("rollout.sample", t + 5 * MS, t + 15 * MS, {}),
                 ("sync", t + 10 * MS, t + 15 * MS, {"what": "sampled"}),
                 ("rollout.decode", t + 15 * MS, t + 25 * MS,
                  {"rows": 4, "live": 2}),
                 ("rollout.observe", t + 25 * MS, t + 55 * MS,
                  {"tokens": 3})]
        for j in range(3):
            a = t + (25 + 10 * j) * MS
            prog.append(("rollout.decode", a, a + 10 * MS,
                         {"rows": 4, "live": 4}))
            ops.append(("jit_rollout_decode/%while", a, a + 4 * MS))
            # a nested op: counted once in the decode device time
            ops.append(("jit_rollout_decode/%fusion", a + MS, a + 2 * MS))
        ops.append(("jit_rollout_decode/%while", t + 16 * MS, t + 20 * MS))
        ops.append(("jit_train_step/%while", t + 60 * MS, t + 100 * MS))
        prog.append(("update", t + 60 * MS, t + 61 * MS, {"step": k}))
    prog.append(("gc", 55 * MS, 58 * MS, {"generation": 2}))
    tr.ops["/device:TPU:0"] = ops
    tr.spans = sorted(bench, key=lambda x: x[1])
    return tr, sorted(prog, key=lambda x: x[1])


def test_reduction_of_a_made_trace():
    tr, prog = made()
    p = spans.reduce(tr, prog)
    assert p.steps == 2 and p.step_s == [0.1, 0.1]
    dec = p.by_name["rollout.decode"]
    assert dec["count"] == 8
    assert dec["s"] == pytest.approx(0.08)
    assert dec["stats"] == {"rows": 32, "live": 28}
    # each decode span of 10 ms holds 4 ms of device work
    assert dec["idle_s"] == pytest.approx(8 * 0.006)
    assert p.by_stage["rollout"]["sync"]["count"] == 2
    assert set(p.by_stage) == {"rollout", "update"}
    assert p.decode_device_s == pytest.approx(2 * 0.016)
    # prefill 5, generate 20, observe 30 of 60 ms; the GC at 55-58 ms of
    # step 0 covers 3 ms more there
    assert p.rollout_cover == pytest.approx(100 * (2 * 55 + 3) / 120)


def test_quantities_of_a_made_trace():
    tr, prog = made()
    q = spans.quantities(spans.reduce(tr, prog))
    assert set(q) == set(spans.QUANTITIES)
    assert q["rollout_generate_ms"] == pytest.approx(20)
    assert q["rollout_observe_ms"] == pytest.approx(30)
    assert q["ref_ms"] is None
    assert q["rollout_host_syncs"] == 1
    assert q["rollout_decode_calls"] == 4
    assert q["rollout_live_row_share"] == pytest.approx(100 * 28 / 32)
    assert q["decode_device_ms"] == pytest.approx(16)


def test_idle_gaps_take_the_innermost_program_span():
    tr, prog = made()
    p = spans.reduce(tr, prog, top=30)
    labels = {round(s * 1e3, 6): n for n, s in p.idle_gaps}
    # the longest gap, 0-16 ms of each step, has its midpoint in the
    # sampled token's span, which starts with the turn's
    assert p.idle_gaps[0] == ["rollout.sample", pytest.approx(0.016)]
    # the 6 ms after each observation decode call's op, inside its span
    assert labels[6.0] == "rollout.decode"
    # with no program span over it, a gap keeps the stage's label
    bare = spans.reduce(tr, [x for x in prog if x[0] == "step"], top=30)
    assert {n for n, _ in bare.idle_gaps} == {"step"}
    none = spans.reduce(tr, [], window=(0, 200 * MS), top=30)
    assert {n for n, _ in none.idle_gaps} == {"rollout"}


def test_nothing_to_read_gives_none():
    tr, _ = made()
    p = spans.reduce(tr, [], window=(0, 200 * MS))
    assert p.steps == 0 and p.rollout_cover == 0.0
    assert spans.quantities(p) == {q: None for q in spans.QUANTITIES}


def test_slowest_step_and_gc():
    tr, prog = made()
    prog = [x if x != ("step", 0, 100 * MS, {"step": 0})
            else ("step", 0, 101 * MS, {"step": 0}) for x in prog]
    slow = spans.slowest_step(prog, top=2)
    assert slow["step"] == 0 and slow["s"] == pytest.approx(0.101)
    assert [n for n, *_ in slow["spans"]] == ["rollout.observe",
                                              "rollout.generate"]
    assert spans.gc_summary(prog) == {"2": {"count": 1, "s": 0.003,
                                            "max_s": 0.003}}
    assert spans.slowest_step([]) == {}


def test_busy_within_matches_overlap():
    ops = [("a", 0, 10), ("b", 5, 20), ("c", 30, 40), ("d", 50, 55)]
    b = spans.Busy(ops)
    merged = trace.merge((s, e) for _, s, e in ops)
    for lo, hi in [(0, 60), (3, 33), (20, 30), (21, 52), (41, 49)]:
        assert b.within(lo, hi) == trace.overlap(merged, lo, hi)


NEW = REPO / "bench" / "testdata" / "tiny.spans.xplane.pb"
OLD = REPO / "bench" / "testdata" / "tiny.xplane.pb"
STAGES = {"rollout", "expprep", "update"}
# the recorded step: 8 games, 2 turns of 2 sampled tokens, one 45-token
# observation fed between them
TURNS, TOKENS, OBS, GAMES = 2, 2, 45, 8


@pytest.fixture(scope="module")
def recorded():
    tr = trace.read(str(NEW))
    return tr, spans.read(str(NEW))


def test_recorded_program_spans(recorded):
    tr, prog = recorded
    count = {}
    for n, *_ in prog:
        count[n] = count.get(n, 0) + 1
    for stage in ("step", "rollout", "expprep", "dispatch", "update"):
        assert [st for n, *_, st in prog if n == stage] == [{"step": 3}]
    assert count["rollout.generate"] == count["rollout.env"] == TURNS
    assert count["rollout.sample"] == TURNS * TOKENS
    assert count["rollout.observe"] == TURNS - 1
    assert count["rollout.decode"] == TURNS * TOKENS + OBS
    assert count["ref"] == count["rollout.prefill"] == 1
    # the step's path read the host 24 times: 21 in the Rollout (see
    # tests/test_tracing.py), the update's loss, KL and IS weight after it
    p = spans.reduce(tr, prog)
    assert p.by_stage["rollout"]["sync"]["count"] == \
        1 + 3 * TURNS * TOKENS + 3 * TURNS + 2
    assert count["sync"] == p.by_stage["rollout"]["sync"]["count"] + 3
    what = {}
    for n, *_, st in prog:
        if n == "sync":
            what[st["what"]] = what.get(st["what"], 0) + 1
    assert what["sampled"] == what["logprob"] == what["action_mask"] \
        == TURNS * TOKENS
    assert what["loss"] == what["kl"] == what["is_weight_mean"] == 1
    dec = p.by_name["rollout.decode"]
    assert dec["stats"]["rows"] == GAMES * dec["count"]
    assert 0 < dec["stats"]["live"] <= dec["stats"]["rows"]


def test_recorded_reduction_and_quantities(recorded):
    tr, prog = recorded
    p = spans.reduce(tr, prog)
    assert p.steps == 1 and len(p.step_s) == 1
    q = spans.quantities(p)
    assert q["rollout_decode_calls"] == TURNS * TOKENS + OBS
    assert q["rollout_host_syncs"] == 21
    assert 0 < q["rollout_live_row_share"] <= 100
    for k in ("rollout_generate_ms", "rollout_observe_ms", "ref_ms",
              "decode_device_ms"):
        assert q[k] > 0, k
    # the Rollout's children cover it
    assert p.rollout_cover >= 95
    roll = sum(trace.reduce(tr).span_s["rollout"])
    assert q["rollout_generate_ms"] + q["rollout_observe_ms"] < 1e3 * roll
    # idle gaps are named by program spans, not only by stage
    names = {n for n, _ in p.idle_gaps}
    assert names & {n for n, *_ in prog}
    for n, sec in p.idle_gaps:
        assert sec > 0


def test_recorded_programs_are_named(recorded):
    tr, _ = recorded
    modules = {n.split("/")[0] for n, _, _ in tr.ops["/device:TPU:0"]}
    assert {"jit_rollout_decode", "jit_rollout_prefill"} <= modules
    assert "jit__lambda" not in modules


def test_benchmark_reduction_ignores_program_spans(recorded):
    """The benchmark's own reduction reads only its ``bench.*`` spans, and
    every accepted reader reads the new trace as it read the old one."""
    from bench import common
    tr, _ = recorded
    assert {n for n, _, _ in tr.spans} == STAGES
    r = trace.reduce(tr)
    assert set(r.span_s) == STAGES
    ctx = trace.Context(reduction=r, step_s=[r.window_s],
                        step_flops=[{"update": 1e9, "total": 5e9}],
                        peaks=trace.peaks("TPU v5 lite"), chips=1)
    spec = common.load_benchmark(REPO)
    for m in spec["per_layer"]:
        v = common.metric_reader(m["name"], REPO).read(ctx)
        assert v is not None and v >= 0, m["name"]
        if m["unit"] == "%":
            assert v <= 100, m["name"]


def test_old_trace_reads_as_before():
    """The trace recorded before the program had spans: the benchmark's
    reduction gives the numbers it gave when it was recorded, and the
    program-span reduction finds nothing to read."""
    tr = trace.read(str(OLD))
    r = trace.reduce(tr)
    assert r.window_s == pytest.approx(1.12050133)
    assert r.busy_s == pytest.approx(0.00165589)
    assert r.span_s == {"rollout": [pytest.approx(1.11655158)],
                        "expprep": [pytest.approx(0.00141773)],
                        "update": [pytest.approx(0.00205199)]}
    assert r.span_idle_s["rollout"] == pytest.approx(1.115061657)
    assert len(r.op_s) == 664 and sum(r.op_calls.values()) == 6963
    assert r.top_ops[0] == ["jit_bitwise_and/%and.1",
                            pytest.approx(0.000227314)]
    assert r.idle_gaps[0] == ["rollout", pytest.approx(0.003301558)]
    assert spans.read(str(OLD)) == []
    p = spans.reduce(tr, [])
    assert spans.quantities(p) == {q: None for q in spans.QUANTITIES}
    assert {n for n, _ in p.idle_gaps} <= STAGES | {"between spans"}
