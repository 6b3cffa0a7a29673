"""A cell of a second block, added by files alone (``tinycell.
make_moe_root``: the program's MoE family at the test size with the
block ``blocks/toymoe.py``), through the whole harness with the cell's
own limits: a sound run is correct; the control (the reference with
float8 matmul operands in the program's place) is not; nor is a run
whose Update returns its state unchanged, or whose Rollout alters a
token where it is produced (the harness's look for a chip is skipped)."""
import sys
import time
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(REPO))
sys.path.insert(0, str(Path(__file__).resolve().parent))

from bench import check, common, harness  # noqa: E402
import tinycell  # noqa: E402

SEED = 2 ** 31 + 977         # more than 32 signed bits


@pytest.fixture(scope="module")
def cell(tmp_path_factory):
    root = tinycell.make_moe_root(tmp_path_factory.mktemp("toymoe"))
    spec = common.load_benchmark(root)
    return root, spec, common.workload(spec, tinycell.MOE_CELL)


def _run(cell, fault=None):
    root, spec, wl = cell
    return harness.run_cell(spec, wl, SEED, 0.5, False,
                            t_start=time.perf_counter(), require_tpu=False,
                            fault=fault, root=root, log=lambda *a: None)


def test_sound_run_is_correct(cell):
    res, lines, n_compiles = _run(cell)
    assert res["correct"], lines
    assert n_compiles == 0


def test_control_is_not_correct(cell):
    root, spec, wl = cell
    cfg = common.load_config(spec, wl["config"], root)
    block = common.block(cfg, root)
    traffic = common.load_traffic(wl["traffic"], root)
    limits = common.load_limits(wl["name"], root)
    trainer, params, opt_state = harness.build(cfg, traffic, SEED, block)
    stages = harness.wrap_stages(trainer)
    steps = harness.program_side(trainer, params, opt_state, params, stages,
                                 block, cfg, traffic["algo"])[2]
    ref = harness.reference_side(block, cfg, traffic, SEED, steps, limits)
    ctl = harness.reference_side(block, cfg, traffic, SEED, steps, limits,
                                 precision="fp8")
    values = check.readings(*ctl, *ref, [s["gen_mask"] for s in steps])
    correct, lines = check.verdict(values, limits["limits"])
    assert not correct, lines


@pytest.mark.parametrize("fault", ["stale_state", "token_altered"])
def test_fault_is_not_correct(cell, fault):
    res, lines, _ = _run(cell, fault)
    assert not res["correct"], lines
