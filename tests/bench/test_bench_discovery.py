"""Every cell of BENCHMARK.json resolves to its files by name; a new cell,
of a new block too, needs only new files; without a TPU the harness
refuses to measure."""
import importlib
import json
import os
import shutil
import subprocess
import sys
from dataclasses import replace
from pathlib import Path

import numpy as np
import pytest

REPO = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(REPO))
sys.path.insert(0, str(Path(__file__).resolve().parent))

from bench import check, common, flops, harness, trace, weights  # noqa: E402
import tinycell  # noqa: E402
from repro.configs import get_config  # noqa: E402
from repro.models.registry import build_model  # noqa: E402

SPEC = common.load_benchmark(REPO)
E2E = {"tokens_per_s", "step_s_p90", "peak_hbm_gib", "setup_s"}


def _block_fits_the_program(cfg, root):
    """The configuration's block resolves, and the benchmark's weights
    have the program's parameter tree, leaf for leaf."""
    import jax
    block = common.block(cfg, root)
    m = cfg["model"]
    model = build_model(replace(get_config(cfg["arch"]),
                                **block.program_config(m)))
    want = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    got = weights.to_program(
        block, {n: jax.ShapeDtypeStruct(s, jax.numpy.bfloat16)
                for n, s in block.shapes(m).items()})
    assert (jax.tree.map(lambda x: x.shape, got)
            == jax.tree.map(lambda x: x.shape, want))
    assert set(block.LAYER_KEYS) >= {n for n in block.shapes(m)
                                     if block.PROGRAM_PATH[n][0] == "layers"}
    assert block.matmul_weights(m) > 0
    return block


@pytest.mark.parametrize("wl", SPEC["workloads"], ids=lambda w: w["name"])
def test_workload_resolves(wl):
    cfg = common.load_config(SPEC, wl["config"], REPO)
    assert cfg["name"] == wl["config"]
    _block_fits_the_program(cfg, REPO)
    traffic = common.load_traffic(wl["traffic"], REPO)
    gen = importlib.import_module(f"bench.traffic.{traffic['generator']}")
    assert hasattr(gen, traffic["class"])
    # the rollout's room check: every turn leaves room for its tokens
    # and one more observation, so no episode is truncated
    env = getattr(gen, traffic["class"])(**traffic["kwargs"])
    need = (env.obs_len + traffic["max_turns"]
            * (traffic["max_turn_tokens"] + env.obs_len))
    assert need <= traffic["max_context"]
    limits = common.load_limits(wl["name"], REPO)
    assert set(limits["limits"]) == set(check.NUMBERS)
    for m in common.metrics_of(SPEC, wl["name"], "per_layer"):
        assert callable(common.metric_reader(m["name"], REPO).read)
    names = {m["name"] for m in common.metrics_of(SPEC, wl["name"],
                                                  "end_to_end")}
    assert "setup_s" in names and len(names) >= 2


def test_metrics_are_known_and_move_end_to_end_metrics():
    e2e = {m["name"] for m in SPEC["end_to_end"]}
    assert e2e <= E2E
    for m in SPEC["per_layer"]:
        assert m["moves"] in e2e
        assert (REPO / "bench" / "metrics" / f"{m['name']}.py").exists()
    cfg_files = [c["file"] for c in SPEC["configs"]]
    assert len(set(cfg_files)) == len(cfg_files)
    for p in SPEC["paths"]:
        assert (REPO / p).is_dir()


def test_new_cell_needs_only_new_files(tmp_path):
    root = tinycell.make_moe_root(tmp_path)
    spec = common.load_benchmark(root)
    for cell, block in ((tinycell.CELL, "qwen2"),
                        (tinycell.MOE_CELL, "toymoe")):
        wl = common.workload(spec, cell)
        cfg = common.load_config(spec, wl["config"], root)
        assert cfg["model"]["vocab_size"] == 512 and cfg["block"] == block
        _block_fits_the_program(cfg, root)
        assert common.load_traffic(wl["traffic"], root)["batch"] == 8
        assert set(common.load_limits(wl["name"], root)["limits"]) == set(
            check.NUMBERS)
    # the second block is a new file, found under the root by its name
    assert (root / "bench/blocks/toymoe.py").exists()
    assert not (REPO / "bench/blocks/toymoe.py").exists()
    with pytest.raises(SystemExit, match="no file"):
        common.block({"name": "x", "block": "absent"}, root)
    # a metric added as a file is found by its name
    (root / "bench" / "metrics" / "new_metric.x.py").write_text(
        "def read(ctx):\n    return None\n")
    assert common.metric_reader("new_metric.x", root).read(None) is None


KERNEL = '''import numpy as np

OP = r"^jit_train_step/%while\\.8$"


def calls(ctx, n):
    m = ctx.config["model"]
    tokens = sum(int(np.sum(x)) for x in ctx.context_lens)
    return [{"tokens": tokens, "d": m["d_model"],
             "layers": m["n_layers"]}] * n


def flops(s):
    return 2.0 * s["tokens"] * s["d"] ** 2 * s["layers"]


def bytes(s):
    return 2.0 * s["d"] ** 2 * s["layers"]
'''


def test_new_kernel_needs_only_new_files(tmp_path):
    """A kernel's cost and its roofline reader, added as files with one
    BENCHMARK.json entry each, read a value from a trace through the
    harness; a kernel whose ops did not run leaves its metric out."""
    root = tinycell.make_root(tmp_path)
    (root / "bench" / "kernels").mkdir()
    (root / "bench" / "kernels" / "x.py").write_text(KERNEL)
    (root / "bench" / "kernels" / "absent.py").write_text(
        KERNEL.replace("jit_train_step", "jit_no_such_module"))
    spec = common.load_benchmark(root)
    for k in ("x", "absent"):
        (root / "bench" / "metrics" / f"{k}_roofline.py").write_text(
            f"def read(ctx):\n    return ctx.roofline({k!r})\n")
        spec["per_layer"].append(
            {"name": f"{k}_roofline", "unit": "%", "better": "higher",
             "source": "device_trace", "layer": "kernels",
             "moves": "tokens_per_s"})
    (root / "BENCHMARK.json").write_text(json.dumps(spec))

    spec = common.load_benchmark(root)
    wl = common.workload(spec, tinycell.CELL)
    cfg = common.load_config(spec, wl["config"], root)
    block = common.block(cfg, root)
    traffic = common.load_traffic(wl["traffic"], root)
    red = trace.reduce(trace.read(
        str(REPO / "bench" / "testdata" / "tiny.xplane.pb")))
    lens = [np.full(traffic["batch"], 37)]
    peaks = trace.peaks("TPU v5 lite")
    ctx = trace.Context(
        reduction=red, step_s=[red.window_s],
        step_flops=[flops.step_flops(block, cfg["model"], x)
                    for x in lens],
        peaks=peaks, chips=1, config=cfg, traffic=traffic,
        context_lens=lens, root=root)
    out = harness.per_layer(spec, wl, ctx, root)
    assert "absent_roofline" not in out
    m = cfg["model"]
    op = "jit_train_step/%while.8"
    one = max(2.0 * 37 * traffic["batch"] * m["d_model"] ** 2
              * m["n_layers"] / peaks["bf16_flops"],
              2.0 * m["d_model"] ** 2 * m["n_layers"]
              / peaks["hbm_bytes_per_s"])
    assert out["x_roofline"]["value"] == pytest.approx(
        100 * one * red.op_calls[op] / red.op_s[op])
    assert out["x_roofline"]["unit"] == "%"


def _run(args, cwd, timeout=240):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    return subprocess.run([sys.executable, "bench/run.py", *args], cwd=cwd,
                          env=env, capture_output=True, text=True,
                          timeout=timeout)


def _no_result(out: str) -> bool:
    lines = out.strip().splitlines()
    if not lines:
        return True
    try:
        return not isinstance(json.loads(lines[-1]), dict)
    except ValueError:
        return True


def test_refuses_without_a_tpu():
    r = _run(["--workload", SPEC["workloads"][0]["name"], "--seed",
              "4294967311", "--seconds", "1", "--trace", "0"], REPO)
    assert r.returncode != 0
    assert _no_result(r.stdout)
    assert "no accelerator" in r.stderr


def test_fails_with_only_the_benchmark_files(tmp_path):
    shutil.copy(REPO / "BENCHMARK.json", tmp_path)
    for p in SPEC["paths"]:
        shutil.copytree(REPO / p, tmp_path / p,
                        ignore=shutil.ignore_patterns("__pycache__"))
    r = _run(["--workload", SPEC["workloads"][0]["name"], "--seed", "1",
              "--seconds", "1", "--trace", "0"], tmp_path)
    assert r.returncode != 0
    assert _no_result(r.stdout)
