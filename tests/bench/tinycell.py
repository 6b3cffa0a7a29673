"""Cells small enough for a test run, on 8 Connect-Four games of 2 turns
(traffic ``tiny-c4``):

- ``tiny.c4``: the qwen2 block at 2 layers, d 128 and a 512-token
  vocabulary, held to limits of its own (``tiny.c4.limits.json``), set as
  a cell's are: between the program's and the control's readings at this
  size, on the CPU over six seeds;
- ``toymoe.c4`` (``make_moe_root``): a sparse-expert configuration of the
  program's MoE family at the same size, 4 experts of width 128, its block
  (``blocks/toymoe.py``) and its limits (``toymoe.c4.limits.json``), added
  to the root as new files and entries only.

``make_root(dir)`` writes the BENCHMARK.json and files, as a new cell
would add them.

    python3 tests/bench/tinycell.py DIR [key=value ...]   # under DIR
"""
from __future__ import annotations

import json
import shutil
import sys
from pathlib import Path

REPO = Path(__file__).resolve().parents[2]
HERE = Path(__file__).resolve().parent
CELL = "tiny.c4"
MOE_CELL = "toymoe.c4"

TINY_MODEL = {"n_layers": 2, "d_model": 128, "n_heads": 4, "n_kv_heads": 2,
              "head_dim": 32, "d_ff": 256, "vocab_size": 512,
              "qkv_bias": True, "rope_theta": 1000000.0, "rms_eps": 1e-06,
              "tie_embeddings": True, "dtype": "bfloat16"}

# every expert takes every token (top_k = n_experts) and capacity T holds
# them all, so no routing decision or drop is left to rounding; no router
# loss enters the Update
TOY_MOE_MODEL = {"n_layers": 2, "d_model": 128, "n_heads": 4,
                 "n_kv_heads": 2, "head_dim": 32, "d_ff": 128,
                 "vocab_size": 512, "qkv_bias": False,
                 "rope_theta": 10000.0, "rms_eps": 1e-06,
                 "tie_embeddings": False, "dtype": "bfloat16",
                 "moe": {"n_experts": 4, "top_k": 4, "d_ff_expert": 128,
                         "capacity_factor": 1.0, "router_aux_weight": 0.0}}


def _copy(src: Path, dst: Path):
    shutil.copytree(src, dst, dirs_exist_ok=True,
                    ignore=shutil.ignore_patterns("__pycache__"))


def make_root(root, **traffic) -> Path:
    """``traffic`` overrides keys of the test mix (batch, max_turns, ...)."""
    root = Path(root)
    spec = json.loads((REPO / "BENCHMARK.json").read_text())
    spec["configs"] = [{"name": "tiny", "source": "tests/bench/tinycell.py",
                        "file": "bench/configs/tiny.json", "reduced": [],
                        "why": "test size"}]
    spec["workloads"] = [{"name": CELL, "config": "tiny",
                          "traffic": "tiny-c4", "chips": 1,
                          "why": "test size"}]
    for m in spec["per_layer"]:
        m.pop("workloads", None)
    for sub in ("configs", "traffic", "limits"):
        (root / "bench" / sub).mkdir(parents=True, exist_ok=True)
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    (root / "bench/configs/tiny.json").write_text(json.dumps(
        {"name": "tiny", "arch": "qwen2-0.5b", "block": "qwen2",
         "model": TINY_MODEL}))
    mix = json.loads(
        (REPO / "bench/traffic/connect4-4turn.json").read_text())
    mix.update(batch=8, max_turns=2, max_turn_tokens=3, max_context=160)
    mix.update(traffic)
    mix["kwargs"]["opening_plies"] = 20
    (root / "bench/traffic/tiny-c4.json").write_text(json.dumps(mix))
    for sub in ("metrics", "blocks"):
        _copy(REPO / "bench" / sub, root / "bench" / sub)
    (root / "bench/limits" / f"{CELL}.json").write_text(
        (HERE / f"{CELL}.limits.json").read_text())
    return root


def make_moe_root(root, **traffic) -> Path:
    """``make_root``, then the toy MoE cell added by new files and new
    entries of BENCHMARK.json alone."""
    root = make_root(root, **traffic)
    spec = json.loads((root / "BENCHMARK.json").read_text())
    spec["configs"].append(
        {"name": "toymoe", "source": "tests/bench/tinycell.py",
         "file": "bench/configs/toymoe.json", "reduced": [],
         "why": "test size"})
    spec["workloads"].append({"name": MOE_CELL, "config": "toymoe",
                              "traffic": "tiny-c4", "chips": 1,
                              "why": "test size"})
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    (root / "bench/configs/toymoe.json").write_text(json.dumps(
        {"name": "toymoe", "arch": "granite-moe-3b-a800m",
         "block": "toymoe", "model": TOY_MOE_MODEL}))
    shutil.copy(HERE / "blocks" / "toymoe.py",
                root / "bench/blocks/toymoe.py")
    (root / "bench/limits" / f"{MOE_CELL}.json").write_text(
        (HERE / f"{MOE_CELL}.limits.json").read_text())
    return root


if __name__ == "__main__":
    make_moe_root(sys.argv[1], **{k: int(v) for k, v in
                                  (a.split("=") for a in sys.argv[2:])})
