"""A cell small enough for a test run: the qwen2 block at 2 layers,
d 128 and a 512-token vocabulary, on 8 Connect-Four games of 2 turns,
held to limits of its own (``tiny.c4.limits.json``), set as a cell's
are: between the program's and the control's readings at this size, on
the CPU over six seeds. ``make_root(dir)`` writes its BENCHMARK.json and
files, as a new cell would add them.

    python3 tests/bench/tinycell.py DIR [key=value ...]   # under DIR
"""
from __future__ import annotations

import json
import shutil
import sys
from pathlib import Path

REPO = Path(__file__).resolve().parents[2]
CELL = "tiny.c4"

TINY_MODEL = {"n_layers": 2, "d_model": 128, "n_heads": 4, "n_kv_heads": 2,
              "head_dim": 32, "d_ff": 256, "vocab_size": 512,
              "qkv_bias": True, "rope_theta": 1000000.0, "rms_eps": 1e-06,
              "tie_embeddings": True, "dtype": "bfloat16"}


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
        {"name": "tiny", "arch": "qwen2-0.5b", "model": TINY_MODEL}))
    mix = json.loads(
        (REPO / "bench/traffic/connect4-4turn.json").read_text())
    mix.update(batch=8, max_turns=2, max_turn_tokens=3, max_context=160)
    mix.update(traffic)
    mix["kwargs"]["opening_plies"] = 20
    (root / "bench/traffic/tiny-c4.json").write_text(json.dumps(mix))
    shutil.copytree(REPO / "bench" / "metrics", root / "bench" / "metrics",
                    dirs_exist_ok=True,
                    ignore=shutil.ignore_patterns("__pycache__"))
    limits = (Path(__file__).resolve().parent
              / f"{CELL}.limits.json").read_text()
    (root / "bench/limits" / f"{CELL}.json").write_text(limits)
    return root


if __name__ == "__main__":
    make_root(sys.argv[1], **{k: int(v) for k, v in
                              (a.split("=") for a in sys.argv[2:])})
