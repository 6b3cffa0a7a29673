"""The FLOP count of bench/flops.py against the program's own parameter
count, and its attention term against a sum over positions."""
import json
import sys
from dataclasses import replace
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(REPO))

from bench import flops  # noqa: E402
from repro.configs import get_config  # noqa: E402


def _sizes(arch, **over):
    c = replace(get_config(arch), **over)
    m = {"n_layers": c.n_layers, "d_model": c.d_model, "n_heads": c.n_heads,
         "n_kv_heads": c.n_kv_heads, "head_dim": c.head_dim_,
         "d_ff": c.d_ff, "vocab_size": c.vocab_size}
    return c, m


@pytest.mark.parametrize("arch,over", [
    ("qwen2-0.5b", {}),
    ("qwen2.5-72b", {}),
    ("qwen2.5-72b", {"n_layers": 4, "n_heads": 8, "n_kv_heads": 1,
                     "vocab_size": 19008}),
])
def test_matmul_weights_tie_to_param_count(arch, over):
    c, m = _sizes(arch, **over)
    d, L = c.d_model, c.n_layers
    # param_count counts the two norms of each block, and neither the
    # final norm nor the QKV biases; a tied table is the head, a matmul,
    # and an untied one is only looked up
    norms = 2 * L * d
    table = 0 if c.tie_embeddings else c.vocab_size * d
    assert flops.matmul_weights(m) == c.param_count() - norms - table


def test_config_file_sizes_match_the_program_config():
    cfg = json.loads((REPO / "bench/configs/qwen2-0.5b.json").read_text())
    c, m = _sizes(cfg["arch"], **cfg["model"])
    assert flops.matmul_weights(cfg["model"]) == flops.matmul_weights(m)


@pytest.mark.parametrize("n", [1, 7, 212])
def test_attention_term_is_causal(n):
    _, m = _sizes("qwen2-0.5b")
    per_pos = sum(4 * t * m["n_heads"] * m["head_dim"] * m["n_layers"]
                  for t in range(1, n + 1))
    assert flops.attention_flops(m, n) == per_pos
    fwd = flops.forward_flops(m, [n])
    assert fwd == 2 * flops.matmul_weights(m) * n + per_pos


def test_step_is_five_forwards():
    _, m = _sizes("qwen2-0.5b")
    lens = [212, 159, 106, 53]
    s = flops.step_flops(m, lens)
    fwd = flops.forward_flops(m, lens)
    assert s["rollout"] == s["reference"] == fwd
    assert s["update"] == 3 * fwd and s["total"] == 5 * fwd
