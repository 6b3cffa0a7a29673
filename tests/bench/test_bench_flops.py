"""The FLOP count of bench/flops.py and each block's matmul weights
against the program's own parameter count, and the attention term
against a sum over positions."""
import json
import sys
from dataclasses import replace
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(REPO))
sys.path.insert(0, str(Path(__file__).resolve().parent))

from bench import common, flops  # noqa: E402
import tinycell  # noqa: E402
from repro.configs import get_config  # noqa: E402

QWEN2 = common.load_module(REPO / "bench/blocks/qwen2.py", "block_qwen2")
TOYMOE = common.load_module(tinycell.HERE / "blocks/toymoe.py",
                            "block_toymoe")


def _sizes(block, arch, **over):
    """The program's config with the block's overrides ``over``, and the
    sizes ``m`` a configuration file would give for it."""
    c = replace(get_config(arch), **block.program_config(over))
    m = {"n_layers": c.n_layers, "d_model": c.d_model, "n_heads": c.n_heads,
         "n_kv_heads": c.n_kv_heads, "head_dim": c.head_dim_,
         "d_ff": c.d_ff, "vocab_size": c.vocab_size}
    if c.moe is not None:
        m["moe"] = {"n_experts": c.moe.n_experts, "top_k": c.moe.top_k,
                    "d_ff_expert": c.moe.d_ff_expert}
    return c, m


@pytest.mark.parametrize("block,arch,over", [
    pytest.param(QWEN2, "qwen2-0.5b", {}, id="qwen2-0.5b-over0"),
    pytest.param(QWEN2, "qwen2.5-72b", {}, id="qwen2.5-72b-over1"),
    pytest.param(QWEN2, "qwen2.5-72b",
                 {"n_layers": 4, "n_heads": 8, "n_kv_heads": 1,
                  "vocab_size": 19008}, id="qwen2.5-72b-over2"),
    # FLOPs only: 2 of 4 experts a token, which the test cell avoids
    pytest.param(TOYMOE, "granite-moe-3b-a800m",
                 dict(tinycell.TOY_MOE_MODEL,
                      moe=dict(tinycell.TOY_MOE_MODEL["moe"], top_k=2)),
                 id="toymoe-top2of4"),
])
def test_matmul_weights_tie_to_param_count(block, arch, over):
    c, m = _sizes(block, arch, **over)
    d, L = c.d_model, c.n_layers
    # param_count counts the two norms of each block, and neither the
    # final norm nor the QKV biases; a tied table is the head, a matmul,
    # and an untied one is only looked up; active_param_count leaves out
    # the experts a token is not sent to
    norms = 2 * L * d
    table = 0 if c.tie_embeddings else c.vocab_size * d
    assert block.matmul_weights(m) == c.active_param_count() - norms - table


def test_config_file_sizes_match_the_program_config():
    cfg = json.loads((REPO / "bench/configs/qwen2-0.5b.json").read_text())
    block = common.block(cfg, REPO)
    c, m = _sizes(block, cfg["arch"], **cfg["model"])
    assert block.matmul_weights(cfg["model"]) == block.matmul_weights(m)


@pytest.mark.parametrize("n", [1, 7, 212])
def test_attention_term_is_causal(n):
    _, m = _sizes(QWEN2, "qwen2-0.5b")
    per_pos = sum(4 * t * m["n_heads"] * m["head_dim"] * m["n_layers"]
                  for t in range(1, n + 1))
    assert QWEN2.attention_flops(m, n) == per_pos
    fwd = flops.forward_flops(QWEN2, m, [n])
    assert fwd == 2 * QWEN2.matmul_weights(m) * n + per_pos


def test_step_is_five_forwards():
    _, m = _sizes(QWEN2, "qwen2-0.5b")
    lens = [212, 159, 106, 53]
    s = flops.step_flops(QWEN2, m, lens)
    fwd = flops.forward_flops(QWEN2, m, lens)
    assert s["rollout"] == s["reference"] == fwd
    assert s["update"] == 3 * fwd and s["total"] == 5 * fwd
