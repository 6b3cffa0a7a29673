"""Model FLOPs that one RL step requires, from the configuration's sizes.

A forward pass over a sequence of ``n`` live tokens needs, per token,
two FLOPs for each weight of a matmul (the Q, K, V and output projections,
the three SwiGLU matrices, and the head over the whole vocabulary), plus
the causal attention term: at position ``t`` (1-based) the scores and the
weighted sum take ``4 * t * n_heads * head_dim`` FLOPs per layer. A
backward pass takes twice the forward. Padding, recomputation (remat),
norms, biases and the softmax are not counted.

One step requires the Rollout's forward of every live token once, the
reference model's forward once, and the Update's forward and backward.
"""
from __future__ import annotations


def matmul_weights(m: dict) -> int:
    """Weights that each token multiplies in one forward pass."""
    d, H, KV, hd, f = (m["d_model"], m["n_heads"], m["n_kv_heads"],
                       m["head_dim"], m["d_ff"])
    per_layer = d * H * hd + 2 * d * KV * hd + H * hd * d + 3 * d * f
    return m["n_layers"] * per_layer + d * m["vocab_size"]


def attention_flops(m: dict, n: int) -> float:
    """Causal attention over one sequence of ``n`` tokens."""
    return 2.0 * m["n_layers"] * m["n_heads"] * m["head_dim"] * n * (n + 1)


def forward_flops(m: dict, lengths) -> float:
    w = matmul_weights(m)
    return float(sum(2.0 * w * n + attention_flops(m, n)
                     for n in map(int, lengths)))


def step_flops(m: dict, lengths) -> dict:
    """Per stage, for a batch whose live lengths are ``lengths``."""
    fwd = forward_flops(m, lengths)
    return {"rollout": fwd, "reference": fwd, "update": 3.0 * fwd,
            "total": 5.0 * fwd}
