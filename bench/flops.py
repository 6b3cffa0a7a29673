"""Model FLOPs that one RL step requires, from the configuration's sizes.

A forward pass over a sequence of ``n`` live tokens needs, per token, two
FLOPs for each weight that the token multiplies (the block's
``matmul_weights``: active weights only, the head over the whole
vocabulary included), plus the block's ``attention_flops(m, n)``. A
backward pass takes twice the forward. Padding, recomputation (remat),
norms, biases and the softmax are not counted.

One step requires the Rollout's forward of every live token once, the
reference model's forward once, and the Update's forward and backward.
"""
from __future__ import annotations


def forward_flops(block, m: dict, lengths) -> float:
    w = block.matmul_weights(m)
    return float(sum(2.0 * w * n + block.attention_flops(m, n)
                     for n in map(int, lengths)))


def step_flops(block, m: dict, lengths) -> dict:
    """Per stage, for a batch whose live lengths are ``lengths``."""
    fwd = forward_flops(block, m, lengths)
    return {"rollout": fwd, "reference": fwd, "update": 3.0 * fwd,
            "total": 5.0 * fwd}
