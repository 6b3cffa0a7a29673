"""The float32 reference against the program, on seeded weights at the
test size, for each block: the program's prefill logits, and the
Update's loss."""
import sys
from dataclasses import replace
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(REPO))
sys.path.insert(0, str(Path(__file__).resolve().parent))

from bench import common, reference, weights  # noqa: E402
import tinycell  # noqa: E402
from repro.configs import get_config  # noqa: E402
from repro.core.train_step import make_rl_train_step  # noqa: E402
from repro.models.registry import build_model  # noqa: E402
from repro.optim.adamw import adamw  # noqa: E402
from repro.rl.experience import ExperienceBatch  # noqa: E402

ALGO = {"kl_coef": 0.05, "clip_eps": 0.2, "lr": 3e-4, "b1": 0.9,
        "b2": 0.95, "eps": 1e-8, "weight_decay": 0.0, "max_grad_norm": 1.0}


QWEN2 = common.load_module(REPO / "bench/blocks/qwen2.py", "block_qwen2")
TOYMOE = common.load_module(tinycell.HERE / "blocks/toymoe.py",
                            "block_toymoe")
# case -> (block, the program's architecture, sizes)
CASES = {
    "tied": (QWEN2, "qwen2-0.5b", tinycell.TINY_MODEL),
    "untied": (QWEN2, "qwen2-0.5b",
               dict(tinycell.TINY_MODEL, tie_embeddings=False)),
    "toymoe": (TOYMOE, "granite-moe-3b-a800m", tinycell.TOY_MOE_MODEL),
}


def _setup(case, seed=3):
    block, arch, m = CASES[case]
    model = build_model(replace(get_config(arch),
                                **block.program_config(m)))
    w = weights.make_weights(block, m, weights.seed_key(seed))
    return block, m, model, w


def _tokens(B, T, V, seed):
    return np.asarray(jax.random.randint(jax.random.PRNGKey(seed), (B, T),
                                         0, V), np.int32)


@pytest.mark.parametrize("case", list(CASES))
def test_reference_matches_prefill_logits(case):
    block, m, model, w = _setup(case)
    toks = _tokens(3, 40, m["vocab_size"], 1)
    cache = model.init_cache(3, 64)
    got, _ = jax.jit(model.prefill)(weights.to_program(block, w),
                                    jnp.asarray(toks), cache)
    want = reference.logits(block, w, toks, m)[:, -1]
    err = float(jnp.max(jnp.abs(got.astype(jnp.float32) - want)))
    scale = float(jnp.max(jnp.abs(want)))
    # the program multiplies in bfloat16: about 3 significant digits
    assert err <= 2e-2 * scale, (err, scale)
    # a wrong head, norm or rotary convention misses by the logits' size
    wrong = reference.logits(block,
                             dict(w, final_norm=w["final_norm"] * 0 + 1),
                             toks, m)[:, -1]
    assert float(jnp.max(jnp.abs(got.astype(jnp.float32) - wrong))) > err


@pytest.mark.parametrize("case", ["tied", "toymoe"])
def test_reference_loss_matches_update_loss(case):
    block, m, model, w = _setup(case)
    B, T, V = 4, 48, m["vocab_size"]
    toks = _tokens(B, T, V, 2)
    gen = np.zeros((B, T), bool)
    for i in range(B):                    # rows of 6, 10, 14, 18 tokens
        gen[i, 20:26 + 4 * i] = True
    rewards = np.array([1.0, -1.0, 0.0, 1.0], np.float32)
    ref = reference.Reference(block, m, ALGO, rows_per_block=3)
    lp0 = ref.logprobs(w, toks)
    adv = np.asarray(reference.advantages(rewards))
    want, _, lp = ref.grad(w, toks, gen, adv, lp0)
    np.testing.assert_allclose(lp, lp0, rtol=0, atol=1e-5)

    pad = np.zeros((B, 1), np.float32)
    batch = ExperienceBatch(
        tokens=jnp.asarray(toks), gen_mask=jnp.asarray(gen),
        loss_mask=jnp.asarray(gen),
        logprobs=jnp.asarray(np.concatenate([pad, lp0], 1)),
        ref_logprobs=jnp.asarray(np.concatenate([pad, lp0], 1)),
        rewards=jnp.asarray(rewards), returns=jnp.asarray(rewards),
        advantages=jnp.asarray(adv),
        context_len=jnp.full((B,), T, jnp.int32),
        truncated=jnp.zeros((B,), bool))
    opt = adamw(ALGO["lr"], weight_decay=0.0)
    params = weights.to_program(block, w)
    step = jax.jit(make_rl_train_step(model, opt, clip_eps=ALGO["clip_eps"],
                                      kl_coef=ALGO["kl_coef"]))
    _, _, metrics = step(params, opt.init(params), batch)
    got = float(metrics["loss"])
    # at ratio 1 the surrogate is minus the advantage per generated token;
    # the program's ratio and KL come from bfloat16 log-probs
    assert abs(got - want) <= 2e-2 * max(1.0, abs(want)), (got, want)
    assert abs(want) > 0.01
