"""The qwen2 cell reads what it read before its block had a file of its
own: values recorded at the test size (``tinycell.TINY_MODEL``, tied and
untied) from the benchmark as it stood then, in ``qwen2.pin.json``: each
seeded leaf's sum and sum of squares, the reference's token log-probs on
fixed tokens, and the FLOPs of a step at the test size and of the
configuration file's sizes."""
import json
import sys
from pathlib import Path

import jax
import numpy as np
import pytest

REPO = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(REPO))
sys.path.insert(0, str(Path(__file__).resolve().parent))

from bench import common, flops, reference, weights  # noqa: E402
import tinycell  # noqa: E402

PIN = json.loads((tinycell.HERE / "qwen2.pin.json").read_text())
CFG = json.loads((REPO / "bench/configs/qwen2-0.5b.json").read_text())
QWEN2 = common.block(CFG, REPO)


def _model(case):
    return dict(tinycell.TINY_MODEL, tie_embeddings=case == "tied")


@pytest.mark.parametrize("case", ["tied", "untied"])
def test_weights_are_bit_identical(case):
    w = weights.make_weights(QWEN2, _model(case),
                             weights.seed_key(PIN["seed"]))
    assert sorted(w) == sorted(PIN["cases"][case]["leaves"])
    for n, want in PIN["cases"][case]["leaves"].items():
        x = np.asarray(jax.device_get(w[n])).astype(np.float64)
        assert list(x.shape) == want["shape"], n
        # sums of bfloat16 values in float64: exact for the same bits
        assert float(x.sum()) == want["sum"], n
        assert float(np.square(x).sum()) == want["sumsq"], n


@pytest.mark.parametrize("case", ["tied", "untied"])
def test_reference_logprobs(case):
    m = _model(case)
    w = weights.make_weights(QWEN2, m, weights.seed_key(PIN["seed"]))
    toks = np.asarray(PIN["tokens"], np.int32)
    got = np.asarray(reference.token_logprobs(QWEN2, w, toks, m))
    np.testing.assert_allclose(got, PIN["cases"][case]["logprobs"],
                               rtol=0, atol=1e-6)


@pytest.mark.parametrize("size", ["tiny", "qwen2-0.5b"])
def test_step_flops(size):
    m = tinycell.TINY_MODEL if size == "tiny" else CFG["model"]
    assert flops.step_flops(QWEN2, m, PIN["lengths"]) == \
        PIN["step_flops"][size]
