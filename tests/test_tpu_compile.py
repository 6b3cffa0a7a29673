"""Each main-path Pallas kernel compiles for a described TPU v5e at the
widths of the model it serves (qwen2-0.5b attention and sampler,
mamba2-370m SSD scan).

No chip is needed: the TPU compiler compiles for a ``v5e:2x2`` topology
that is described, not attached. The compile refuses what interpret mode
accepts — block shapes off the (8, 128) tiling, kernels that overrun the
core's VMEM — so these tests guard the kernels' TPU lowering. The
topology is described inside a fixture, never at import, so that only the
pytest worker that runs this file loads the TPU library.
"""
import os

import jax
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs.base import get_config
from repro.kernels.cases import kernel_cases

CASES = {c.name: c for c in kernel_cases(get_config("qwen2-0.5b"),
                                         get_config("mamba2-370m"))}


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_compile_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without one; keep it out of the cache."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


@pytest.mark.parametrize("name", sorted(CASES))
def test_kernel_compiles_for_v5e(name, one_chip, no_compile_cache):
    case = CASES[name]
    shapes = jax.eval_shape(case.args, jax.random.PRNGKey(0))
    shapes = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one_chip),
        shapes)
    compiled = jax.jit(
        lambda *a: case.kernel(*a, interpret=False)).lower(*shapes).compile()
    assert "tpu_custom_call" in compiled.as_text(), name
