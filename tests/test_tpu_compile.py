"""The routing kernels compile for a TPU v5e at their serving widths.

Interpret mode on the CPU checks a kernel's arithmetic, not Mosaic's
lowering rules (block tiling, VMEM budget). These tests hand the TPU
compiler a described, unattached v5e chip and compile each routing
kernel through its public wrapper in Mosaic mode, so a block spec the
chip would refuse fails here.

The topology is described inside a module fixture, never at import:
only one process at a time may load the TPU library, and every test
worker imports this file.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import ops

IN_DIM, HID, N_EXPERTS, N_CLASSES = 784, 128, 6, 10


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a described chip's executables cannot be read back from the
    # persistent cache, so keep these compiles out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _spec(shape, sharding):
    return jax.ShapeDtypeStruct(shape, jnp.float32, sharding=sharding)


def _assert_mosaic(compiled):
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("B", [1, 128, 256])
def test_expert_score_compiles_for_v5e(one_chip, B):
    Dp = 896
    folded = {"w1": _spec((N_EXPERTS, Dp, HID), one_chip),
              "b1": _spec((N_EXPERTS, HID), one_chip),
              "w2": _spec((N_EXPERTS, HID, Dp), one_chip),
              "b2": _spec((N_EXPERTS, Dp), one_chip)}
    x = _spec((B, IN_DIM), one_chip)
    compiled = ops.expert_score_folded.lower(
        folded, x, interpret=False).compile()
    _assert_mosaic(compiled)


@pytest.mark.parametrize("B", [1, 128, 256])
def test_cosine_scores_compiles_for_v5e(one_chip, B):
    compiled = ops.cosine_scores.lower(
        _spec((B, HID), one_chip), _spec((N_CLASSES, HID), one_chip),
        _spec((N_CLASSES,), one_chip), interpret=False).compile()
    _assert_mosaic(compiled)
