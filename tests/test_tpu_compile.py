"""The routing kernels and the paged decode tick compile for a TPU v5e
at their serving widths.

Interpret mode on the CPU checks a kernel's arithmetic, not Mosaic's
lowering rules (block tiling, VMEM budget). These tests hand the TPU
compiler a described, unattached v5e chip and compile each routing
kernel through its public wrapper in Mosaic mode, so a block spec the
chip would refuse fails here. The decode tick is compiled at the KV
geometry of each benchmark cell, where the chip's default layouts, not
the CPU's, decide whether the KV pool is relaid out.

The topology is described inside a module fixture, never at import:
only one process at a time may load the TPU library, and every test
worker imports this file.
"""
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import ops
from repro.models import build_model
from repro.models.common import ArchConfig

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


# (experts, layers, query heads, kv heads, head dim, pool pages, max_len)
# of the smollm bank and the qwen pair; the widths that only size the
# weights are cut, since they do not touch the KV pool
_KV_GEOMETRY = {"smollm-135m.bank6": (6, 30, 9, 3, 64, 640, 640),
                "qwen2.5-14b.d4x2": (1, 4, 40, 8, 128, 3072, 768)}


@pytest.mark.parametrize("name", sorted(_KV_GEOMETRY))
def test_paged_decode_keeps_pool_in_place_on_v5e(one_chip, name):
    """The compiled decode tick takes and returns the pool in its
    row-major default layout, and no operation in it yields the pool,
    one layer's plane of it, or a relaid-out copy: only the loop
    carrying it and its in-place slot writes touch that shape."""
    E, L, H, KV, dh, pages, max_len = _KV_GEOMETRY[name]
    page, B = 8, 4
    model = build_model(ArchConfig(
        name=name, family="dense", n_layers=L, d_model=128, n_heads=H,
        n_kv_heads=KV, head_dim=dh, d_ff=256, vocab_size=512,
        param_dtype="bfloat16", compute_dtype="bfloat16"))

    def spec(x, lead=(E,)):
        return jax.ShapeDtypeStruct(lead + tuple(x.shape), x.dtype,
                                    sharding=one_chip)

    params = jax.tree_util.tree_map(
        spec, jax.eval_shape(model.init, jax.random.PRNGKey(0)))
    pool = jax.tree_util.tree_map(
        spec, jax.eval_shape(lambda: model.init_paged_pool(pages, page)))
    i32 = jax.ShapeDtypeStruct((), jnp.int32)
    tick = jax.jit(jax.vmap(
        lambda p, pool, tbl, pos, t, b: model.paged_decode(
            p, pool, tbl, pos, t, b, page=page)), donate_argnums=(1,))
    hlo = tick.lower(params, pool, spec(i32, (E, B, max_len // page)),
                     spec(i32, (E, max_len)), spec(i32, (E,)),
                     {"token": spec(i32, (E, B, 1))}).compile().as_text()
    dims = pool["k"].shape
    planes = (dims, dims[:1] + dims[2:])         # the pool, one layer
    shapes = {",".join(str(n) for n in d if n > 1 or not squeeze)
              for d in planes for squeeze in (False, True)}
    layout = re.search(r"entry_computation_layout=\{\((.*?)\)->", hlo)[1]
    assert layout.count(f"bf16[{','.join(map(str, dims))}]{{3,2,1,0:") == 2
    makers = set()
    for line in hlo.splitlines():
        m = re.search(r"= bf16\[([\d,]+)\]\{[^}]*\} ([\w-]+)\(", line)
        if m and m[1] in shapes:
            # a fusion may only be the slot write, scattering in place
            scatter = m[2] == "fusion" and '/scatter"' in line
            makers.add("scatter" if scatter else m[2])
    assert makers <= {"parameter", "get-tuple-element", "tuple", "while",
                      "bitcast", "scatter", "dynamic-update-slice"}, makers
