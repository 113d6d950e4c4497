"""Production mesh construction.

Single pod: (data=16, model=16) = 256 chips (TPU v5e pod slice).
Multi-pod:  (pod=2, data=16, model=16) = 512 chips; the ``pod`` axis is
pure data-parallel (DCN), ``data``/``model`` stay intra-pod (ICI).

Defined as functions (never module-level constants) so importing this
module never touches jax device state — only the dry-run sets
``xla_force_host_platform_device_count``.
"""
from __future__ import annotations

import jax

# TPU v5e hardware constants (per chip) used by the roofline analysis.
HW = {
    "peak_flops_bf16": 197e12,   # FLOP/s
    "hbm_bw": 819e9,             # B/s
    "ici_bw": 50e9,              # B/s per link
    "hbm_bytes": 16 << 30,
}


def make_mesh(shape, axes):
    """``jax.make_mesh`` with every axis ``Auto``: the sharding rules
    annotate inputs and leave propagation to GSPMD."""
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_host_mesh():
    """1x1 mesh for CPU smoke runs (everything replicated)."""
    return make_mesh((1, 1), ("data", "model"))


def make_expert_mesh():
    """1-D mesh over an ``expert`` axis spanning all visible devices.

    Used by ``serve.placement``: banked expert engines shard their
    stacked params/caches along this axis so co-located experts run on
    their own devices under one dispatch. On a laptop/CI box drive it
    with a forced host device count (set *before* jax initialises its
    backend, e.g. ``XLA_FLAGS=--xla_force_host_platform_device_count=8``
    with ``JAX_PLATFORMS=cpu``); on a TPU slice the real chips show up
    here instead.
    """
    return make_mesh((len(jax.devices()),), ("expert",))


def mesh_devices_required(multi_pod: bool) -> int:
    return 512 if multi_pod else 256
