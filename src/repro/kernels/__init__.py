"""Pallas TPU kernels for the framework's compute hot-spots.

  expert_score.py     — fused AE-bank routing score (encode→decode→MSE)
  cosine_topk.py      — fine-grained assignment cosine scores
  decode_attention.py — GQA flash-decode vs (ring) KV cache
  wkv_step.py         — fused RWKV6 decode step (state + output, one pass)

Each kernel ships with a pure-jnp oracle in ref.py and a jitted public
wrapper in ops.py. The backend picks the mode (mode.py): the Pallas
interpreter on the CPU, where tests/test_kernels.py checks every kernel
against its oracle, and Mosaic on a TPU; tests/test_tpu_compile.py
compiles the routing kernels for a described v5e chip.
"""
from . import ops, ref

__all__ = ["ops", "ref"]
