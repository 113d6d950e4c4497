"""Where a Pallas kernel runs: the one place that decides it.

Every kernel entry point takes ``interpret: Optional[bool] = None``.
``None`` means "decided by the backend": the Pallas interpreter on the
CPU (tests), Mosaic on a TPU. Any other backend has no kernel path and
is an error, never a silent fallback to the interpreter.
"""
from __future__ import annotations

from typing import Optional

import jax


def resolve_interpret(interpret: Optional[bool]) -> bool:
    """``interpret`` if given, else the backend's mode (see module doc)."""
    if interpret is not None:
        return bool(interpret)
    backend = jax.default_backend()
    if backend == "cpu":
        return True
    if backend == "tpu":
        return False
    raise RuntimeError(
        f"no Pallas kernel path for backend {backend!r}: kernels run "
        "interpreted on 'cpu' and through Mosaic on 'tpu'")
