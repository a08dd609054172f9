"""Where the Pallas kernels run: compiled by Mosaic on a TPU, interpreted
everywhere else (the CPU test suite).  The one place that decides it."""
from __future__ import annotations

from typing import Optional

import jax

__all__ = ["interpret_mode"]


def interpret_mode(interpret: Optional[bool] = None) -> bool:
    """``interpret`` if given, else ``True`` unless JAX's default backend is
    a TPU.  Every kernel entry point defaults to ``None`` and resolves here,
    so a TPU never silently runs the interpreter."""
    if interpret is None:
        return jax.default_backend() != "tpu"
    return bool(interpret)
