"""Jit'd wrapper: hot gathers from the Pallas kernel, cold tail from XLA."""
from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp

from .gather_embed import hot_gather_pallas

__all__ = ["split_gather"]


def _pad_to(x: jnp.ndarray, multiple: int) -> jnp.ndarray:
    rem = (-x.shape[0]) % multiple
    if rem == 0:
        return x
    return jnp.concatenate([x, jnp.zeros((rem,), x.dtype)])


@partial(jax.jit, static_argnames=("token_tile", "interpret"))
def split_gather(
    hot: jnp.ndarray,
    cold: jnp.ndarray,
    ids: jnp.ndarray,
    *,
    token_tile: int = 256,
    interpret: Optional[bool] = None,
) -> jnp.ndarray:
    """Gather from the logical table concat([hot, cold]) with the hot path
    served by the VMEM-resident Pallas kernel."""
    t = ids.shape[0]
    h = hot.shape[0]
    ids_p = _pad_to(ids.astype(jnp.int32), token_tile)
    hot_rows = hot_gather_pallas(ids_p, hot, token_tile=token_tile,
                                 interpret=interpret)[:t]
    is_cold = ids >= h
    cold_rows = cold[jnp.where(is_cold, ids - h, 0)]
    return jnp.where(is_cold[:, None], cold_rows, hot_rows)
