"""Pallas TPU kernel: skew-aware (hot/cold split) embedding gather — K2.

After DBG vocabulary reordering (repro.core.vocab), the first H rows of the
embedding table are the hot set — small enough to pin in VMEM (the paper's
"hot vertices fit in the fast level").  The kernel serves the hot gathers from
the VMEM-resident panel; cold ids (the long tail, low reuse) are masked out
and served by the caller from HBM (ops.py) — exactly the hot/cold traffic
split of the paper, with VMEM as the cache.

Grid over token tiles; per step:
  * hot panel (H, D) VMEM-resident across all steps (index_map → (0, 0)),
  * ids tile (T,), output tile (T, D) = hot[ids] where hot, else 0.

VMEM: H*D*4 (e.g. 2048x512 f32 = 4 MiB) + T*D*4 (256x512 = 512 KiB) — fits.
D multiple of 128 (lanes), T multiple of 8 (sublanes).
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ..mode import interpret_mode

__all__ = ["hot_gather_pallas"]


def _kernel(ids_ref, hot_ref, out_ref):
    ids = ids_ref[...]  # (T,)
    hot = hot_ref[...]  # (H, D)
    h = hot.shape[0]
    is_hot = ids < h
    safe = jnp.where(is_hot, ids, 0)
    rows = hot[safe]  # (T, D) vector gather from VMEM
    out_ref[...] = jnp.where(is_hot[:, None], rows, jnp.zeros_like(rows))


def hot_gather_pallas(
    ids: jnp.ndarray,
    hot_table: jnp.ndarray,
    *,
    token_tile: int = 256,
    interpret: Optional[bool] = None,
) -> jnp.ndarray:
    """(T,) ids, (H, D) hot table -> (T, D); cold ids produce zero rows."""
    t = ids.shape[0]
    h, d = hot_table.shape
    assert t % token_tile == 0, (t, token_tile)
    grid = (t // token_tile,)
    return pl.pallas_call(
        _kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((token_tile,), lambda i: (i,)),
            pl.BlockSpec((h, d), lambda i: (0, 0)),  # hot panel resident
        ],
        out_specs=pl.BlockSpec((token_tile, d), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((t, d), hot_table.dtype),
        interpret=interpret_mode(interpret),
    )(ids, hot_table)
