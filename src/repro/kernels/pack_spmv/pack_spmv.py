"""Pallas TPU kernel: SpMV over the packed hot segment — kernel family K4.

The ``repro.pack`` hot segment stores each DBG group as a fixed-stride slot
table (rows padded to the group's degree ceiling, cache-line-aligned).  That
regularity is exactly what a TPU wants: the gather ``x[idx]`` is a dense
(TR, TW) VMEM vector gather with *no* per-row indirection, and the padding
mask is computed from the per-row true degree — no stored padding weights, so
the unweighted path reads half the bytes of the ELL kernel in
``csr_spmv`` (idx only, no w plane).

Grid: (row_tiles, width_tiles); y is accumulated across width tiles (the
index map ignores the width coordinate, init on the first width step), the
same revisiting structure as ``csr_spmv.ell_spmv_pallas``.  ``deg`` rides in
as a (TR,) block; the in-kernel mask is ``col_id < deg`` with a broadcasted
iota offset by the width-tile coordinate.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ..mode import interpret_mode

__all__ = ["hot_spmv_pallas"]


def _kernel_unweighted(x_ref, idx_ref, deg_ref, y_ref):
    wi = pl.program_id(1)

    @pl.when(wi == 0)
    def _init():
        y_ref[...] = jnp.zeros_like(y_ref)

    x = x_ref[...]
    idx = idx_ref[...].astype(jnp.int32)
    tr, tw = idx.shape
    cols = jax.lax.broadcasted_iota(jnp.int32, (tr, tw), 1) + wi * tw
    mask = cols < deg_ref[...][:, None]
    gathered = x[idx]  # regular fixed-stride VMEM gather
    y_ref[...] += jnp.sum(jnp.where(mask, gathered, 0.0), axis=1)


def _kernel_weighted(x_ref, idx_ref, deg_ref, w_ref, y_ref):
    wi = pl.program_id(1)

    @pl.when(wi == 0)
    def _init():
        y_ref[...] = jnp.zeros_like(y_ref)

    x = x_ref[...]
    idx = idx_ref[...].astype(jnp.int32)
    tr, tw = idx.shape
    cols = jax.lax.broadcasted_iota(jnp.int32, (tr, tw), 1) + wi * tw
    mask = cols < deg_ref[...][:, None]
    gathered = x[idx] * w_ref[...]
    y_ref[...] += jnp.sum(jnp.where(mask, gathered, 0.0), axis=1)


def hot_spmv_pallas(
    x: jnp.ndarray,
    idx: jnp.ndarray,
    deg: jnp.ndarray,
    w: jnp.ndarray | None = None,
    *,
    row_tile: int = 64,
    width_tile: int = 128,
    interpret: Optional[bool] = None,
) -> jnp.ndarray:
    """y (R,) = rowsum over valid slots of x[idx] (* w).

    ``idx`` (R, W) may be any integer dtype (the packed storage uses the
    minimal width); padding slots are masked by ``deg``, so their contents
    are irrelevant.  R % row_tile == 0 and W % width_tile == 0 (ops.py pads).
    """
    r, width = idx.shape
    assert r % row_tile == 0 and width % width_tile == 0, (
        idx.shape, row_tile, width_tile)
    grid = (r // row_tile, width // width_tile)
    x_spec = pl.BlockSpec((x.shape[0],), lambda i, j: (0,))
    tile_spec = pl.BlockSpec((row_tile, width_tile), lambda i, j: (i, j))
    row_spec = pl.BlockSpec((row_tile,), lambda i, j: (i,))
    if w is None:
        return pl.pallas_call(
            _kernel_unweighted,
            grid=grid,
            in_specs=[x_spec, tile_spec, row_spec],
            out_specs=row_spec,
            out_shape=jax.ShapeDtypeStruct((r,), x.dtype),
            interpret=interpret_mode(interpret),
        )(x, idx, deg)
    return pl.pallas_call(
        _kernel_weighted,
        grid=grid,
        in_specs=[x_spec, tile_spec, row_spec, tile_spec],
        out_specs=row_spec,
        out_shape=jax.ShapeDtypeStruct((r,), x.dtype),
        interpret=interpret_mode(interpret),
    )(x, idx, deg, w)
