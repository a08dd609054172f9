"""Pallas TPU kernel: degree-binned (DBG-grouped) CSR SpMV — integration K1.

TPU adaptation of the paper's pull-mode edge map (DESIGN.md §2).  Irregular
CSR traversal maps poorly onto dense tiles; but after DBG reordering, rows of
one group have degree within a single geometric range [B, 2B), so padding each
group's rows to the group's width wastes < 50% of lanes *by construction* —
the paper's binning doubles as the TPU occupancy structure.

Layout per group: ELL pack ``idx``(R, W) int32 + ``w``(R, W) f32 (padding w=0).
Grid: (row_tiles, width_tiles).  Blocks:
  * x: the full property vector, VMEM-resident across all steps (the "cache");
    hot-first DBG ordering means x's first blocks serve most gathers — on real
    hardware this is what keeps the working set in VMEM.
  * idx/w: (TR, TW) VMEM tiles; y: (TR,) accumulator, revisited across width
    tiles (index_map ignores the width coordinate; init on first width step).

VMEM per step (TR=256, TW=512): idx+w tiles 2*256*512*4 = 1 MiB, x = V*4
(<= 2 MiB for V<=512k), y 1 KiB — comfortably inside the ~16 MiB budget, lane
dims multiples of 128.

The in-kernel gather ``x[idx_tile]`` is a VMEM vector gather (Mosaic
DynamicGather on v4+); validated in interpret mode on CPU.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ..mode import interpret_mode

__all__ = ["ell_spmv_pallas"]


def _kernel(x_ref, idx_ref, w_ref, y_ref):
    wi = pl.program_id(1)

    @pl.when(wi == 0)
    def _init():
        y_ref[...] = jnp.zeros_like(y_ref)

    x = x_ref[...]  # (V,) property vector, VMEM-resident
    idx = idx_ref[...]  # (TR, TW)
    w = w_ref[...]  # (TR, TW)
    gathered = x[idx]  # vector gather from VMEM
    y_ref[...] += jnp.sum(gathered * w, axis=1)


def ell_spmv_pallas(
    x: jnp.ndarray,
    idx: jnp.ndarray,
    w: jnp.ndarray,
    *,
    row_tile: int = 256,
    width_tile: int = 512,
    interpret: Optional[bool] = None,
) -> jnp.ndarray:
    """y (R,) = rowsum(x[idx] * w). R % row_tile == 0, W % width_tile == 0
    (ops.py pads)."""
    r, width = idx.shape
    assert r % row_tile == 0 and width % width_tile == 0, (idx.shape, row_tile, width_tile)
    grid = (r // row_tile, width // width_tile)
    return pl.pallas_call(
        _kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((x.shape[0],), lambda i, j: (0,)),  # x: whole vector
            pl.BlockSpec((row_tile, width_tile), lambda i, j: (i, j)),
            pl.BlockSpec((row_tile, width_tile), lambda i, j: (i, j)),
        ],
        out_specs=pl.BlockSpec((row_tile,), lambda i, j: (i,)),  # y: per row tile
        out_shape=jax.ShapeDtypeStruct((r,), x.dtype),
        interpret=interpret_mode(interpret),
    )(x, idx, w)
