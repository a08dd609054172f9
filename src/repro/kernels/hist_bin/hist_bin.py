"""Pallas TPU kernel: DBG degree binning + histogram (Listing 1, steps 1-2).

Grid over vertex tiles.  Each tile:
  * compares its (TILE,) degree block against the (K,) boundary vector in VREGs
    (K <= 32 — the paper's DBG uses 8 groups, so the compare broadcast is a
    handful of vector ops, no gather);
  * writes the per-vertex group id;
  * accumulates a per-group count into an output accumulator block that maps
    every grid step to the SAME block (index_map -> 0), initialized on the
    first step — the canonical Pallas TPU cross-step accumulation pattern.

VMEM footprint per step: TILE*4 (degrees) + TILE*4 (groups) + K*4 * 2 ≈ 8*TILE
bytes — TILE=4096 keeps it ~32 KiB, far under the ~16 MiB VMEM budget; the
tile is lane-aligned (multiple of 128).
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ..mode import interpret_mode

__all__ = ["hist_bin_pallas"]


def _kernel(deg_ref, bounds_ref, groups_ref, hist_ref):
    pid = pl.program_id(0)

    deg = deg_ref[...]  # (TILE,)
    bounds = bounds_ref[...]  # (K,)
    # group = first k with deg >= bounds[k]  (bounds descending, last == 0)
    ge = deg[:, None] >= bounds[None, :]  # (TILE, K)
    groups = jnp.argmax(ge, axis=1).astype(jnp.int32)
    groups_ref[...] = groups

    # histogram for this tile: one-hot reduce (TILE, K) -> (K,)
    k = bounds.shape[0]
    onehot = (groups[:, None] == jnp.arange(k, dtype=jnp.int32)[None, :]).astype(
        jnp.int32
    )
    tile_hist = jnp.sum(onehot, axis=0)

    @pl.when(pid == 0)
    def _init():
        hist_ref[...] = jnp.zeros_like(hist_ref)

    hist_ref[...] += tile_hist


def hist_bin_pallas(
    degrees: jnp.ndarray,
    boundaries: jnp.ndarray,
    *,
    tile: int = 4096,
    interpret: Optional[bool] = None,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Returns (groups (V,), histogram (K,)). V must be a multiple of ``tile``
    (ops.py pads)."""
    v = degrees.shape[0]
    k = boundaries.shape[0]
    assert v % tile == 0, (v, tile)
    grid = (v // tile,)
    return pl.pallas_call(
        _kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((tile,), lambda i: (i,)),  # degrees: one tile per step
            pl.BlockSpec((k,), lambda i: (0,)),  # boundaries: broadcast
        ],
        out_specs=[
            pl.BlockSpec((tile,), lambda i: (i,)),  # groups
            pl.BlockSpec((k,), lambda i: (0,)),  # histogram accumulator
        ],
        out_shape=[
            jax.ShapeDtypeStruct((v,), jnp.int32),
            jax.ShapeDtypeStruct((k,), jnp.int32),
        ],
        interpret=interpret_mode(interpret),
    )(degrees, boundaries)
