"""Jit'd wrapper: full pull-mode SpMV over a ``PackedAdjacency``.

``pack_spmv`` is the end-to-end decode-free edge map of the packed layout:
one ``hot_spmv_pallas`` launch per hot group (fixed-stride slots, degree-
masked — no stored padding weights on the unweighted path), and a
**decoded-tile** path for the cold segment: each varint block is decoded
independently (``codec.decode_block`` — exercising the per-block metadata),
the tiles are concatenated and reduced with one sorted segment-sum.

Validated against ``kernels.csr_spmv.ref.csr_spmv_ref`` over the unpacked
graph (tests), like every kernel family in this package.
"""
from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from ...pack import codec
from ...pack.layout import PackedAdjacency
from .pack_spmv import hot_spmv_pallas

__all__ = ["pack_spmv", "decode_cold_tiles"]


def _round_up(n: int, m: int) -> int:
    return ((n + m - 1) // m) * m


@partial(jax.jit, static_argnames=("row_tile", "width_tile", "interpret"))
def _hot_group(x, idx, deg, w, *, row_tile, width_tile, interpret):
    return hot_spmv_pallas(x, idx, deg, w, row_tile=row_tile,
                           width_tile=width_tile, interpret=interpret)


def decode_cold_tiles(adj: PackedAdjacency):
    """Decode the cold segment block-by-block into one edge-parallel tile.

    Returns ``(seg, neigh, w)``: local cold-row index, neighbor id and weight
    per cold edge, row-major.  Each block decodes independently from its own
    (ctrl, data) slice — the on-the-fly path the engine adapter caches.
    """
    lists = adj.cold.lists
    cdeg = adj.cold.deg.astype(np.int64)
    rpb = lists.rows_per_block
    neigh_parts = []
    for b in range(lists.num_blocks):
        vals, first_row = codec.decode_block(lists, b)
        counts = cdeg[first_row:first_row + rpb]
        neigh_parts.append(codec.delta_decode_values(vals, counts))
    neigh = (np.concatenate(neigh_parts) if neigh_parts
             else np.zeros(0, np.int64))
    seg = np.repeat(np.arange(adj.cold.num_rows, dtype=np.int32), cdeg)
    return seg, neigh.astype(np.int32), adj.cold.w


def pack_spmv(
    x: jnp.ndarray,
    adj: PackedAdjacency,
    *,
    row_tile: int = 64,
    width_tile: int = 128,
    interpret: Optional[bool] = None,
) -> jnp.ndarray:
    """y (V,) = pull-mode SpMV over the packed pull adjacency.

    Unweighted adjacencies multiply by an implicit 1 (the hot path then
    reads only the idx plane — the packed layout's bandwidth win).
    """
    v = adj.num_vertices
    y = jnp.zeros((v,), x.dtype)
    for h in adj.hot:
        if h.num_rows == 0 or h.stride == 0:
            continue
        r_pad = _round_up(h.num_rows, row_tile)
        w_pad = _round_up(h.stride, width_tile)
        idx = np.zeros((r_pad, w_pad), h.idx.dtype)
        idx[: h.num_rows, : h.stride] = h.idx
        deg = np.zeros(r_pad, np.int32)
        deg[: h.num_rows] = h.deg
        wgt = None
        if h.w is not None:
            wgt = np.zeros((r_pad, w_pad), np.float32)
            wgt[: h.num_rows, : h.stride] = h.w
            wgt = jnp.asarray(wgt)
        ys = _hot_group(x, jnp.asarray(idx), jnp.asarray(deg), wgt,
                        row_tile=row_tile, width_tile=width_tile,
                        interpret=interpret)
        y = y.at[jnp.asarray(h.rows)].add(ys[: h.num_rows])

    seg, neigh, w = decode_cold_tiles(adj)
    if neigh.shape[0]:
        vals = x[jnp.asarray(neigh)]
        if w is not None:
            vals = vals * jnp.asarray(w)
        ys = jax.ops.segment_sum(vals, jnp.asarray(seg),
                                 num_segments=adj.cold.num_rows,
                                 indices_are_sorted=True)
        y = y.at[jnp.asarray(adj.cold.rows)].add(ys)
    return y
