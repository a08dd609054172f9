"""``PackedBackend`` — run the apps straight over a ``PackedGraph``.

Since PR 5 the packed storage is an ``apps.engine`` edge-map backend rather
than a parallel engine: the **hot segment**'s fixed-stride slot tables ARE
ELL tiles (rows × stride planes with a true-degree mask — exactly the
geometry ``kernels.edge_map`` consumes), so they feed the fused Pallas
kernels directly, still packed, minimal-width ids and all; the **cold
segment** decodes once into per-degree-group ELL tiles (the decoded-tile
path — the compressed varint bytes stay the storage of record).  One
in-direction tile set serves both primitives (push is the transposed pull
with an ``init``-seeded accumulator), so PR/PRΔ/SSSP/BC/Radii run through
``apps.pagerank`` / ``apps.sssp`` / … unchanged — no packed reimplementation
of any app remains.

Parity contract (tested): min/max reductions (SSSP's relaxation, the BFS
levels inside BC/Radii) are BIT-identical to ``FlatBackend`` on
``pg.unpack()`` — padding slots contribute the reduction's exact identity
element and min/max are exactly associative.  Sum reductions agree to fp
association (~1e-6 relative), the same contract as ``EllBackend``.

BC's backward dependency sweep dispatches through
``apps.engine.out_edge_sum``: here it folds per hot slot table / cold tile
of the OUT direction (a segmented sum in packed traversal order) instead of
materializing an edge-parallel out-edge list.
"""
from __future__ import annotations

import dataclasses
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..apps.engine import FusedEdgeMaps
from ..kernels.edge_map.ops import EllTileGroup, _pad_dim, ell_tiles
from .layout import PackedAdjacency, PackedGraph

__all__ = [
    "HotDev",
    "ColdDev",
    "PackedBackend",
    "packed_backend",
]


class HotDev(NamedTuple):
    """Device view of one hot group's slot table (still packed)."""

    rows: jnp.ndarray  # (R,) int32 owning vertex ids
    deg: jnp.ndarray  # (R,) int32
    idx: jnp.ndarray  # (R, W) int32 (upcast from the storage dtype)
    w: Optional[jnp.ndarray]  # (R, W) f32 or None


class ColdDev(NamedTuple):
    """Decoded cold tiles in edge-parallel form (row-major, sorted rows)."""

    rows: jnp.ndarray  # (C,) int32 owning vertex ids
    owners: jnp.ndarray  # (E,) int32 owning vertex id per edge
    seg: jnp.ndarray  # (E,) int32 local row index per edge (ascending)
    neigh: jnp.ndarray  # (E,) int32 neighbor ids
    w: Optional[jnp.ndarray]  # (E,) f32 or None


def _hot_dev(adj: PackedAdjacency) -> Tuple[HotDev, ...]:
    out = []
    for h in adj.hot:
        if h.num_rows == 0 or h.stride == 0:
            continue
        out.append(HotDev(
            rows=jnp.asarray(h.rows, jnp.int32),
            deg=jnp.asarray(h.deg, jnp.int32),
            idx=jnp.asarray(h.idx.astype(np.int32)),
            w=None if h.w is None else jnp.asarray(h.w)))
    return tuple(out)


def _cold_dev(adj: PackedAdjacency) -> ColdDev:
    cdeg = adj.cold.deg.astype(np.int64)
    neigh = adj.cold.neighbors()
    seg = np.repeat(np.arange(adj.cold.num_rows, dtype=np.int32),
                    cdeg)
    owners = np.repeat(adj.cold.rows.astype(np.int32), cdeg)
    return ColdDev(
        rows=jnp.asarray(adj.cold.rows, jnp.int32),
        owners=jnp.asarray(owners),
        seg=jnp.asarray(seg),
        neigh=jnp.asarray(neigh, jnp.int32),
        w=None if adj.cold.w is None else jnp.asarray(adj.cold.w))


def _hot_tiles(adj: PackedAdjacency, row_tile: int,
               width_tile: int) -> Tuple[EllTileGroup, ...]:
    """Wrap the hot slot tables as fused-kernel tiles WITHOUT re-packing.

    A slot table is already an ELL plane: rows padded to the group stride,
    minimal-width ids, per-row true degree.  Only the tile-granularity zero
    padding is added here; the id plane keeps the storage dtype (uint16 on
    every benchmark graph — half the idx bytes of an int32 plane).
    """
    tiles = []
    for h in adj.hot:
        if h.num_rows == 0 or h.stride == 0:
            continue
        r, s = h.num_rows, h.stride
        r_pad = _pad_dim(r, row_tile)
        w_pad = _pad_dim(s, width_tile)
        idx = np.zeros((r_pad, w_pad), h.idx.dtype)
        idx[:r, :s] = h.idx
        deg = np.zeros(r_pad, np.int32)
        deg[:r] = h.deg
        w = None
        if h.w is not None:
            w = np.zeros((r_pad, w_pad), np.float32)
            w[:r, :s] = h.w
        tiles.append(EllTileGroup(
            rows=jnp.asarray(h.rows.astype(np.int32)),
            idx=jnp.asarray(idx),
            deg=jnp.asarray(deg),
            w=None if w is None else jnp.asarray(w)))
    return tuple(tiles)


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass(frozen=True)
class PackedBackend(FusedEdgeMaps):
    """``apps.engine`` backend over hot/cold packed storage (see module doc)."""

    in_tiles: Tuple  # hot slot tables + decoded cold tiles, pull direction
    out_hot: Tuple[HotDev, ...]
    out_cold: ColdDev
    in_deg: jnp.ndarray  # (V,) int32
    out_deg: jnp.ndarray  # (V,) int32
    row_tile: int = 64
    width_tile: int = 128
    interpret: Optional[bool] = None
    # build-time edge count, kept STATIC (pytree aux) so the observability
    # hook can read it under jax tracing, where array values are abstract
    num_edges: int = 0

    @property
    def num_vertices(self) -> int:
        return int(self.in_deg.shape[0])

    def out_edge_sum(self, edge_val) -> jnp.ndarray:
        """Segment-sum ``edge_val(src, child)`` over OUT-edges grouped by
        source — BC's backward gather, folded per hot table / cold tile."""
        v = self.num_vertices
        out = jnp.zeros((v,), jnp.float32)
        for h in self.out_hot:
            r, width = h.idx.shape
            src = jnp.broadcast_to(h.rows[:, None], (r, width))
            vals = edge_val(src, h.idx)
            cols = jax.lax.broadcasted_iota(jnp.int32, (r, width), 1)
            vals = jnp.where(cols < h.deg[:, None], vals, 0.0)
            seg = jax.lax.broadcasted_iota(jnp.int32, (r, width), 0)
            ys = jax.ops.segment_sum(vals.ravel(), seg.ravel(),
                                     num_segments=r, indices_are_sorted=True)
            out = out.at[h.rows].add(ys)
        c = self.out_cold
        if c.neigh.shape[0]:
            vals = edge_val(c.owners, c.neigh)
            ys = jax.ops.segment_sum(vals, c.seg,
                                     num_segments=c.rows.shape[0],
                                     indices_are_sorted=True)
            out = out.at[c.rows].add(ys)
        return out

    def tree_flatten(self):
        return ((self.in_tiles, self.out_hot, self.out_cold,
                 self.in_deg, self.out_deg),
                (self.row_tile, self.width_tile, self.interpret,
                 self.num_edges))

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children, *aux)


def packed_backend(pg: PackedGraph, *, row_tile: int = 64,
                   width_tile: int = 128,
                   interpret: Optional[bool] = None) -> PackedBackend:
    """Build the ``apps.engine`` backend for a ``PackedGraph``.

    The pull direction becomes the fused-kernel tile set (hot slot tables
    wrapped in place + cold rows decoded once, binned by the layout's own
    boundaries); the push primitive rides the SAME tiles (transposed-pull
    trick), so only BC's backward sweep touches the out direction.
    """
    in_adj = pg.in_adj
    tiles = _hot_tiles(in_adj, row_tile, width_tile)
    tiles += ell_tiles(in_adj.cold_csr(), in_adj.boundaries,
                       row_tile=row_tile, width_tile=width_tile)
    return PackedBackend(
        in_tiles=tiles,
        out_hot=_hot_dev(pg.out_adj),
        out_cold=_cold_dev(pg.out_adj),
        in_deg=jnp.asarray(in_adj.degrees(), jnp.int32),
        out_deg=jnp.asarray(pg.out_adj.degrees(), jnp.int32),
        row_tile=row_tile, width_tile=width_tile, interpret=interpret,
        num_edges=int(in_adj.degrees().sum()))
