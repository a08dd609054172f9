"""Host-side ELL tile packer + device driver for the fused edge map (K5).

``ell_tiles`` packs ONE adjacency direction into per-DBG-group ELL tiles
(the paper's Table IV column structure, same geometric-bin padding bound as
``csr_spmv.ell_pack_groups``) with a per-row true-degree vector instead of a
stored padding-weight plane, vectorized through ``csr.ragged_offsets``.

``fused_edge_map`` is the device driver: one fused Pallas call per group,
then an O(V) combine of per-group row results back into vertex space.  Rows
are grouped by degree, so within the primary tile set every vertex appears in
exactly one group and the combine is a plain set-scatter; ``extra_tiles``
(the stream delta segment, whose destinations duplicate base rows) combine
with the reduction's scatter-op instead.  Nothing here ever materializes an
O(E) edge-parallel intermediate — that is the whole point.
"""
from __future__ import annotations

from typing import NamedTuple, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ...graph import csr as csr_mod
from .edge_map import REDUCE_IDENTITY, edge_map_tile_bytes, ell_edge_map_pallas

__all__ = [
    "EllTileGroup",
    "ell_tiles",
    "ell_tiles_sharded",
    "coo_tiles",
    "coo_tiles_sharded",
    "refresh_alive",
    "fused_edge_map",
    "fused_edge_map_bytes",
    "width_bins",
]


class EllTileGroup(NamedTuple):
    """Device view of one degree-group's ELL tiles.

    ``rows``  (R,)  int32 owning vertex ids (true, unpadded count); a
                    primary tile set (``ell_tiles``, the packed hot tables)
                    keeps them ascending, which its combine declares
    ``idx``   (R_pad, W_pad) int32 neighbor ids (0 in padding lanes)
    ``deg``   (R_pad,) int32 true degrees (0 for padding rows)
    ``w``     optional (R_pad, W_pad) f32 additive weights
    ``alive`` optional (R_pad, W_pad) int8 tombstone mask (stream base)
    """

    rows: jnp.ndarray
    idx: jnp.ndarray
    deg: jnp.ndarray
    w: Optional[jnp.ndarray] = None
    alive: Optional[jnp.ndarray] = None

    @property
    def num_rows(self) -> int:
        return int(self.rows.shape[0])


def _round_up(n: int, m: int) -> int:
    return ((n + m - 1) // m) * m


def _pad_dim(n: int, tile: int, fine: int = 8) -> int:
    """Adaptive padding: groups smaller than one tile pad to the fine (8-lane)
    granularity and run as a single grid step; larger ones pad to full tiles.
    Without this, a width-3 cold group would pad 42x to a 128-lane tile —
    with it, per-group padding stays bounded by the geometric-bin argument."""
    if n >= tile:
        return _round_up(n, tile)
    return _round_up(max(1, n), fine)


def _tile_of(pad: int, tile: int) -> int:
    """Grid tile size for a padded dim (== tile, or the whole dim if small)."""
    return tile if pad >= tile else pad


def _id_dtype(num_vertices: int):
    """Minimal-width storage for neighbor ids (the pack-subsystem idiom:
    uint16 slots halve the dominant idx-plane bytes at bench scales)."""
    return np.uint16 if num_vertices <= np.iinfo(np.uint16).max else np.int32


def width_bins(boundaries: Sequence[int], max_degree: int) -> Tuple[int, ...]:
    """DBG ``boundaries`` (descending) extended with doubling bins above the
    hottest one, so no row is padded to more than twice its degree.

    DBG's top group is unbounded: on a Graph500 scale-22 graph it spans
    in-degrees 512 to ~100K, and one width class for all of it would pad
    ~25K rows to the widest (gigabytes of idx plane).  The geometric bins
    below it already bound padding at 2x; this continues them upward."""
    out = [int(b) for b in boundaries]
    top = max(1, out[0])
    while top * 2 <= max_degree:
        top *= 2
        out.insert(0, top)
    return tuple(out)


def _slot_coords(degs: np.ndarray):
    """(row_rep, col): the ELL slot of each edge of a group, in row order."""
    row_rep = np.repeat(np.arange(degs.shape[0], dtype=np.int64), degs)
    col = csr_mod.ragged_offsets(np.zeros(degs.shape[0], np.int64), degs)
    return row_rep, col


def _scatter_plane(r_pad: int, w_pad: int, row_rep, col, vals, dtype):
    plane = np.zeros((r_pad, w_pad), dtype)
    plane[row_rep, col] = vals
    return plane


def _fill_planes(adj: csr_mod.CSR, rows: np.ndarray, degs: np.ndarray,
                 r_pad: int, w_pad: int, alive_edges: Optional[np.ndarray]):
    """Vectorized ELL fill for one group; returns (idx, w, alive)."""
    row_rep, col = _slot_coords(degs)
    pos = csr_mod.ragged_offsets(adj.indptr[rows], degs)
    idx = _scatter_plane(r_pad, w_pad, row_rep, col, adj.indices[pos],
                         _id_dtype(adj.num_vertices))
    w = None
    if adj.weights is not None:
        w = _scatter_plane(r_pad, w_pad, row_rep, col, adj.weights[pos],
                           np.float32)
    alive = None
    if alive_edges is not None:
        alive = _scatter_plane(r_pad, w_pad, row_rep, col, alive_edges[pos],
                               np.int8)
    return idx, w, alive


def refresh_alive(
    adj: csr_mod.CSR,
    tiles: Tuple["EllTileGroup", ...],
    alive_edges: Optional[np.ndarray],
) -> Tuple["EllTileGroup", ...]:
    """Rebuild ONLY the alive bitplanes of existing tiles (idx/w untouched).

    This is what makes tombstones cheap on the fused stream path: a deletion
    batch re-scatters one int8 plane per group instead of repacking the base
    (no degree binning, no idx/w fills).  ``alive_edges=None`` drops the
    planes (everything alive again, e.g. after compaction)."""
    out = []
    for t in tiles:
        if alive_edges is None:
            out.append(t._replace(alive=None))
            continue
        rows = np.asarray(t.rows)
        degs = np.asarray(t.deg)[: rows.shape[0]].astype(np.int64)
        row_rep, col = _slot_coords(degs)
        pos = csr_mod.ragged_offsets(adj.indptr[rows], degs)
        plane = _scatter_plane(t.idx.shape[0], t.idx.shape[1], row_rep, col,
                               alive_edges[pos], np.int8)
        out.append(t._replace(alive=jnp.asarray(plane)))
    return tuple(out)


def ell_tiles(
    adj: csr_mod.CSR,
    boundaries: Sequence[int],
    *,
    row_tile: int = 64,
    width_tile: int = 128,
    alive_edges: Optional[np.ndarray] = None,
) -> Tuple[EllTileGroup, ...]:
    """Pack one CSR direction into per-DBG-group ELL tiles (host, one pass).

    Rows (owning vertices) are binned by THEIR degree into the geometric
    ``boundaries`` ranges, so each group's width is at most ~2x its smallest
    member — the paper's binning doubling as the TPU occupancy structure.
    Zero-degree rows are skipped (they take the reduction identity in the
    combine).  ``alive_edges`` is an optional per-edge bool in storage order
    (the stream base tombstone mask).
    """
    from ...core.reorder import _assign_groups

    deg_all = adj.degrees()
    boundaries = width_bins(boundaries, int(deg_all.max(initial=0)))
    grp = _assign_groups(deg_all, boundaries)
    # bin by DBG group, then MERGE bins that land in the same padded width
    # class: the deg mask already handles intra-group variance, and one tile
    # set per width class means one gather + kernel launch per class instead
    # of one per bin (several cold bins share the fine 8/16-lane widths).
    by_width = {}
    for k in range(len(boundaries)):
        # zero-degree rows really are skipped (they take the reduction
        # identity in the combine) — essential when the CSR covers only a
        # row SUBSET (repro.pack's cold segment): a deg-0 row here may be
        # owned by another tile set, and a set-combine row must not clobber
        # it with the identity.
        rows = np.where((grp == k) & (deg_all > 0))[0]
        if rows.size == 0:
            continue
        degs = deg_all[rows].astype(np.int64)
        wmax = int(degs.max())
        w_pad = _pad_dim(wmax, width_tile)
        by_width.setdefault(w_pad, []).append((rows, degs))
    out = []
    for w_pad, parts in by_width.items():  # insertion order: hottest first
        rows = np.concatenate([p[0] for p in parts])
        degs = np.concatenate([p[1] for p in parts])
        order = np.argsort(rows, kind="stable")  # ascending: sorted combine
        rows, degs = rows[order], degs[order]
        r_pad = _pad_dim(rows.size, row_tile)
        idx, w, alive = _fill_planes(adj, rows, degs, r_pad, w_pad,
                                     alive_edges)
        deg_arr = np.zeros(r_pad, np.int32)
        deg_arr[: rows.size] = degs
        out.append(EllTileGroup(
            rows=jnp.asarray(rows.astype(np.int32)),
            idx=jnp.asarray(idx),
            deg=jnp.asarray(deg_arr),
            w=None if w is None else jnp.asarray(w),
            alive=None if alive is None else jnp.asarray(alive),
        ))
    return tuple(out)


def ell_tiles_sharded(
    shard_edges: Sequence[Tuple[np.ndarray, np.ndarray, Optional[np.ndarray]]],
    *,
    id_upper: int,
    boundaries: Optional[Sequence[int]] = None,
    row_tile: int = 64,
    width_tile: int = 128,
    with_positions: bool = False,
    with_alive: bool = False,
):
    """Pack D per-shard edge lists into ELL groups that STACK across shards.

    ``shard_edges[i] = (rows, cols, w|None)`` is shard *i*'s edge list in host
    numpy (rows = owning row ids in that shard's private row space, cols =
    gather indices < ``id_upper``).  The returned groups are host (numpy)
    planes, which the sharded engine puts shard by shard on their devices
    (``dist.graph.place_shards``).  They carry a leading shard dim on every
    plane — ``rows (D, R_pad)``, ``idx (D, R_pad, W_pad)``,
    ``deg (D, R_pad)``, optional ``w`` — because ``shard_map`` needs one
    static tile geometry per device: rows are binned by their (shard-local)
    degree into the shared geometric ``boundaries``, each bin's padded width
    is taken from its max over ALL shards, same-width bins merge into one
    class (the ``ell_tiles`` idiom), and each class's row dim pads to the max
    shard population.  Padding rows have ``deg == 0`` and ``rows == 0``, so a
    scatter-combine into an identity-initialized accumulator ignores them.

    ``with_positions=True`` additionally returns, per shard, an ``(E_i, 3)``
    int32 array mapping each input edge (input order) to its ``(class, row,
    col)`` tile slot — the patch index ``repro.dist.graph.apply_remap`` uses
    to retarget individual lanes without repacking.  ``with_alive=True``
    attaches an all-ones int8 tombstone plane to every group so a streaming
    layout can later kill individual lanes in place (the sharded counterpart
    of the stream base's ``refresh_alive`` bitplanes).
    """
    from ...core.reorder import _assign_groups, dbg_spec

    d = len(shard_edges)
    per = []  # (urows, degs, starts, cols_sorted, w_sorted, order)
    for rows, cols, w in shard_edges:
        order = np.argsort(rows, kind="stable")
        urows, degs = np.unique(rows[order], return_counts=True)
        starts = np.concatenate([[0], np.cumsum(degs)])
        per.append((urows, degs.astype(np.int64), starts, cols[order],
                    None if w is None else w[order], order))
    pooled = (np.concatenate([p[1] for p in per])
              if any(p[1].size for p in per) else np.zeros(0, np.int64))
    if boundaries is None:
        mean = max(1.0, float(pooled.mean()) if pooled.size else 1.0)
        boundaries = dbg_spec(mean).boundaries
    boundaries = width_bins(boundaries, int(pooled.max(initial=0)))
    nb = len(boundaries)
    shard_bins = [_assign_groups(p[1], boundaries) for p in per]
    bin_wmax = np.zeros(nb, np.int64)
    for (_, degs, *_), grp in zip(per, shard_bins):
        if degs.size:
            np.maximum.at(bin_wmax, grp, degs)
    by_width: dict = {}  # w_pad -> [bin ids], hottest bin first
    for k in range(nb):
        if bin_wmax[k] == 0:
            continue
        by_width.setdefault(_pad_dim(int(bin_wmax[k]), width_tile),
                            []).append(k)

    weighted = any(p[4] is not None for p in per)
    id_dtype = _id_dtype(id_upper)
    groups = []
    positions = [np.full((rows.shape[0], 3), -1, np.int32)
                 for rows, _, _ in shard_edges]
    for ci, (w_pad, bins) in enumerate(by_width.items()):
        sels = [np.concatenate([np.flatnonzero(g == k) for k in bins])
                if g.size else np.zeros(0, np.int64)
                for g in shard_bins]
        r_pad = _pad_dim(max(int(s.size) for s in sels), row_tile)
        idx = np.zeros((d, r_pad, w_pad), id_dtype)
        deg = np.zeros((d, r_pad), np.int32)
        rws = np.zeros((d, r_pad), np.int32)
        wgt = np.zeros((d, r_pad, w_pad), np.float32) if weighted else None
        for i, ((urows, degs, starts, cs, ws, order), sel) in enumerate(
                zip(per, sels)):
            if sel.size == 0:
                continue
            rdeg = degs[sel]
            row_rep, col = _slot_coords(rdeg)
            pos = csr_mod.ragged_offsets(starts[sel], rdeg)
            idx[i][row_rep, col] = cs[pos].astype(id_dtype)
            if wgt is not None and ws is not None:
                wgt[i][row_rep, col] = ws[pos]
            deg[i, : sel.size] = rdeg
            rws[i, : sel.size] = urows[sel].astype(np.int32)
            if with_positions:
                # sorted-edge position p holds input edge order[p]
                inp = order[pos]
                positions[i][inp, 0] = ci
                positions[i][inp, 1] = row_rep
                positions[i][inp, 2] = col
        groups.append(EllTileGroup(
            rows=rws, idx=idx, deg=deg, w=wgt,
            alive=(np.ones((d, r_pad, w_pad), np.int8)
                   if with_alive else None)))
    tiles = tuple(groups)
    if with_positions:
        return tiles, positions
    return tiles


def coo_tiles(
    src: np.ndarray,
    dst: np.ndarray,
    w: Optional[np.ndarray] = None,
    alive: Optional[np.ndarray] = None,
    *,
    row_tile: int = 64,
    width_tile: int = 128,
) -> Tuple[EllTileGroup, ...]:
    """Group a small COO edge list by destination into ONE ELL tile group.

    The stream delta buffer's fused path: destinations become rows (width =
    max multiplicity, padded), so the tiny cold segment rides the same kernel
    as the base tiles instead of paying its own scatter.  Returns () for an
    empty list.
    """
    if src.shape[0] == 0:
        return ()
    order = np.argsort(dst, kind="stable")
    dsts = dst[order]
    rows, degs = np.unique(dsts, return_counts=True)
    w_pad = _pad_dim(int(degs.max()), width_tile)
    r_pad = _pad_dim(rows.shape[0], row_tile)
    row_rep, col = _slot_coords(degs)
    num_vertices = int(max(src.max(initial=0), dsts.max(initial=0))) + 1
    idx = _scatter_plane(r_pad, w_pad, row_rep, col, src[order],
                         _id_dtype(num_vertices))
    wp = None if w is None else _scatter_plane(
        r_pad, w_pad, row_rep, col, w[order], np.float32)
    ap = None if alive is None else _scatter_plane(
        r_pad, w_pad, row_rep, col, alive[order], np.int8)
    deg_arr = np.zeros(r_pad, np.int32)
    deg_arr[: rows.shape[0]] = degs
    return (EllTileGroup(
        rows=jnp.asarray(rows.astype(np.int32)),
        idx=jnp.asarray(idx),
        deg=jnp.asarray(deg_arr),
        w=None if wp is None else jnp.asarray(wp),
        alive=None if ap is None else jnp.asarray(ap),
    ),)


def coo_tiles_sharded(
    shard_edges: Sequence[Tuple[np.ndarray, np.ndarray, Optional[np.ndarray]]],
    *,
    id_upper: int,
    row_cap: int = 0,
    width_cap: int = 0,
    row_tile: int = 64,
    width_tile: int = 128,
) -> Tuple[EllTileGroup, ...]:
    """The delta-segment companion of :func:`ell_tiles_sharded`: D per-shard
    COO delta lists packed into ONE dst-grouped tile group with a leading
    shard dim (host planes, like the base packer's), so the stream delta
    buffer rides ``shard_map`` next to the stacked base tiles.

    ``shard_edges[i] = (rows, cols, w|None)`` is shard *i*'s ALIVE delta
    edges (rows = destination ids in that shard's row space, cols = gather
    indices < ``id_upper``).  Unlike the base packer the geometry here is
    CAPACITY-driven, not content-driven: the row/width dims pad to at least
    ``row_cap`` / ``width_cap`` (callers pass the running maxima back in), so
    the device shapes stay stable while the buffer fills and only grow
    monotonically — recompiles of a cached sharded query stay logarithmic in
    the number of ingest batches instead of per-batch.  Delta destinations
    duplicate base rows, so results fold in through the reduction's
    scatter-op (``fused_edge_map``'s ``extra_tiles`` contract).  Delta rows
    are shallow (multiplicity ~1), so a single width class — the first
    geometric bin the base packer would assign them to — covers the segment.
    """
    d = len(shard_edges)
    per = []
    max_rows = max_width = 0
    for rows, cols, w in shard_edges:
        order = np.argsort(rows, kind="stable")
        urows, degs = np.unique(rows[order], return_counts=True)
        per.append((urows, degs.astype(np.int64), cols[order],
                    None if w is None else w[order]))
        max_rows = max(max_rows, int(urows.size))
        max_width = max(max_width, int(degs.max()) if degs.size else 0)
    r_pad = _pad_dim(max(1, max_rows, row_cap), row_tile)
    w_pad = _pad_dim(max(1, max_width, width_cap), width_tile)
    weighted = any(p[3] is not None for p in per)
    id_dtype = _id_dtype(id_upper)
    idx = np.zeros((d, r_pad, w_pad), id_dtype)
    deg = np.zeros((d, r_pad), np.int32)
    rws = np.zeros((d, r_pad), np.int32)
    wgt = np.zeros((d, r_pad, w_pad), np.float32) if weighted else None
    for i, (urows, degs, cs, ws) in enumerate(per):
        if urows.size == 0:
            continue
        row_rep, col = _slot_coords(degs)
        idx[i][row_rep, col] = cs.astype(id_dtype)
        if wgt is not None and ws is not None:
            wgt[i][row_rep, col] = ws
        deg[i, : urows.size] = degs
        rws[i, : urows.size] = urows.astype(np.int32)
    return (EllTileGroup(rows=rws, idx=idx, deg=deg, w=wgt),)


def _scatter_combine(out: jnp.ndarray, rows: jnp.ndarray, vals: jnp.ndarray,
                     reduce: str) -> jnp.ndarray:
    if reduce == "sum":
        return out.at[rows].add(vals)
    if reduce == "min":
        return out.at[rows].min(vals)
    return out.at[rows].max(vals)


def fused_edge_map(
    tiles: Tuple[EllTileGroup, ...],
    x: jnp.ndarray,
    num_vertices: int,
    *,
    reduce: str = "sum",
    src_frontier: Optional[jnp.ndarray] = None,
    use_weights: bool = False,
    neutral: float = 0.0,
    init: Optional[jnp.ndarray] = None,
    identity: Optional[float] = None,
    extra_tiles: Tuple[EllTileGroup, ...] = (),
    row_tile: int = 64,
    width_tile: int = 128,
    interpret: Optional[bool] = None,
) -> jnp.ndarray:
    """Full fused edge map: per-group kernels + O(V) combine.

    Pull mode (``init is None``): every vertex lands in exactly one primary
    group; uncovered (zero-degree) vertices take the reduction identity —
    matching the flat engine's empty segments.  Push mode (``init`` given):
    the accumulator is seeded per-row inside the kernel, fusing the separate
    ``init.at[dst].op`` scatter.  ``extra_tiles`` (delta segments whose rows
    duplicate primary rows) fold in with the reduction's scatter-op.

    ``x`` may be a (V, K) plane (K batched queries, one pass over the tiles);
    ``init`` is then (V, K) and ``src_frontier`` either shared (V,) or
    per-query (V, K).
    """
    if identity is None:
        identity = REDUCE_IDENTITY[reduce]
    frontier = None
    if src_frontier is not None:
        frontier = src_frontier.astype(jnp.int8)
    out_shape = (num_vertices,) + tuple(x.shape[1:])
    out = jnp.full(out_shape, identity, x.dtype) if init is None \
        else init.astype(x.dtype)
    # each tile group's pass is named by its padded width (``ell.w<W>``),
    # so a reader ties device time to the DBG width group by name
    for t in tiles:
        r_pad, w_pad = t.idx.shape
        with jax.named_scope(f"ell.w{w_pad}"):
            init_rows = None
            if init is not None:
                init_rows = jnp.full(
                    (r_pad,) + tuple(x.shape[1:]), identity,
                    x.dtype).at[: t.num_rows].set(out[t.rows])
            y = ell_edge_map_pallas(
                x, t.idx, t.deg,
                reduce=reduce,
                w=t.w if use_weights else None,
                unit_weights=use_weights,
                frontier=frontier,
                alive=t.alive,
                init_rows=init_rows,
                neutral=neutral,
                identity=identity,
                row_tile=_tile_of(r_pad, row_tile),
                width_tile=_tile_of(w_pad, width_tile),
                interpret=interpret,
            )
            # rows are ascending and disjoint: a sorted scatter, which the
            # TPU compiler lowers in ~1 s where an unsorted one takes ~25 s
            # at 1M+ rows
            with jax.named_scope("edge_map.combine"):
                out = out.at[t.rows].set(y[: t.num_rows],
                                         indices_are_sorted=True,
                                         unique_indices=True)
    for t in extra_tiles:
        r_pad, w_pad = t.idx.shape
        with jax.named_scope(f"ell.w{w_pad}"):
            y = ell_edge_map_pallas(
                x, t.idx, t.deg,
                reduce=reduce,
                w=t.w if use_weights else None,
                unit_weights=use_weights,
                frontier=frontier,
                alive=t.alive,
                neutral=neutral,
                identity=identity,
                row_tile=_tile_of(r_pad, row_tile),
                width_tile=_tile_of(w_pad, width_tile),
                interpret=interpret,
            )
            with jax.named_scope("edge_map.combine"):
                out = _scatter_combine(out, t.rows, y[: t.num_rows], reduce)
    return out


def fused_edge_map_bytes(
    tiles: Tuple[EllTileGroup, ...],
    num_vertices: int,
    *,
    use_weights: bool = False,
    frontier: bool = False,
    push_init: bool = False,
    extra_tiles: Tuple[EllTileGroup, ...] = (),
    plane_k: int = 1,
    frontier_planar: bool = False,
) -> int:
    """Single-pass HBM bytes of one fused edge map (sum of tile CostEstimates
    plus the O(V) combine write) — the number BENCH_apps.json reports.

    ``plane_k > 1`` prices a batched (V, K) property plane: property/output
    bytes scale with K, the tile structure is read once — dividing by K gives
    the per-query cost curve ``BENCH_serve.json`` reports."""
    total = num_vertices * 4 * plane_k  # combine write
    for t in tuple(tiles) + tuple(extra_tiles):
        r_pad, w_pad = t.idx.shape
        total += edge_map_tile_bytes(
            r_pad, w_pad,
            weighted=use_weights and t.w is not None,
            frontier=frontier,
            alive=t.alive is not None,
            init=push_init,
            idx_itemsize=t.idx.dtype.itemsize,
            plane_k=plane_k,
            frontier_planar=frontier_planar)
    return total
