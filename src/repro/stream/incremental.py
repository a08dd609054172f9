"""Delta-based recompute: PageRank / SSSP refresh over a ``DeltaGraph``.

The engine mirrors ``apps.engine`` but runs over *stream arrays*: the frozen
base edge arrays (both directions, with tombstone masks) plus the padded
delta-edge buffer.  Padding the delta buffer to a power of two keeps jit
recompiles logarithmic in stream length.

Incremental PageRank maintains the invariant

    residual == F(rank) - rank        (F = the PR operator of the CURRENT graph)

After an update batch, the residual changes only at vertices adjacent to the
batch: ``IncrementalPageRank.ingest`` computes that exact change on the host
in O(batch + adjacency of degree-changed sources) — never a full rescan.
``refresh`` then push-propagates residual mass (Gauss-Jacobi forward push,
the same loop shape as ``apps.pagerank_delta``) until ``max|residual| <=
epsilon``; work is proportional to how far the batch's perturbation reaches,
so a small batch re-converges in a handful of frontier-local iterations
instead of PageRank's ~50 full-graph iterations.  Since the invariant is
maintained exactly (not re-estimated), repeated batches do not drift: the
fixed point of the push loop is the true PageRank of the current graph.

Incremental SSSP uses the classic asymmetry: edge *insertions* only ever
shorten paths, so relaxation restarts from the improved destinations; an edge
*deletion* is a problem only when the deleted edge supported a shortest path
(``dist[dst] == dist[src] + w``), in which case we conservatively recompute
from scratch — detected at refresh time, exact either way.  A deletion that
lands on an edge still waiting in the pending-insert buffers (inserted after
the last refresh, so invisible to ``dist``) is scrubbed from those buffers
instead, so ``refresh`` never relaxes through a tombstoned edge.
"""
from __future__ import annotations

from functools import partial
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..kernels.edge_map.edge_map import reduce_identity
from .delta import ApplyResult, DeltaGraph, occurrence_rank

__all__ = [
    "StreamArrays",
    "StreamBackend",
    "stream_arrays",
    "edge_map_pull_stream",
    "edge_map_push_stream",
    "stream_push_tiles",
    "edge_map_push_stream_fused",
    "edge_map_pull_stream_fused",
    "IncrementalPageRank",
    "IncrementalSSSP",
]


class StreamArrays(NamedTuple):
    """Edge-parallel view of base + delta, analogous to engine.GraphArrays."""

    # base pull direction (in-edges grouped by destination) + tombstone mask
    in_src: jnp.ndarray
    in_dst: jnp.ndarray
    in_w: jnp.ndarray
    in_alive: jnp.ndarray
    # base push direction (out-edges grouped by source) + tombstone mask
    out_src: jnp.ndarray
    out_dst: jnp.ndarray
    out_w: jnp.ndarray
    out_alive: jnp.ndarray
    # delta buffer (padded; padding has alive=False), serves both directions
    ex_src: jnp.ndarray
    ex_dst: jnp.ndarray
    ex_w: jnp.ndarray
    ex_alive: jnp.ndarray
    # CURRENT degrees (base + deltas - tombstones)
    in_deg: jnp.ndarray
    out_deg: jnp.ndarray

    @property
    def num_vertices(self) -> int:
        return int(self.in_deg.shape[0])


def _next_pow2(n: int) -> int:
    return 1 << max(0, int(np.ceil(np.log2(max(1, n)))))


def stream_arrays(dg: DeltaGraph) -> StreamArrays:
    """Materialize stream arrays; base-direction uploads are cached per base."""
    cache = getattr(dg, "_stream_base_cache", None)
    if cache is None or cache[0] is not dg.base:
        base = dg.base
        v = base.num_vertices
        in_csr, out_csr = base.in_csr, base.out_csr
        in_dst = np.repeat(np.arange(v, dtype=np.int32),
                           in_csr.degrees().astype(np.int64))
        out_src = np.repeat(np.arange(v, dtype=np.int32),
                            out_csr.degrees().astype(np.int64))
        ones = lambda m: np.ones(m, np.float32)
        bd = dict(
            in_src=jnp.asarray(in_csr.indices, jnp.int32),
            in_dst=jnp.asarray(in_dst),
            in_w=jnp.asarray(in_csr.weights if in_csr.weights is not None
                             else ones(in_csr.num_edges), jnp.float32),
            out_src=jnp.asarray(out_src),
            out_dst=jnp.asarray(out_csr.indices, jnp.int32),
            out_w=jnp.asarray(out_csr.weights if out_csr.weights is not None
                              else ones(out_csr.num_edges), jnp.float32),
        )
        cache = (base, bd)
        dg._stream_base_cache = cache
    bd = cache[1]
    # The O(E) alive masks change only when a BASE tombstone lands (extras
    # deletions live in the delta buffer below); cache the device arrays on
    # (base identity, tombstone count) so insert-only refreshes skip the
    # host scatter and the two O(E) uploads.
    masks = getattr(dg, "_stream_mask_cache", None)
    if (masks is None or masks[0] is not dg.base
            or masks[1] != dg.dead_base_edges):
        masks = (dg.base, dg.dead_base_edges,
                 jnp.asarray(dg.in_alive_mask()), jnp.asarray(dg.base_alive))
        dg._stream_mask_cache = masks
    ex_src, ex_dst, ex_w, ex_alive = dg.extras()
    n = ex_src.shape[0]
    pad = _next_pow2(max(1, n))
    p_src = np.zeros(pad, np.int32)
    p_dst = np.zeros(pad, np.int32)
    p_w = np.ones(pad, np.float32)
    p_alive = np.zeros(pad, bool)
    p_src[:n] = ex_src
    p_dst[:n] = ex_dst
    p_w[:n] = ex_w
    p_alive[:n] = ex_alive
    return StreamArrays(
        **bd,
        in_alive=masks[2],
        out_alive=masks[3],
        ex_src=jnp.asarray(p_src),
        ex_dst=jnp.asarray(p_dst),
        ex_w=jnp.asarray(p_w),
        ex_alive=jnp.asarray(p_alive),
        in_deg=jnp.asarray(dg.in_deg, jnp.int32),
        out_deg=jnp.asarray(dg.out_deg, jnp.int32),
    )


def edge_map_pull_stream(
    sa: StreamArrays,
    prop: jnp.ndarray,
    *,
    reduce: str = "sum",
    src_frontier: Optional[jnp.ndarray] = None,
    use_weights: bool = False,
    neutral: Optional[float] = None,
):
    """dst <- REDUCE over CURRENT in-edges of f(prop[src]) (base + delta).

    Unlike the engine's edge maps, tombstoned and padding edges are ALWAYS
    masked to ``neutral``, so the default neutral must be the reduction's
    identity element (not 0.0, which absorbs under min).
    """
    if neutral is None:
        neutral = reduce_identity(reduce)
    v = sa.in_deg.shape[0]
    vals = prop[sa.in_src]
    if use_weights:
        vals = vals + sa.in_w
    mask = sa.in_alive
    if src_frontier is not None:
        mask = mask & src_frontier[sa.in_src]
    vals = jnp.where(mask, vals, neutral)
    if reduce == "sum":
        out = jax.ops.segment_sum(vals, sa.in_dst, num_segments=v,
                                  indices_are_sorted=True)
    elif reduce == "min":
        out = jax.ops.segment_min(vals, sa.in_dst, num_segments=v,
                                  indices_are_sorted=True)
    elif reduce in ("max", "or"):
        out = jax.ops.segment_max(vals, sa.in_dst, num_segments=v,
                                  indices_are_sorted=True)
    else:
        raise ValueError(reduce)
    evals = prop[sa.ex_src]
    if use_weights:
        evals = evals + sa.ex_w
    emask = sa.ex_alive
    if src_frontier is not None:
        emask = emask & src_frontier[sa.ex_src]
    evals = jnp.where(emask, evals, neutral)
    if reduce == "sum":
        return out.at[sa.ex_dst].add(evals)
    if reduce == "min":
        return out.at[sa.ex_dst].min(evals)
    return out.at[sa.ex_dst].max(evals)


def edge_map_push_stream(
    sa: StreamArrays,
    prop: jnp.ndarray,
    *,
    reduce: str = "sum",
    src_frontier: Optional[jnp.ndarray] = None,
    use_weights: bool = False,
    neutral: Optional[float] = None,
    init: Optional[jnp.ndarray] = None,
):
    """dst <- REDUCE over pushes along CURRENT out-edges (base + delta).

    Masked (tombstoned/padding/out-of-frontier) edges push ``neutral``, which
    defaults to the reduction's identity element.
    """
    if neutral is None:
        neutral = reduce_identity(reduce)
    v = sa.in_deg.shape[0]
    if init is None:
        init = jnp.full((v,), reduce_identity(reduce), dtype=prop.dtype)

    def scatter(acc, src, dst, w, alive):
        vals = prop[src]
        if use_weights:
            vals = vals + w
        mask = alive
        if src_frontier is not None:
            mask = mask & src_frontier[src]
        vals = jnp.where(mask, vals, neutral)
        if reduce == "sum":
            return acc.at[dst].add(vals)
        if reduce == "min":
            return acc.at[dst].min(vals)
        if reduce in ("max", "or"):
            return acc.at[dst].max(vals)
        raise ValueError(reduce)

    acc = scatter(init, sa.out_src, sa.out_dst, sa.out_w, sa.out_alive)
    return scatter(acc, sa.ex_src, sa.ex_dst, sa.ex_w, sa.ex_alive)


# ---------------------------------------------------------------------------
# Engine-protocol backend over the live base + delta layout
# ---------------------------------------------------------------------------

class StreamBackend:
    """``engine.EdgeMapBackend`` over :class:`StreamArrays`.

    Construction via :func:`from_delta` costs O(delta): ``stream_arrays``
    reuses the base-direction uploads cached on the ``DeltaGraph`` (and the
    O(E) alive masks, unless a base tombstone landed) and only re-pads the
    pending extras.  This is what lets ``serve.SnapshotStore`` publish a
    version without rebuilding backend arrays from scratch.

    Batched (V, K) query planes vmap the 1-D stream edge maps over the plane
    axis; registered as a pytree so the jitted batched solvers take it as an
    argument like any other backend.
    """

    def __init__(self, sa: StreamArrays, weighted: bool = False):
        self.sa = sa
        self.weighted = bool(weighted)

    @classmethod
    def from_delta(cls, dg: DeltaGraph) -> "StreamBackend":
        return cls(stream_arrays(dg), dg.base.out_csr.weights is not None)

    # -- delegate surface ---------------------------------------------------
    @property
    def num_vertices(self) -> int:
        return self.sa.num_vertices

    @property
    def in_deg(self) -> jnp.ndarray:
        return self.sa.in_deg

    @property
    def out_deg(self) -> jnp.ndarray:
        return self.sa.out_deg

    # -- edge maps ----------------------------------------------------------
    def pull(self, prop, *, src_frontier=None, **kw):
        if prop.ndim == 1:
            return edge_map_pull_stream(self.sa, prop,
                                        src_frontier=src_frontier, **kw)
        if src_frontier is None:
            return jax.vmap(
                lambda p: edge_map_pull_stream(self.sa, p, **kw),
                in_axes=1, out_axes=1)(prop)
        return jax.vmap(
            lambda p, f: edge_map_pull_stream(self.sa, p, src_frontier=f,
                                              **kw),
            in_axes=(1, 1), out_axes=1)(prop, src_frontier)

    def push(self, prop, *, src_frontier=None, init=None, reduce="sum",
             **kw):
        if prop.ndim == 1:
            return edge_map_push_stream(self.sa, prop, reduce=reduce,
                                        src_frontier=src_frontier,
                                        init=init, **kw)
        v = self.sa.num_vertices
        if src_frontier is None:
            src_frontier = jnp.ones((v, prop.shape[1]), bool)
        if init is None:
            init = jnp.full((v, prop.shape[1]), reduce_identity(reduce),
                            prop.dtype)
        return jax.vmap(
            lambda p, f, i: edge_map_push_stream(
                self.sa, p, reduce=reduce, src_frontier=f, init=i, **kw),
            in_axes=(1, 1, 1), out_axes=1)(prop, src_frontier, init)

    def out_edge_sum(self, edge_val) -> jnp.ndarray:
        v = self.sa.num_vertices
        vals = jnp.where(self.sa.out_alive,
                         edge_val(self.sa.out_src, self.sa.out_dst), 0)
        out = jax.ops.segment_sum(vals, self.sa.out_src, num_segments=v,
                                  indices_are_sorted=True)
        evals = jnp.where(self.sa.ex_alive,
                          edge_val(self.sa.ex_src, self.sa.ex_dst), 0)
        return out.at[self.sa.ex_src].add(evals)

    # -- the lazy-snapshot escape hatch -------------------------------------
    def materialize(self):
        """The exact version-N graph these arrays pin (alive base edges +
        alive extras) as an immutable ``csr.Graph`` — O(E), taken only when
        a reader forces ``Snapshot.graph`` on a lazily published version."""
        from ..graph import csr
        keep = np.asarray(self.sa.in_alive)
        src = [np.asarray(self.sa.in_src)[keep]]
        dst = [np.asarray(self.sa.in_dst)[keep]]
        ekeep = np.asarray(self.sa.ex_alive)
        src.append(np.asarray(self.sa.ex_src)[ekeep])
        dst.append(np.asarray(self.sa.ex_dst)[ekeep])
        w = None
        if self.weighted:
            w = np.concatenate([np.asarray(self.sa.in_w)[keep],
                                np.asarray(self.sa.ex_w)[ekeep]])
        return csr.from_edges(np.concatenate(src), np.concatenate(dst),
                              self.num_vertices, weights=w)


jax.tree_util.register_pytree_node(
    StreamBackend,
    lambda b: ((b.sa,), b.weighted),
    lambda aux, ch: StreamBackend(ch[0], aux),
)


# ---------------------------------------------------------------------------
# Fused base+delta push (kernels.edge_map K5 over the stream layout)
# ---------------------------------------------------------------------------

def stream_push_tiles(dg: DeltaGraph, *, row_tile: int = 64,
                      width_tile: int = 128):
    """(base_tiles, delta_tiles) for the fused stream push.

    The base in-direction is packed once per base snapshot into ELL width
    classes — tombstones ride as an alive bitplane that is re-scattered (idx/w
    planes untouched) when the tombstone count moves, so a deletion does NOT
    force repacking between compactions.  The pending delta buffer (tiny,
    cold) becomes one dst-grouped ELL group per refresh and runs through the
    SAME fused kernel as a second segment, replacing the separate O(E_base)
    + O(D) scatters of ``edge_map_push_stream``.
    """
    from ..kernels.edge_map.ops import coo_tiles, ell_tiles, refresh_alive

    # Two-level cache, base compared by IDENTITY (Graph holds arrays; ==
    # would be elementwise).  Level 1: the expensive structural pack (degree
    # binning + idx/w fills), invalidated only by compaction.  Level 2: the
    # alive bitplanes, re-scattered when the tombstone count moves — a
    # deletion batch never repacks the base.
    in_csr = dg.base.in_csr
    struct = getattr(dg, "_push_tile_struct", None)
    if (struct is None or struct[0] is not dg.base
            or struct[1] != (row_tile, width_tile)):
        tiles = ell_tiles(in_csr, row_tile=row_tile, width_tile=width_tile)
        struct = (dg.base, (row_tile, width_tile), tiles)
        dg._push_tile_struct = struct
        dg._push_tile_alive = None
    alive_cache = getattr(dg, "_push_tile_alive", None)
    if alive_cache is None or alive_cache[0] != dg.dead_base_edges:
        tiles = struct[2]
        if dg.dead_base_edges:
            tiles = refresh_alive(in_csr, tiles,
                                  np.asarray(dg.in_alive_mask()))
        alive_cache = (dg.dead_base_edges, tiles)
        dg._push_tile_alive = alive_cache
    base_tiles = alive_cache[1]
    ex_src, ex_dst, ex_w, ex_alive = dg.extras()
    delta_tiles = coo_tiles(
        np.asarray(ex_src), np.asarray(ex_dst), w=np.asarray(ex_w),
        alive=np.asarray(ex_alive), row_tile=row_tile, width_tile=width_tile)
    return base_tiles, delta_tiles


def edge_map_push_stream_fused(
    base_tiles,
    delta_tiles,
    prop: jnp.ndarray,
    num_vertices: int,
    *,
    reduce: str = "sum",
    src_frontier: Optional[jnp.ndarray] = None,
    use_weights: bool = False,
    init: Optional[jnp.ndarray] = None,
    row_tile: int = 64,
    width_tile: int = 128,
    interpret: Optional[bool] = None,
):
    """Fused-kernel twin of :func:`edge_map_push_stream` (base + delta in one
    kernel family, no edge-parallel scatter).  Masked edges always take the
    reduction's identity element — the stream engine's default ``neutral`` —
    which is what lets tombstones and frontier share one in-kernel mask."""
    from ..kernels.edge_map.ops import fused_edge_map

    red = "max" if reduce == "or" else reduce
    neutral = reduce_identity(reduce)
    if init is None:
        init = jnp.full((num_vertices,), neutral, dtype=prop.dtype)
    return fused_edge_map(
        base_tiles, prop, num_vertices,
        reduce=red, src_frontier=src_frontier, use_weights=use_weights,
        neutral=neutral, init=init, extra_tiles=delta_tiles,
        row_tile=row_tile, width_tile=width_tile, interpret=interpret)


def edge_map_pull_stream_fused(
    base_tiles,
    delta_tiles,
    prop: jnp.ndarray,
    num_vertices: int,
    *,
    reduce: str = "sum",
    src_frontier: Optional[jnp.ndarray] = None,
    use_weights: bool = False,
    row_tile: int = 64,
    width_tile: int = 128,
    interpret: Optional[bool] = None,
):
    """Fused-kernel twin of :func:`edge_map_pull_stream`.

    The in-direction tiles ``stream_push_tiles`` maintains (push here is the
    transposed pull, so the ONE tile set serves both) run in pull mode —
    ``init=None``, every dst row reduced over its current in-edges, base +
    delta in the same kernel family — replacing the O(E_base) segment reduce
    + O(D) scatter of the edge-parallel pull."""
    from ..kernels.edge_map.ops import fused_edge_map

    red = "max" if reduce == "or" else reduce
    return fused_edge_map(
        base_tiles, prop, num_vertices,
        reduce=red, src_frontier=src_frontier, use_weights=use_weights,
        neutral=reduce_identity(reduce), init=None, extra_tiles=delta_tiles,
        row_tile=row_tile, width_tile=width_tile, interpret=interpret)


@partial(jax.jit, static_argnames=("max_iters", "row_tile", "width_tile"))
def _sssp_converge_fused(base_tiles, delta_tiles, dist, frontier,
                         max_iters: int, row_tile: int = 64,
                         width_tile: int = 128):
    """Frontier Bellman-Ford with the fused base+delta push kernel."""
    v = dist.shape[0]

    def cond(state):
        _, f, it = state
        return jnp.logical_and(it < max_iters, jnp.any(f))

    def body(state):
        dist, frontier, it = state
        cand = edge_map_push_stream_fused(
            base_tiles, delta_tiles, dist, v, reduce="min",
            src_frontier=frontier, use_weights=True, init=dist,
            row_tile=row_tile, width_tile=width_tile)
        return cand, cand < dist, it + 1

    return jax.lax.while_loop(cond, body, (dist, frontier, 0))


# ---------------------------------------------------------------------------
# Incremental PageRank
# ---------------------------------------------------------------------------

@jax.jit
def _pr_residual(sa: StreamArrays, rank: jnp.ndarray, damping: jnp.ndarray):
    """Exact residual F(rank) - rank on the current graph (one full pull)."""
    v = rank.shape[0]
    dangling = sa.out_deg == 0
    odeg = jnp.maximum(1, sa.out_deg).astype(jnp.float32)
    contrib = jnp.where(dangling, 0.0, rank / odeg)
    pulled = edge_map_pull_stream(sa, contrib, reduce="sum")
    dmass = jnp.sum(jnp.where(dangling, rank, 0.0)) / v
    return (1.0 - damping) / v + damping * (pulled + dmass) - rank


@jax.jit
def _pr_residual_fused(base_tiles, delta_tiles, out_deg, rank, damping):
    """:func:`_pr_residual` with the full pull on the fused base+delta tiles
    (the same in-direction tile set the push loop rides) — the resync after
    compaction was the last edge-parallel pass left under
    ``use_fused_push=True``."""
    v = rank.shape[0]
    dangling = out_deg == 0
    odeg = jnp.maximum(1, out_deg).astype(jnp.float32)
    contrib = jnp.where(dangling, 0.0, rank / odeg)
    pulled = edge_map_pull_stream_fused(base_tiles, delta_tiles, contrib, v,
                                        reduce="sum")
    dmass = jnp.sum(jnp.where(dangling, rank, 0.0)) / v
    return (1.0 - damping) / v + damping * (pulled + dmass) - rank


@partial(jax.jit, static_argnames=("max_iters",))
def _pr_converge(sa: StreamArrays, rank, residual, damping, epsilon,
                 max_iters: int):
    """Forward-push until max|residual| <= epsilon, preserving the invariant
    residual == F(rank) - rank at every step."""
    v = rank.shape[0]
    dangling = sa.out_deg == 0
    odeg = jnp.maximum(1, sa.out_deg).astype(jnp.float32)

    def cond(state):
        _, res, it = state
        return jnp.logical_and(it < max_iters,
                               jnp.max(jnp.abs(res)) > epsilon)

    def body(state):
        rank, res, it = state
        moved = jnp.where(jnp.abs(res) > epsilon, res, 0.0)
        contrib = jnp.where(dangling, 0.0, moved / odeg)
        pushed = edge_map_push_stream(sa, contrib, reduce="sum")
        dmass = jnp.sum(jnp.where(dangling, moved, 0.0)) / v
        res = res - moved + damping * (pushed + dmass)
        return rank + moved, res, it + 1

    return jax.lax.while_loop(cond, body, (rank, residual, 0))


@partial(jax.jit, static_argnames=("max_iters",))
def _pr_converge_fused(base_tiles, delta_tiles, out_deg, rank, residual,
                       damping, epsilon, max_iters: int):
    """Fused-kernel twin of :func:`_pr_converge`: the forward push rides the
    base+delta Pallas kernel (``edge_map_push_stream_fused``) the way
    ``IncrementalSSSP(use_fused_push=True)`` already does — same invariant,
    same loop, no edge-parallel scatter.  Sum pushes reassociate, so ranks
    agree with the unfused loop to fp association (~1e-8), not bitwise."""
    v = rank.shape[0]
    dangling = out_deg == 0
    odeg = jnp.maximum(1, out_deg).astype(jnp.float32)

    def cond(state):
        _, res, it = state
        return jnp.logical_and(it < max_iters,
                               jnp.max(jnp.abs(res)) > epsilon)

    def body(state):
        rank, res, it = state
        moved = jnp.where(jnp.abs(res) > epsilon, res, 0.0)
        contrib = jnp.where(dangling, 0.0, moved / odeg)
        pushed = edge_map_push_stream_fused(
            base_tiles, delta_tiles, contrib, v, reduce="sum")
        dmass = jnp.sum(jnp.where(dangling, moved, 0.0)) / v
        res = res - moved + damping * (pushed + dmass)
        return rank + moved, res, it + 1

    return jax.lax.while_loop(cond, body, (rank, residual, 0))


class IncrementalPageRank:
    """PageRank that re-converges from batch-local residual mass.

    ``use_fused_push=True`` routes the push-convergence loop through the
    fused base+delta Pallas kernel (``stream_push_tiles`` +
    :func:`_pr_converge_fused`); the exact-residual resync and ingest stay
    identical, so the invariant is maintained either way.
    """

    def __init__(self, dg: DeltaGraph, *, damping: float = 0.85,
                 epsilon: float = 1e-9, max_iters: int = 4096,
                 use_fused_push: bool = False):
        self.dg = dg
        self.damping = float(damping)
        self.epsilon = float(epsilon)
        self.max_iters = int(max_iters)
        self.use_fused_push = bool(use_fused_push)
        v = dg.num_vertices
        self.rank = np.full(v, 1.0 / v, np.float32)
        self._residual = np.zeros(v, np.float32)
        # uniform component of the residual (dangling-mass changes), kept as
        # a scalar and folded in at refresh so ingest stays batch-local
        self._res_uniform = 0.0
        self._needs_full_residual = True  # first refresh = initial full solve
        self._dirty = True
        self.last_iters = 0
        self.total_push_iters = 0

    def ingest(self, result: ApplyResult) -> None:
        """Fold one applied batch into the residual — O(batch + touched).

        Every array below is indexed over the batch's candidate sources and
        their adjacency, never the full vertex set: the pre-batch degrees
        come from ``result.cand_old_out_deg`` (all sources the batch named
        are in ``cand_sources``), and the uniform dangling-mass term is
        carried as a scalar instead of being spread over V entries here.
        """
        if self._needs_full_residual:
            self._dirty = True
            return
        dg = self.dg
        rank = self.rank
        odn = dg.out_deg
        cand = result.cand_sources  # sorted (np.unique output)
        odo_cand = result.cand_old_out_deg
        changed = odn[cand] != odo_cand
        c_sources = cand[changed]

        # + contributions of every CURRENT edge whose source changed degree,
        #   plus edges inserted from unchanged sources
        s1s, s1d = dg.out_edges_of(c_sources)
        keep = ~np.isin(result.add_src, c_sources)
        s1s = np.concatenate([s1s, result.add_src[keep]])
        s1d = np.concatenate([s1d, result.add_dst[keep]])
        v1 = rank[s1s].astype(np.float64) / np.maximum(1, odn[s1s])
        # - contributions of every PRE-BATCH edge whose source changed degree,
        #   plus edges deleted from unchanged sources (all such sources are
        #   in ``cand``, so their pre-batch degree is in ``odo_cand``)
        old_c = np.isin(result.old_edges_src, c_sources)
        s2s = result.old_edges_src[old_c]
        s2d = result.old_edges_dst[old_c]
        keep = ~np.isin(result.del_src, c_sources)
        s2s = np.concatenate([s2s, result.del_src[keep]])
        s2d = np.concatenate([s2d, result.del_dst[keep]])
        odo_s2 = odo_cand[np.searchsorted(cand, s2s)]
        v2 = rank[s2s].astype(np.float64) / np.maximum(1, odo_s2)

        idx = np.concatenate([s1d, s2d])
        if idx.size:
            u, inv = np.unique(idx, return_inverse=True)
            acc = np.bincount(inv, weights=np.concatenate([v1, -v2]))
            self._residual[u] = (self._residual[u].astype(np.float64)
                                 + self.damping * acc).astype(np.float32)
        # dangling-mass change (uniformly spread term)
        r_cand = rank[cand].astype(np.float64)
        dmass = float(np.sum(r_cand * ((odn[cand] == 0).astype(np.float64)
                                       - (odo_cand == 0))))
        self._res_uniform += self.damping * dmass / dg.num_vertices
        self._dirty = True

    def resync(self) -> None:
        """Recompute the residual exactly (one O(E) pull) — called after
        compaction to shed accumulated float32 noise."""
        self._needs_full_residual = True
        self._dirty = True

    def refresh(self) -> int:
        """Push-converge; returns the number of push iterations run."""
        if not self._dirty:
            return 0
        sa = stream_arrays(self.dg)
        if self._needs_full_residual:
            if self.use_fused_push:
                # resync rides the SAME fused base+delta tiles as the push
                # loop (cached on the DeltaGraph) instead of dropping back
                # to the edge-parallel segment reduce
                base_tiles, delta_tiles = stream_push_tiles(self.dg)
                self._residual = np.asarray(
                    _pr_residual_fused(base_tiles, delta_tiles, sa.out_deg,
                                       jnp.asarray(self.rank),
                                       jnp.float32(self.damping)))
            else:
                self._residual = np.asarray(
                    _pr_residual(sa, jnp.asarray(self.rank),
                                 jnp.float32(self.damping)))
            self._needs_full_residual = False
            self._res_uniform = 0.0
        elif self._res_uniform:
            self._residual = (self._residual.astype(np.float64)
                              + self._res_uniform).astype(np.float32)
            self._res_uniform = 0.0
        if self.use_fused_push:
            base_tiles, delta_tiles = stream_push_tiles(self.dg)
            rank, res, it = _pr_converge_fused(
                base_tiles, delta_tiles, sa.out_deg, jnp.asarray(self.rank),
                jnp.asarray(self._residual), jnp.float32(self.damping),
                jnp.float32(self.epsilon), self.max_iters)
        else:
            rank, res, it = _pr_converge(
                sa, jnp.asarray(self.rank), jnp.asarray(self._residual),
                jnp.float32(self.damping), jnp.float32(self.epsilon),
                self.max_iters)
        self.rank = np.asarray(rank)
        # writable copy: ingest patches the residual in place batch-locally
        self._residual = np.array(res)
        self.last_iters = int(it)
        self.total_push_iters += self.last_iters
        self._dirty = False
        return self.last_iters

    def query(self) -> np.ndarray:
        self.refresh()
        return self.rank.copy()


# ---------------------------------------------------------------------------
# Incremental SSSP
# ---------------------------------------------------------------------------

# the per-key occurrence-claim primitive now lives in ``delta`` (it is shared
# with the vectorized deletion staging of ``DeltaGraph.apply``)
_occurrence_rank = occurrence_rank


@partial(jax.jit, static_argnames=("max_iters",))
def _sssp_converge(sa: StreamArrays, dist, frontier, max_iters: int):
    """Frontier Bellman-Ford over the current (base + delta) edges."""

    def cond(state):
        _, f, it = state
        return jnp.logical_and(it < max_iters, jnp.any(f))

    def body(state):
        dist, frontier, it = state
        cand = edge_map_push_stream(
            sa, dist, reduce="min", src_frontier=frontier,
            use_weights=True, neutral=jnp.inf, init=dist)
        return cand, cand < dist, it + 1

    return jax.lax.while_loop(cond, body, (dist, frontier, 0))


class IncrementalSSSP:
    """SSSP with insertion-driven relaxation and deletion fallback.

    ``use_fused_push=True`` routes the convergence loop through the fused
    base+delta Pallas push kernel (``stream_push_tiles`` +
    ``_sssp_converge_fused``) instead of the edge-parallel scatters —
    identical results (min-relaxation is exactly associative).
    """

    def __init__(self, dg: DeltaGraph, root: int, *, max_iters: int = 0,
                 use_fused_push: bool = False):
        self.dg = dg
        self.root = int(root)
        self.max_iters = max_iters
        self.use_fused_push = bool(use_fused_push)
        self.dist: Optional[np.ndarray] = None
        self._pending_src: list = []
        self._pending_dst: list = []
        self._pending_w: list = []
        self._del_src: list = []
        self._del_dst: list = []
        self._del_w: list = []
        self._needs_full = True
        self.full_recomputes = 0
        self.last_iters = 0

    def _edge_w(self, result: ApplyResult, which: str) -> np.ndarray:
        w = getattr(result, which)
        n = getattr(result, which.replace("_w", "_src")).shape[0]
        return np.ones(n, np.float32) if w is None else w

    def ingest(self, result: ApplyResult) -> None:
        """Record one applied batch — pure O(batch) appends.  The deletion
        analysis (pending scrub + criticality check) is deferred to
        ``refresh``: ``dist`` is static between refreshes, so the deferred
        check is identical, and a long query-free churn stream stays linear
        instead of re-scanning the pending buffers every batch."""
        if self._needs_full or self.dist is None:
            self._needs_full = True
            return
        if result.add_src.size:
            self._pending_src.append(result.add_src)
            self._pending_dst.append(result.add_dst)
            self._pending_w.append(self._edge_w(result, "add_w"))
        if result.del_src.size:
            self._del_src.append(result.del_src)
            self._del_dst.append(result.del_dst)
            self._del_w.append(self._edge_w(result, "del_w"))

    def _settle_deletions(self) -> None:
        """Fold the recorded deletions into the pending state (refresh-time).

        A deletion may target an edge still sitting in the pending insert
        buffers (inserted since the last refresh, so invisible to ``dist`` —
        and to the criticality check below when its destination was
        unreachable).  Scrub one matching (src, dst, w) occurrence per
        deletion first; otherwise the seeding in ``refresh`` would relax a
        finite distance through a tombstoned edge.  A matched deletion needs
        no criticality check: either it killed the pending insert itself
        (never part of ``dist``), or it killed an identical (src, dst, w)
        edge while a pending twin stays alive and preserves every path the
        victim carried.
        """
        if not self._del_src:
            return
        ds = np.concatenate(self._del_src)
        dd = np.concatenate(self._del_dst)
        w = np.concatenate(self._del_w)
        self._del_src, self._del_dst, self._del_w = [], [], []
        unmatched = self._scrub_pending(ds, dd, w)
        if np.any(unmatched):
            dist = self.dist
            ds, dd, w = ds[unmatched], dd[unmatched], w[unmatched]
            # the deletion matters only if the edge supported a shortest path
            reach = np.isfinite(dist[ds])
            slack = dist[ds] + w - dist[dd]
            tol = 1e-4 * (1.0 + np.abs(dist[dd]))
            if np.any(reach & np.isfinite(dist[dd]) & (slack <= tol)):
                self._needs_full = True

    def _scrub_pending(self, ds: np.ndarray, dd: np.ndarray,
                       w: np.ndarray) -> np.ndarray:
        """Drop one pending-insert occurrence matching each deletion.

        Occurrences with identical (src, dst, w) are interchangeable, so the
        matching reduces to per-key counting — each key scrubs
        min(#deletions, #pending) occurrences.  One O((D + P) log(D + P))
        pass per refresh.

        Returns a bool mask over the deletions marking the ones that matched
        nothing (these must still pass the criticality check).
        """
        nd = ds.shape[0]
        unmatched = np.ones(nd, dtype=bool)
        if not self._pending_src:
            return unmatched
        ps = np.concatenate(self._pending_src)
        pd = np.concatenate(self._pending_dst)
        pw = np.concatenate(self._pending_w)
        trip = np.empty(nd + ps.shape[0], dtype=[
            ("s", np.int64), ("d", np.int64), ("w", np.float32)])
        trip["s"] = np.concatenate([ds, ps])
        trip["d"] = np.concatenate([dd, pd])
        trip["w"] = np.concatenate([w, pw])
        uniq, inv = np.unique(trip, return_inverse=True)
        inv_d, inv_p = inv[:nd], inv[nd:]
        nk = uniq.shape[0]
        scrub = np.minimum(np.bincount(inv_d, minlength=nk),
                           np.bincount(inv_p, minlength=nk))
        if not scrub.any():
            return unmatched
        unmatched = _occurrence_rank(inv_d) >= scrub[inv_d]
        keep = _occurrence_rank(inv_p) >= scrub[inv_p]
        if keep.any():
            self._pending_src = [ps[keep]]
            self._pending_dst = [pd[keep]]
            self._pending_w = [pw[keep]]
        else:
            self._clear_pending()
        return unmatched

    def refresh(self) -> int:
        dg = self.dg
        v = dg.num_vertices
        max_iters = self.max_iters or v
        if not self._needs_full and self.dist is not None:
            self._settle_deletions()
        if not self._needs_full and self.dist is not None \
                and not self._pending_src:
            self.last_iters = 0  # nothing changed: skip materialization too
            return 0
        if self._needs_full or self.dist is None:
            dist0 = np.full(v, np.inf, np.float32)
            dist0[self.root] = 0.0
            frontier0 = np.zeros(v, bool)
            frontier0[self.root] = True
            if self.dist is not None:
                self.full_recomputes += 1
        else:
            src = np.concatenate(self._pending_src)
            dst = np.concatenate(self._pending_dst)
            w = np.concatenate(self._pending_w)
            dist0 = self.dist.copy()
            cand = np.where(np.isfinite(dist0[src]), dist0[src] + w, np.inf)
            np.minimum.at(dist0, dst, cand.astype(np.float32))
            frontier0 = dist0 < self.dist
            if not frontier0.any():
                self._clear_pending()
                self.last_iters = 0
                return 0
        if self.use_fused_push:
            base_tiles, delta_tiles = stream_push_tiles(dg)
            dist, _, it = _sssp_converge_fused(
                base_tiles, delta_tiles, jnp.asarray(dist0),
                jnp.asarray(frontier0), max_iters)
        else:
            dist, _, it = _sssp_converge(stream_arrays(dg), jnp.asarray(dist0),
                                         jnp.asarray(frontier0), max_iters)
        self.dist = np.asarray(dist)
        self._needs_full = False
        self._clear_pending()
        self.last_iters = int(it)
        return self.last_iters

    def _clear_pending(self) -> None:
        self._pending_src, self._pending_dst, self._pending_w = [], [], []
        self._del_src, self._del_dst, self._del_w = [], [], []

    def query(self) -> np.ndarray:
        self.refresh()
        return self.dist.copy()
