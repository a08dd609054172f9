"""Ligra-style vertex-centric engine with pluggable edge-map backends.

The engine mirrors Ligra's two primitives:

  * ``edge_map_pull``  — for every destination vertex, reduce a function of its
    in-neighbors' properties (irregular READS of the property array);
  * ``edge_map_push``  — for every (active) source vertex, scatter a function of
    its property to its out-neighbors (irregular WRITES, the coherence-heavy
    mode of §VI-C).

Frontiers are dense boolean masks — static shapes keep everything jit-able;
``frontier_density`` is Ligra's pull/push switch statistic and now drives the
direction-optimizing SSSP/BC loops.

Two backends implement the primitives behind one protocol:

  * ``FlatBackend`` — the original edge-parallel path (gather ``prop[src]`` →
    weight add → frontier mask → segment reduce / scatter), 3-4 separate O(E)
    HBM passes.  Kept as the oracle: every app must agree with it.
  * ``EllBackend`` — the ``kernels.edge_map`` Pallas family: the whole edge
    map fused into one pass over per-DBG-group ELL tiles (the layouts the
    paper's grouping argues for).  Push needs no scatter at all — a push with
    a reduction into destinations is the pull of the transposed direction, so
    the same in-direction tiles serve both primitives.  min/max reductions
    are bit-identical to flat; sum differs only in fp association (~1e-6).

Apps are written against the dispatching ``edge_map_pull``/``edge_map_push``
functions and run unchanged on either backend; raw ``GraphArrays`` (the
``repro.dist`` / ``repro.stream`` substrate) keep the flat path.
"""
from __future__ import annotations

import dataclasses
from typing import (Callable, Dict, NamedTuple, Optional, Protocol, Tuple,
                    runtime_checkable)

import jax
import jax.numpy as jnp
import numpy as np

from ..graph import csr
from ..kernels.edge_map.edge_map import reduce_identity
from ..obs import trace as obs_trace
from ..obs.metrics import get_registry

__all__ = [
    "GraphArrays",
    "EdgeMapBackend",
    "FlatBackend",
    "EllBackend",
    "BACKENDS",
    "resolve_backend",
    "to_arrays",
    "edge_map_pull",
    "edge_map_push",
    "out_edge_sum",
    "set_edge_map_hook",
    "get_edge_map_hook",
    "vertex_map",
    "frontier_density",
    "switch_by_density",
    "DENSITY_THRESHOLD",
]


class GraphArrays(NamedTuple):
    # pull direction (in-edges, grouped by destination)
    in_src: jnp.ndarray  # (E,) int32 — source of each in-edge
    in_dst: jnp.ndarray  # (E,) int32 — owning destination (sorted ascending)
    in_w: jnp.ndarray    # (E,) float32 — weights (shared ones plane if unweighted)
    # push direction (out-edges, grouped by source)
    out_dst: jnp.ndarray  # (E,) int32 — destination of each out-edge
    out_src: jnp.ndarray  # (E,) int32 — owning source (sorted ascending)
    out_w: jnp.ndarray    # (E,) float32
    in_deg: jnp.ndarray   # (V,) int32
    out_deg: jnp.ndarray  # (V,) int32

    @property
    def num_vertices(self) -> int:
        return int(self.in_deg.shape[0])

    @property
    def num_edges(self) -> int:
        return int(self.in_src.shape[0])


@obs_trace.traced("layout.graph_arrays", cat="setup")
def _graph_arrays(g: csr.Graph) -> GraphArrays:
    """Host-side flattening of both CSR directions into GraphArrays.

    Unweighted graphs share ONE device plane of ones between ``in_w`` and
    ``out_w`` (they were two identical O(E) allocations; the flat edge maps
    only read the plane when ``use_weights`` anyway, and the fused backend
    drops it entirely)."""
    v = g.num_vertices
    in_csr, out_csr = g.in_csr, g.out_csr
    in_deg = in_csr.degrees().astype(np.int32)
    out_deg = out_csr.degrees().astype(np.int32)
    in_dst = np.repeat(np.arange(v, dtype=np.int32), in_deg)
    out_src = np.repeat(np.arange(v, dtype=np.int32), out_deg)
    if in_csr.weights is None and out_csr.weights is None:
        ones = jnp.ones(in_csr.num_edges, jnp.float32)
        in_w = out_w = ones  # one buffer, both fields
    else:
        in_w = jnp.asarray(
            in_csr.weights if in_csr.weights is not None
            else np.ones(in_csr.num_edges, np.float32), jnp.float32)
        out_w = jnp.asarray(
            out_csr.weights if out_csr.weights is not None
            else np.ones(out_csr.num_edges, np.float32), jnp.float32)
    return GraphArrays(
        in_src=jnp.asarray(in_csr.indices, jnp.int32),
        in_dst=jnp.asarray(in_dst),
        in_w=in_w,
        out_dst=jnp.asarray(out_csr.indices, jnp.int32),
        out_src=jnp.asarray(out_src),
        out_w=out_w,
        in_deg=jnp.asarray(in_deg),
        out_deg=jnp.asarray(out_deg),
    )


# ---------------------------------------------------------------------------
# Flat (edge-parallel) implementations — the oracle path
# ---------------------------------------------------------------------------

def _pull_flat(
    ga: GraphArrays,
    prop: jnp.ndarray,
    *,
    reduce: str = "sum",
    src_frontier: Optional[jnp.ndarray] = None,
    use_weights: bool = False,
    neutral: float = 0.0,
):
    vals = prop[ga.in_src]  # irregular gather — THE hot access of the paper
    if use_weights:
        w = ga.in_w if vals.ndim == 1 else ga.in_w[:, None]
        vals = vals + w  # SSSP-style relaxation uses additive weights
    if src_frontier is not None:
        m = src_frontier[ga.in_src]  # (E,) shared or (E, K) per-query
        if vals.ndim > 1 and m.ndim == 1:
            m = m[:, None]
        vals = jnp.where(m, vals, neutral)
    v = ga.in_deg.shape[0]
    if reduce == "sum":
        return jax.ops.segment_sum(vals, ga.in_dst, num_segments=v,
                                   indices_are_sorted=True)
    if reduce == "min":
        return jax.ops.segment_min(vals, ga.in_dst, num_segments=v,
                                   indices_are_sorted=True)
    if reduce in ("max", "or"):  # OR == max for boolean/int8 masks
        return jax.ops.segment_max(vals, ga.in_dst, num_segments=v,
                                   indices_are_sorted=True)
    raise ValueError(reduce)


def _push_flat(
    ga: GraphArrays,
    prop: jnp.ndarray,
    *,
    reduce: str = "sum",
    src_frontier: Optional[jnp.ndarray] = None,
    use_weights: bool = False,
    neutral: float = 0.0,
    init: Optional[jnp.ndarray] = None,
):
    vals = prop[ga.out_src]
    if use_weights:
        w = ga.out_w if vals.ndim == 1 else ga.out_w[:, None]
        vals = vals + w
    if src_frontier is not None:
        m = src_frontier[ga.out_src]  # (E,) shared or (E, K) per-query
        if vals.ndim > 1 and m.ndim == 1:
            m = m[:, None]
        vals = jnp.where(m, vals, neutral)
    v = ga.in_deg.shape[0]
    shape = (v,) + tuple(prop.shape[1:])
    if init is None:
        init = jnp.full(shape, reduce_identity(reduce), dtype=vals.dtype)
    if reduce == "sum":
        return init.at[ga.out_dst].add(vals)
    if reduce == "min":
        return init.at[ga.out_dst].min(vals)
    if reduce in ("max", "or"):
        return init.at[ga.out_dst].max(vals)
    raise ValueError(reduce)


# ---------------------------------------------------------------------------
# Backend protocol + implementations
# ---------------------------------------------------------------------------

@runtime_checkable
class EdgeMapBackend(Protocol):
    """What an edge-map backend must provide for the five apps to run.

    ``pull``/``push`` are the two Ligra primitives.  Backends whose storage
    is not edge-parallel (``repro.pack``'s `PackedBackend`) additionally
    implement ``out_edge_sum`` — BC's backward dependency gather — otherwise
    the dispatching :func:`out_edge_sum` takes the edge-parallel path over
    the delegate ``out_src``/``out_dst`` arrays.
    """

    def pull(self, prop, *, reduce="sum", src_frontier=None,
             use_weights=False, neutral=0.0): ...

    def push(self, prop, *, reduce="sum", src_frontier=None,
             use_weights=False, neutral=0.0, init=None): ...


class _Delegate:
    """Field passthrough so backends look like GraphArrays to existing code
    (dist sharding, BC's backward sweep, tests poking at raw arrays)."""

    ga: GraphArrays

    @property
    def in_src(self): return self.ga.in_src
    @property
    def in_dst(self): return self.ga.in_dst
    @property
    def in_w(self): return self.ga.in_w
    @property
    def out_dst(self): return self.ga.out_dst
    @property
    def out_src(self): return self.ga.out_src
    @property
    def out_w(self): return self.ga.out_w
    @property
    def in_deg(self): return self.ga.in_deg
    @property
    def out_deg(self): return self.ga.out_deg
    @property
    def num_vertices(self) -> int: return self.ga.num_vertices
    @property
    def num_edges(self) -> int: return self.ga.num_edges


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass(frozen=True)
class FlatBackend(_Delegate):
    """Today's gather/segment/scatter path — the correctness oracle."""

    ga: GraphArrays

    def pull(self, prop, **kw):
        return _pull_flat(self.ga, prop, **kw)

    def push(self, prop, **kw):
        return _push_flat(self.ga, prop, **kw)

    def tree_flatten(self):
        return (self.ga,), ()

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children)


def _int_identity(dtype, reduce: str) -> float:
    """Finite identity for integer-sourced props (matches the flat engine's
    empty segments: segment_max over int8 fills with iinfo.min, etc.)."""
    info = jnp.iinfo(dtype)
    return {"sum": 0.0, "min": float(info.max), "max": float(info.min),
            "or": float(info.min)}[reduce]


class FusedEdgeMaps:
    """Shared fused-edge-map implementation family (kernels.edge_map K5).

    Everything a backend needs to run the five apps through the fused Pallas
    kernels, given an in-direction tile set: one tile set serves both
    primitives — pull reduces a row's lanes directly; push seeds the row
    accumulator with ``init`` and runs the same kernel (a push-with-reduction
    IS the transposed pull).  Subclasses provide ``in_tiles``,
    ``num_vertices`` and the kernel geometry fields; `EllBackend` derives the
    tiles from a flat CSR, ``repro.pack.PackedBackend`` from the hot/cold
    packed storage, and ``repro.dist`` stacks the same tile structure
    per-shard — the three surfaces share THIS implementation instead of
    reimplementing edge-map semantics.
    """

    in_tiles: Tuple  # Tuple[EllTileGroup, ...]
    row_tile: int
    width_tile: int
    interpret: Optional[bool]

    def _kernel_kw(self):
        return dict(row_tile=self.row_tile, width_tile=self.width_tile,
                    interpret=self.interpret)

    def _map1(self, prop, *, reduce, src_frontier, use_weights, neutral, init):
        from ..kernels.edge_map.ops import fused_edge_map

        red = "max" if reduce == "or" else reduce
        if red not in ("sum", "min", "max"):
            raise ValueError(reduce)
        dtype = prop.dtype
        identity = None
        x = prop
        if not jnp.issubdtype(dtype, jnp.floating):
            x = prop.astype(jnp.float32)
            identity = _int_identity(dtype, reduce)
            if init is not None:
                init = init.astype(jnp.float32)
        out = fused_edge_map(
            self.in_tiles, x, self.num_vertices,
            reduce=red, src_frontier=src_frontier, use_weights=use_weights,
            neutral=neutral, init=init, identity=identity,
            **self._kernel_kw())
        return out.astype(dtype)

    def pull(self, prop, *, reduce="sum", src_frontier=None,
             use_weights=False, neutral=0.0):
        # (V, K) planes (Radii samples, repro.serve batched queries) run as
        # ONE fused pass: all K lanes share the tile/idx/frontier traffic.
        return self._map1(prop, reduce=reduce, src_frontier=src_frontier,
                          use_weights=use_weights, neutral=neutral, init=None)

    def push(self, prop, *, reduce="sum", src_frontier=None,
             use_weights=False, neutral=0.0, init=None):
        if init is None:
            init = jnp.full((self.num_vertices,) + tuple(prop.shape[1:]),
                            reduce_identity(reduce), dtype=prop.dtype)
        return self._map1(prop, reduce=reduce, src_frontier=src_frontier,
                          use_weights=use_weights, neutral=neutral, init=init)


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass(frozen=True)
class EllBackend(_Delegate, FusedEdgeMaps):
    """Fused Pallas edge maps over per-DBG-group ELL tiles (kernels.edge_map).

    The flat arrays stay on board for the operations outside the fused hot
    path (BC's backward dependency sweep, ``frontier_density``, dist
    sharding).
    """

    ga: GraphArrays
    in_tiles: Tuple  # Tuple[EllTileGroup, ...]
    row_tile: int = 64
    width_tile: int = 128
    interpret: Optional[bool] = None

    def tree_flatten(self):
        return ((self.ga, self.in_tiles),
                (self.row_tile, self.width_tile, self.interpret))

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(children[0], children[1], *aux)


# ---------------------------------------------------------------------------
# Backend registry — THE single table behind every backend-name switch
# ---------------------------------------------------------------------------

def _build_arrays(g: csr.Graph, **_):
    return _graph_arrays(g)


def _build_flat(g: csr.Graph, **_):
    return FlatBackend(_graph_arrays(g))


def _build_ell(g: csr.Graph, *, row_tile: int = 64, width_tile: int = 128,
               interpret: Optional[bool] = None):
    from ..core.reorder import dbg_spec
    from ..kernels.edge_map.ops import ell_tiles

    in_deg = g.in_csr.degrees()
    spec = dbg_spec(max(1.0, float(in_deg.mean()) if in_deg.size else 1.0))
    with obs_trace.span("layout.ell_pack", "setup", edges=g.num_edges):
        tiles = ell_tiles(g.in_csr, spec.boundaries,
                          row_tile=row_tile, width_tile=width_tile)
    # the slots each pass gathers, against the arcs they hold
    registry = get_registry()
    registry.gauge("layout.ell_slots").set(
        sum(int(t.idx.size) for t in tiles))
    registry.gauge("layout.arcs").set(g.num_edges)
    return EllBackend(_graph_arrays(g), tiles, row_tile=row_tile,
                      width_tile=width_tile, interpret=interpret)


def _build_packed(g: csr.Graph, *, row_tile: int = 64, width_tile: int = 128,
                  interpret: Optional[bool] = None, slot_align: int = 16,
                  hot_groups: int = 0):
    from ..pack.engine import packed_backend
    from ..pack.layout import pack_graph

    pg = pack_graph(g, slot_align=slot_align,
                    hot_groups=hot_groups if hot_groups > 0 else None,
                    rows_per_block=row_tile)
    return packed_backend(pg, row_tile=row_tile,
                          width_tile=width_tile, interpret=interpret)


def _build_auto(g: csr.Graph, *, app: Optional[str] = None, plan=None,
                **overrides):
    """``backend="auto"``: resolve the tuned execution plan for ``g``
    (``repro.tune.plan``) and build the backend it names.  Explicit kwargs
    override the plan; knobs the resolved backend does not consume are
    dropped silently (the plan may carry ELL geometry while resolving a
    graph to ``flat``)."""
    from ..tune import plan as tune_plan
    from ..tune import space as tune_space

    name, cfg = tune_plan.resolve_auto(g, app=app, plan=plan)
    cfg.update({k: v for k, v in overrides.items() if v is not None})
    accepted, _ignored = tune_space.validate_knobs(name, cfg)
    return resolve_backend(name)(g, **accepted)


#: name -> builder(g, **knobs).  ``to_arrays``, the sharded engine
#: (``repro.dist.graph``) and the benchmarks all resolve backend names
#: through this one table; extend it rather than matching strings locally.
#: The knobs each builder consumes are declared in
#: ``repro.tune.space.BACKEND_KNOBS`` — keep the two tables in sync.
BACKENDS: Dict[str, Callable] = {
    "flat": _build_flat,      # edge-parallel oracle (gather/segment/scatter)
    "ell": _build_ell,        # fused Pallas kernels over DBG-ELL tiles
    "packed": _build_packed,  # fused kernels straight over pack.PackedGraph
    "arrays": _build_arrays,  # raw GraphArrays (the dist/stream substrate)
    "auto": _build_auto,      # plan-resolved (repro.tune) concrete backend
}


def resolve_backend(name: str) -> Callable:
    """Look up a backend builder, with a clear error on unknown names."""
    try:
        return BACKENDS[name]
    except KeyError:
        raise ValueError(
            f"unknown edge-map backend {name!r}; known backends: "
            f"{', '.join(sorted(BACKENDS))}") from None


def to_arrays(
    g: csr.Graph,
    *,
    backend: str = "flat",
    strict: bool = False,
    **knobs,
):
    """Build an edge-map backend for ``g`` (resolved through ``BACKENDS``).

    ``backend="flat"`` (default) keeps the edge-parallel oracle path;
    ``"ell"`` packs the in-direction into per-DBG-group ELL tiles and routes
    every edge map through the fused Pallas kernels (``row_tile`` /
    ``width_tile`` / ``interpret``); ``"packed"`` packs ``g`` into hot/cold
    segmented storage (``repro.pack``, plus ``slot_align`` / ``hot_groups``)
    and runs the same fused kernels straight over the slot tables;
    ``"arrays"`` returns the raw ``GraphArrays`` (the dist/stream
    substrate); ``"auto"`` resolves the active tuned execution plan
    (``repro.tune``) — falling back to the hand-tuned defaults when no plan
    matches — and builds the backend it names (optionally per-``app``).

    Knob kwargs are validated against ``repro.tune.space.BACKEND_KNOBS``:
    unknown names always raise; knobs the chosen backend does not consume
    warn and are dropped (a tile-geometry kwarg on ``flat`` used to be a
    silent no-op), or raise with ``strict=True``.
    """
    from ..tune.space import validate_knobs

    accepted, ignored = validate_knobs(backend, knobs, strict=strict)
    if ignored:
        import warnings
        warnings.warn(
            f"to_arrays(backend={backend!r}): ignoring knob(s) "
            f"{sorted(ignored)} — not consumed by this backend "
            "(pass strict=True to make this an error)",
            stacklevel=2)
    with obs_trace.span("engine.build_backend", cat="engine",
                        backend=backend, vertices=g.num_vertices,
                        edges=g.num_edges):
        return resolve_backend(backend)(g, **accepted)


# ---------------------------------------------------------------------------
# instrumentation hook (repro.obs) — one table-stakes check per dispatch
# ---------------------------------------------------------------------------

#: When set (``repro.obs.counters.install()``), every ``edge_map_pull`` /
#: ``edge_map_push`` / ``out_edge_sum`` dispatch calls
#: ``hook.on_pass(ga, direction, prop, kw)`` BEFORE running — the hook must
#: not touch operand values (instrumented runs stay bitwise identical; the
#: obs test suite property-checks this on all three backends).  ``None``
#: (the default) costs one ``is not None`` per dispatch.
_EDGE_MAP_HOOK = None


def set_edge_map_hook(hook):
    """Install (or clear, with ``None``) the edge-map instrumentation hook.
    Returns the previously installed hook."""
    global _EDGE_MAP_HOOK
    prev, _EDGE_MAP_HOOK = _EDGE_MAP_HOOK, hook
    return prev


def get_edge_map_hook():
    return _EDGE_MAP_HOOK


def edge_map_pull(ga, prop, **kw):
    """dst <- REDUCE over in-edges of f(prop[src]).

    ``prop`` may be (V,) or (V, S) (multi-source apps like Radii/BC batches).
    ``reduce`` in {sum, min, max, or}.  ``src_frontier`` masks contributing
    sources (inactive sources contribute ``neutral``).  Dispatches to the
    backend; raw ``GraphArrays`` take the flat path.
    """
    if _EDGE_MAP_HOOK is not None:
        _EDGE_MAP_HOOK.on_pass(ga, "pull", prop, kw)
    if isinstance(ga, GraphArrays):
        return _pull_flat(ga, prop, **kw)
    return ga.pull(prop, **kw)


def edge_map_push(ga, prop, **kw):
    """dst <- REDUCE over pushes from active sources.

    On the flat backend this is the scatter-with-duplicates of the paper's
    read-modify-write traffic; on the fused backend it is the transposed
    pull with an ``init``-seeded accumulator — no scatter at all.
    """
    if _EDGE_MAP_HOOK is not None:
        _EDGE_MAP_HOOK.on_pass(ga, "push", prop, kw)
    if isinstance(ga, GraphArrays):
        return _push_flat(ga, prop, **kw)
    return ga.push(prop, **kw)


def out_edge_sum(ga, edge_val) -> jnp.ndarray:
    """src <- SUM over out-edges of ``edge_val(src_ids, dst_ids)``.

    BC's backward dependency gather: a pull in the OUT direction whose edge
    value depends on both endpoints.  Backends with segmented (non-edge-
    parallel) storage provide their own ``out_edge_sum``; everything backed
    by flat arrays takes the edge-parallel segment sum here.
    """
    if _EDGE_MAP_HOOK is not None:
        _EDGE_MAP_HOOK.on_pass(ga, "out_sum", None, {})
    fn = getattr(ga, "out_edge_sum", None)
    if fn is not None:
        return fn(edge_val)
    v = ga.in_deg.shape[0]
    vals = edge_val(ga.out_src, ga.out_dst)
    return jax.ops.segment_sum(vals, ga.out_src, num_segments=v,
                               indices_are_sorted=True)


def vertex_map(frontier: jnp.ndarray, fn) -> jnp.ndarray:
    """Apply fn over active vertices (dense mask semantics)."""
    return jnp.where(frontier, fn(), 0)


def frontier_density(ga, frontier: jnp.ndarray) -> jnp.ndarray:
    """Fraction of edges touched by the frontier — Ligra's pull/push switch
    statistic (|out-edges of frontier| / E)."""
    e = jnp.maximum(1, ga.out_deg.sum())
    return jnp.sum(jnp.where(frontier, ga.out_deg, 0)) / e


# Ligra's heuristic: go pull once the frontier touches > E/20 edges.  The
# fallback for every direction-optimizing app (SSSP, BC, serve.batched) —
# now a per-plan tunable (``repro.tune``'s ``density_threshold`` knob): the
# switch is a traffic choice, both directions reduce the identical edge set,
# so any threshold yields bitwise-identical results at different cost.
DENSITY_THRESHOLD = 0.05


def switch_by_density(ga, frontier, pull_step, push_step, operand,
                      threshold: Optional[float] = None):
    """``lax.cond`` on :func:`frontier_density`: dense → pull, sparse → push.

    ``threshold`` (static) overrides :data:`DENSITY_THRESHOLD`; tuned plans
    thread their ``density_threshold`` knob through the apps to here."""
    if threshold is None:
        threshold = DENSITY_THRESHOLD
    return jax.lax.cond(
        frontier_density(ga, frontier) > threshold,
        pull_step, push_step, operand)
