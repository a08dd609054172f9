"""Analytic cost pre-ranker: price every candidate, run nothing.

The measured sweep (``tune.search``) is the expensive half of the tuner; it
can only afford a handful of candidates per graph.  This module prices the
WHOLE configuration space analytically — the same byte models the benchmarks
report and ``repro.obs.counters`` charges per pass — and prunes it to a
top-k shortlist:

  * ``flat``   — :func:`repro.obs.counters.flat_edge_map_bytes` (the
    edge-parallel pass model ``benchmarks/edge_map_perf.py`` cross-checks
    against XLA's ``cost_analysis``);
  * ``ell``    — per-width-class tile geometry from the degree vector alone
    (the packer's own ``kernels.edge_map.ops.width_classes`` —
    property-tested equal to ``fused_edge_map_bytes`` over the
    actually-built tiles), priced with ``edge_map_tile_bytes``;
  * ``packed`` — the hot/cold split of ``pack.layout.pack_adjacency``
    (stride quantization, sub-line power-of-two slots, hot-group
    thresholding) recomputed the same way, hot slot tables + cold ELL
    classes priced per tile.

Bytes become seconds through :class:`repro.roofline.HW`: a memory term
(modeled bytes / bandwidth), a compute term (~2 FLOPs per edge-lane), and
a **dispatch term** — the number of (row tile × width tile) blocks each
config's tile geometry implies times the profile's ``dispatch_overhead``
(the kernel's own grid rounds row blocks up to 128 lanes, so this is the
geometry's count, not the compiled grid's).  On real hardware the
dispatch cost is ~0 and ranking is effectively by bytes; under
``cpu-interpret`` the interpreter's per-grid-step Python cost dominates
small-graph wall clock, so pricing it is what makes the analytic shortlist
contain the measured winner instead of ranking tile geometry at random.

Nothing here touches a device array: a ~160-candidate space prices in
milliseconds, and the ranker's honesty (does the shortlist contain the
measured winner?) is logged per graph by ``benchmarks/autotune.py``.
"""
from __future__ import annotations

import dataclasses
import json
import math
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from ..kernels.edge_map.ops import _pad_dim, width_classes
from ..roofline import HW
from .space import DEFAULT_CONFIG, canonical, split_config

__all__ = [
    "PassProfile",
    "APP_PROFILES",
    "GraphCost",
    "Scored",
    "config_key",
    "config_steps",
    "rank",
    "shortlist",
]


# ---------------------------------------------------------------------------
# workload profiles — the pass mix each app pays per iteration
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class PassProfile:
    """Shape of one edge-map pass (what the byte models charge for)."""

    direction: str  # "pull" | "push"
    use_weights: bool = False
    frontier: bool = False
    frontier_planar: bool = False
    plane_k: int = 1


#: app -> per-iteration pass mix.  PR is one clean pull; PRΔ and SSSP push
#: from a frontier (SSSP with additive weights and an init-seeded
#: accumulator); BC pays its forward sigma pull plus the backward dependency
#: gather (out_edge_sum — pull-shaped traffic in the out direction); Radii
#: rides a (V, S) sample plane through one pull.
APP_PROFILES: Dict[str, Tuple[PassProfile, ...]] = {
    "pr": (PassProfile("pull"),),
    "prd": (PassProfile("push", frontier=True),),
    "sssp": (PassProfile("push", use_weights=True, frontier=True),),
    "bc": (PassProfile("pull"), PassProfile("pull")),
    "radii": (PassProfile("pull", plane_k=4),),
}


# ---------------------------------------------------------------------------
# geometry mirrors (host-side, degree vector only)
# ---------------------------------------------------------------------------

def _round_up(n: int, m: int) -> int:
    return ((n + m - 1) // m) * m


def _ell_itemsize(num_vertices: int) -> int:
    # mirrors kernels.edge_map.ops._id_dtype
    return 2 if num_vertices <= np.iinfo(np.uint16).max else 4


def _hot_itemsize(num_vertices: int) -> int:
    # mirrors pack.codec.min_uint_dtype(v - 1) — the hot tables keep the
    # storage dtype when wrapped as tiles
    from ..pack.codec import min_uint_dtype

    return np.dtype(min_uint_dtype(max(0, num_vertices - 1))).itemsize


def _dbg_boundaries(deg: np.ndarray) -> Tuple[int, ...]:
    from ..core.reorder import dbg_spec

    mean = max(1.0, float(deg.mean()) if deg.size else 1.0)
    return tuple(int(b) for b in dbg_spec(mean).boundaries)


def ell_tile_geometry(
    deg: np.ndarray,
    *,
    row_tile: int,
    width_tile: int,
    itemsize: int,
) -> List[Tuple[int, int, int]]:
    """``[(r_pad, w_pad, idx_itemsize)]`` of ``ell_tiles`` on this degree
    vector: the packer's own ``width_classes``, without building a plane."""
    return [(_pad_dim(rows.size, row_tile), w_pad, itemsize)
            for w_pad, rows in width_classes(deg, row_tile=row_tile,
                                             width_tile=width_tile)]


def packed_tile_geometry(
    deg: np.ndarray,
    *,
    row_tile: int,
    width_tile: int,
    slot_align: int = 16,
    hot_groups: int = 0,
    num_vertices: Optional[int] = None,
) -> List[Tuple[int, int, int]]:
    """Tile geometry of ``PackedBackend.in_tiles`` for one degree vector:
    hot slot tables (stride-quantized per ``pack_adjacency``'s rules,
    wrapped in place at the storage dtype) followed by the cold segment's
    ELL width classes.  ``hot_groups=0`` takes the layout's own threshold
    (groups whose lower bound is at least the mean degree)."""
    from ..core.reorder import _assign_groups

    deg = np.asarray(deg, np.int64)
    v = int(num_vertices if num_vertices is not None else deg.shape[0])
    boundaries = _dbg_boundaries(deg)
    if not hot_groups:
        mean = max(1.0, float(deg.mean()) if deg.size else 1.0)
        hot_groups = max(1, sum(1 for b in boundaries if b >= mean))
    hot_groups = min(int(hot_groups), len(boundaries))
    grp = _assign_groups(deg, boundaries)

    geom: List[Tuple[int, int, int]] = []
    hot_item = _hot_itemsize(v)
    for k in range(hot_groups):
        rows = int((grp == k).sum())
        if rows == 0:
            continue
        wmax = int(deg[grp == k].max())
        if wmax and wmax < slot_align:
            stride = 1 << int(math.ceil(math.log2(wmax)))
        else:
            stride = _round_up(wmax, slot_align)
        if stride == 0:
            continue
        geom.append((_pad_dim(rows, row_tile), _pad_dim(stride, width_tile),
                     hot_item))

    cold = deg.copy()
    cold[grp < hot_groups] = 0  # hot rows have degree 0 in the cold CSR
    geom.extend(ell_tile_geometry(cold, row_tile=row_tile,
                                  width_tile=width_tile,
                                  itemsize=_ell_itemsize(v)))
    return geom


# ---------------------------------------------------------------------------
# pricing
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class GraphCost:
    """Everything the pricer needs from a graph, host-side and tiny."""

    in_deg: np.ndarray  # (V,) — the pull direction's degree vector
    num_vertices: int
    num_edges: int
    weighted: bool = False

    @classmethod
    def from_graph(cls, g, *, weighted: Optional[bool] = None) -> "GraphCost":
        return cls(
            in_deg=np.asarray(g.in_degrees(), np.int64),
            num_vertices=g.num_vertices,
            num_edges=g.num_edges,
            weighted=(g.in_csr.weights is not None
                      if weighted is None else bool(weighted)))


def _tile_set_bytes(geom: List[Tuple[int, int, int]], v: int,
                    p: PassProfile, weighted: bool) -> int:
    from ..kernels.edge_map.edge_map import edge_map_tile_bytes

    push_init = p.direction == "push"
    total = v * 4 * p.plane_k  # the O(V) combine write
    for r_pad, w_pad, itemsize in geom:
        total += edge_map_tile_bytes(
            r_pad, w_pad,
            weighted=p.use_weights and weighted,
            frontier=p.frontier, alive=False, init=push_init,
            idx_itemsize=itemsize, plane_k=p.plane_k,
            frontier_planar=p.frontier_planar)
    return total


def pass_bytes(gc: GraphCost, config: Dict, p: PassProfile) -> int:
    """Modeled HBM bytes of ONE edge-map pass of shape ``p`` under
    ``config`` — the same number ``EdgeMapCounters`` would charge for the
    built backend (property-tested)."""
    from ..obs.counters import flat_edge_map_bytes

    cfg = canonical(config)
    backend = cfg["backend"]
    if backend in ("flat", "arrays"):
        return flat_edge_map_bytes(
            gc.num_edges, gc.num_vertices,
            weighted=p.use_weights and gc.weighted, frontier=p.frontier,
            push_init=p.direction == "push", plane_k=p.plane_k,
            frontier_planar=p.frontier_planar)
    if backend not in ("ell", "packed"):
        raise ValueError(f"cannot price backend {backend!r}")
    return _tile_set_bytes(_config_geometry(gc, cfg), gc.num_vertices, p,
                           gc.weighted)


def _config_geometry(gc: GraphCost, cfg: Dict) -> List[Tuple[int, int, int]]:
    backend = cfg["backend"]
    row_tile = int(cfg.get("row_tile", 64))
    width_tile = int(cfg.get("width_tile", 128))
    if backend == "ell":
        return ell_tile_geometry(
            gc.in_deg, row_tile=row_tile, width_tile=width_tile,
            itemsize=_ell_itemsize(gc.num_vertices))
    return packed_tile_geometry(
        gc.in_deg, row_tile=row_tile, width_tile=width_tile,
        slot_align=int(cfg.get("slot_align", 16)),
        hot_groups=int(cfg.get("hot_groups", 0)),
        num_vertices=gc.num_vertices)


def config_steps(gc: GraphCost, config: Dict, app: str = "pr") -> int:
    """Tile blocks one iteration of ``app`` walks under ``config`` —
    ``(r_pad // tile) * (w_pad // tile)``
    (with whole-dim blocks when a padded dim is smaller than its tile,
    mirroring ``ops._tile_of``) summed over tile groups and passes.  The
    flat backend is a fused XLA op chain — zero Pallas dispatches."""
    cfg = canonical(config)
    if cfg["backend"] in ("flat", "arrays"):
        return 0
    row_tile = int(cfg.get("row_tile", 64))
    width_tile = int(cfg.get("width_tile", 128))
    per_pass = 0
    for r_pad, w_pad, _ in _config_geometry(gc, cfg):
        rt = row_tile if r_pad >= row_tile else r_pad
        wt = width_tile if w_pad >= width_tile else w_pad
        per_pass += (r_pad // rt) * (w_pad // wt)
    return per_pass * len(APP_PROFILES[app])


def app_bytes(gc: GraphCost, config: Dict, app: str = "pr") -> int:
    """Per-iteration modeled HBM bytes of ``app`` under ``config``."""
    return sum(pass_bytes(gc, config, p) for p in APP_PROFILES[app])


def app_seconds(gc: GraphCost, config: Dict, app: str = "pr",
                hw: Optional[HW] = None) -> float:
    """Roofline time of one iteration: memory term from the byte models,
    compute term ~2 FLOPs per (edge, lane), dispatch term = grid steps ×
    the profile's per-step overhead.  Under ``HW.profile("v5e")`` the
    dispatch term is 0 and this is effectively the byte ranking; under
    ``"cpu-interpret"`` the dispatch term dominates for small graphs —
    exactly as the interpreter does."""
    hw = hw if hw is not None else HW.profile()
    bytes_ = app_bytes(gc, config, app)
    flops = sum(2.0 * gc.num_edges * p.plane_k for p in APP_PROFILES[app])
    seconds = bytes_ / hw.hbm_bw + flops / hw.peak_flops
    if hw.dispatch_overhead:
        seconds += config_steps(gc, config, app) * hw.dispatch_overhead
    return seconds


# ---------------------------------------------------------------------------
# ranking
# ---------------------------------------------------------------------------

def config_key(config: Dict) -> str:
    """Deterministic identity of a canonical config (sort/tie-break key)."""
    return json.dumps(canonical(config), sort_keys=True)


@dataclasses.dataclass(frozen=True)
class Scored:
    config: Dict
    model_bytes: int
    cost_s: float
    steps: int = 0  # Pallas grid steps per iteration (0 for flat)


def rank(gc: GraphCost, candidates: Sequence[Dict], *, app: str = "pr",
         hw: Optional[HW] = None) -> List[Scored]:
    """Price every candidate, cheapest first (ties broken by modeled bytes,
    then the canonical config key — fully deterministic)."""
    hw = hw if hw is not None else HW.profile()
    scored = []
    for cfg in candidates:
        cfg = canonical(cfg)
        engine_cfg, _, _ = split_config(cfg)
        scored.append(Scored(
            config=cfg,
            model_bytes=app_bytes(gc, engine_cfg, app),
            cost_s=app_seconds(gc, engine_cfg, app, hw=hw),
            steps=config_steps(gc, engine_cfg, app)))
    return sorted(scored, key=lambda s: (s.cost_s, s.model_bytes,
                                         config_key(s.config)))


def shortlist(ranked: Sequence[Scored], k: int, *,
              must_include: Optional[Dict] = None) -> List[Scored]:
    """Top-k *distinct cost classes* of a ranking: candidates tied on
    ``(cost_s, model_bytes)`` build identical-shaped tile sets (e.g. packed
    ``slot_align`` variants whose strides quantize the same), so measuring
    more than one of a tie class spends sweep budget on duplicates —
    instead each class contributes its first (deterministic key-ordered)
    member and the shortlist covers k genuinely different geometries.
    ``must_include`` (normally the hand-tuned :data:`DEFAULT_CONFIG`) is
    appended if pruned — the measured sweep always sees the incumbent, so
    ``backend="auto"`` can never regress past it unnoticed."""
    out: List[Scored] = []
    seen_classes = set()
    for s in ranked:
        if len(out) >= k:
            break
        sig = (s.cost_s, s.model_bytes)
        if sig in seen_classes:
            continue
        seen_classes.add(sig)
        out.append(s)
    if must_include is not None:
        want = config_key(split_config(must_include)[0])
        if not any(config_key(s.config) == want for s in out):
            for s in ranked:
                if config_key(s.config) == want:
                    out.append(s)
                    break
    return out


def default_budget(gc: GraphCost, app: str = "pr") -> int:
    """Modeled bytes of the hand-tuned default — the never-spend-more
    budget the measured selection is constrained by."""
    engine_cfg, _, _ = split_config(DEFAULT_CONFIG)
    return app_bytes(gc, engine_cfg, app)
