"""Destination-sharded graph engine with DBG-aware hot-vertex replication.

The paper segregates hot degree-groups from cold ones so the hot working set
fits the fast memory level (DBG, Table V).  This module lifts that insight
from the cache level to the DEVICE level: vertices in the hot degree-groups
of ``core.reorder.dbg_spec`` get their property slices REPLICATED on every
device (policy ``"replicate_hot"``); the cold tail is OWNER-PARTITIONED and
exchanged on demand.

Layout (built host-side by :func:`shard_graph`):

* vertices are 1D-partitioned into ``n_shards`` contiguous blocks of
  ``v_blk`` ids (destination ownership);
* pull: each shard owns the in-edges of its destination block (globally
  sorted by dst, so per-shard segments stay sorted);
* push: each shard owns the out-edges of its source block.

Pull-side communication is a HALO EXCHANGE: shard ``d`` needs ``prop[s]`` for
every remote, non-hot source ``s`` of its local edges.  The exchange is a
single ``jax.lax.all_to_all`` whose payload is exactly the halo — replicating
the hot groups shrinks it dramatically on power-law graphs, because the few
high-degree vertices account for most remote references (the same skew DBG
exploits in cache).  Each device then gathers edge values from one
concatenated table ``[local block | hot table | received halo]``.

Push-side communication is the reduction: per-device partial destination
vectors are combined with ``psum_scatter`` (sum) / ``pmin``/``pmax``.

Two EDGE-MAP BACKENDS implement the per-shard compute, resolved through the
same ``apps.engine.BACKENDS`` name table as the single-device engine:

* ``"flat"`` — the edge-parallel oracle above (gather → mask → segment
  reduce / scatter), 3-4 separate O(E_shard) HBM passes per device;
* ``"ell"`` — each shard's edge segment packed into DBG-ELL tiles
  (``kernels.edge_map.ops.ell_tiles_sharded``) whose lanes index the SAME
  concatenated value table, so the whole per-shard edge map is one fused
  Pallas pass; the collectives are identical.  Push needs no scatter — the
  per-shard partial is the transposed pull over dst-grouped tiles.

Shard-aware update routing: :func:`apply_remap` consumes a
``stream.RemapDelta`` and re-homes ONLY the vertices whose degree group
changed — retargeting their edge slots between the hot table and the halo
(and patching the affected ELL tile lanes in place) instead of re-sharding
from a full mapping.  The layout reserves slack for this (``remap_headroom``)
and raises :class:`RemapOverflow` when the drift exceeds it (the caller then
does the full re-shard it would have done every time before).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, List, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import AxisType, Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

from ..apps.engine import GraphArrays
from ..apps import engine as apps_engine
from ..core import reorder
from ..obs import trace as obs_trace
from ..kernels.edge_map.edge_map import (edge_map_tile_bytes,
                                         ell_edge_map_pallas,
                                         reduce_identity)
from ..kernels.edge_map.ops import (_scatter_combine, _tile_of,
                                    ell_tiles_sharded)

__all__ = ["ShardedGraphArrays", "ShardDeltaSegment", "shard_graph",
           "graph_mesh", "place_shards",
           "edge_map_pull_sharded", "edge_map_push_sharded",
           "edge_map_bytes_sharded", "pagerank_sharded", "apply_remap",
           "RemapOverflow", "HaloOverflow"]

AXIS = "graph"


@functools.lru_cache(maxsize=None)
def graph_mesh(n: int) -> Mesh:
    """1D ``(AXIS,)`` mesh over the first ``n`` devices: the mesh every
    sharded entry builds and the one :func:`place_shards` puts the shard
    state on.  Cached per size, so repeat solves hit the compiled-executable
    cache (which is mesh-identity keyed).  The axis is ``Auto``: outside the
    ``shard_map`` bodies XLA propagates the sharding itself."""
    return Mesh(np.array(jax.devices()[:n]), (AXIS,),
                axis_types=(AxisType.Auto,))

#: backends the sharded engine implements (a subset of apps.engine.BACKENDS)
SHARDED_BACKENDS = ("flat", "ell")


class RemapOverflow(RuntimeError):
    """apply_remap ran out of reserved hot/halo slots — re-shard instead."""


class HaloOverflow(RemapOverflow):
    """Streaming edge-delta routing ran out of reserved halo slots: an
    inserted cold edge crosses a shard pair whose halo segment is full.
    Subclasses :class:`RemapOverflow` so callers' existing full-re-shard
    fallback covers both drift kinds with one except clause."""


class ShardDeltaSegment(NamedTuple):
    """Device view of the per-shard streaming delta buffers (a NamedTuple so
    it rides jit/shard_map as a pytree).

    The flat arrays are the edge-parallel delta representation (one entry
    per routed edge, padded to capacity ``C``; dead/padding entries have
    ``alive == False``).  ``pull_tiles``/``push_tiles`` are the fused
    representation (``kernels.edge_map.ops.coo_tiles_sharded``) packed from
    the same buffers for the ``"ell"`` backend.  Capacities grow
    monotonically in powers of two, so the pytree SHAPES — and therefore any
    cached sharded-query executable — stay stable across ingest batches.
    """

    # pull side (owner = destination shard): slots into [local|hot|halo]
    slot: jnp.ndarray     # (D, C) int32
    dstl: jnp.ndarray     # (D, C) int32 — dst - i*v_blk
    w: jnp.ndarray        # (D, C) float32 (ones when unweighted)
    alive: jnp.ndarray    # (D, C) bool
    # push side (owner = source shard)
    p_srcl: jnp.ndarray   # (D, Cp) int32
    p_dst: jnp.ndarray    # (D, Cp) int32 — global (padded space)
    p_w: jnp.ndarray      # (D, Cp) float32
    p_alive: jnp.ndarray  # (D, Cp) bool
    # fused COO delta tiles (backend "ell" only)
    pull_tiles: Optional[Tuple] = None
    push_tiles: Optional[Tuple] = None

    @property
    def capacity(self) -> Tuple[int, int]:
        return int(self.slot.shape[1]), int(self.p_srcl.shape[1])


@dataclasses.dataclass(frozen=True)
class ShardedGraphArrays:
    """Host-built sharded layout; leading dim of every (D, …) array is the
    shard dim fed to ``shard_map`` with ``P("graph")``."""

    n_shards: int
    num_vertices: int
    v_blk: int          # vertices per shard block (last block padded)
    halo_max: int       # padded halo slots per (owner, dest) device pair
    policy: str         # "replicate_hot" | "partition"
    # pull side (destination-sharded in-edges)
    in_slot: jnp.ndarray       # (D, E_blk) int32 — index into the value table
    in_dst_local: jnp.ndarray  # (D, E_blk) int32 — dst - d*v_blk, sorted
    in_w: jnp.ndarray          # (D, E_blk) float32
    in_mask: jnp.ndarray       # (D, E_blk) bool — real edge vs pad
    send_idx: jnp.ndarray      # (D, D, halo_max) int32 — owner-local sends
    hot_ids: jnp.ndarray       # (H_cap,) int32 — replicated ids (padded w/ 0)
    # push side (source-sharded out-edges)
    out_src_local: jnp.ndarray  # (D, E_out_blk) int32
    out_dst: jnp.ndarray        # (D, E_out_blk) int32 — global (padded space)
    out_w: jnp.ndarray          # (D, E_out_blk) float32
    out_mask: jnp.ndarray       # (D, E_out_blk) bool
    # replicated degree vectors (apps need them)
    in_deg: jnp.ndarray   # (V,) int32
    out_deg: jnp.ndarray  # (V,) int32
    # engine backend ("flat" | "ell") + per-shard fused tiles when "ell"
    backend: str = "flat"
    hot_cap: int = 0          # hot-table slots incl. remap headroom
    hot_group_count: int = 0  # DBG groups counted as hot at build time
    weighted: bool = False
    row_tile: int = 64
    width_tile: int = 128
    # Pallas interpret mode for the fused per-shard kernels; None derives it
    # from the platform (repro.kernels.mode), as apps.engine.EllBackend does
    interpret: Optional[bool] = None
    pull_tiles: Optional[Tuple] = None  # stacked EllTileGroups (slots → table)
    push_tiles: Optional[Tuple] = None  # stacked EllTileGroups (dst → local)
    # streaming delta segment (dist.stream): per-shard edge-delta buffers +
    # COO delta tiles riding the same shard_map next to the base arrays
    delta: Optional[ShardDeltaSegment] = None
    stats: Dict[str, Any] = dataclasses.field(default_factory=dict)
    # mutable host-side bookkeeping for apply_remap (shared across patched
    # copies; patching moves it forward, invalidating older snapshots)
    host: Optional[dict] = dataclasses.field(
        default=None, repr=False, compare=False)

    @property
    def v_pad(self) -> int:
        return self.n_shards * self.v_blk

    @property
    def table_len(self) -> int:
        """Per-shard gather-table length: [local | hot | halo]."""
        return self.v_blk + self.hot_cap + self.n_shards * self.halo_max


#: device-array fields of a ShardedGraphArrays: the (D, ...) per-shard
#: stacks (incl. tile groups and the delta segment) and the replicated rest
_SHARD_FIELDS = ("in_slot", "in_dst_local", "in_w", "in_mask", "send_idx",
                 "out_src_local", "out_dst", "out_w", "out_mask",
                 "pull_tiles", "push_tiles", "delta")
_REPLICA_FIELDS = ("hot_ids", "in_deg", "out_deg")
_ARRAY_FIELDS = _SHARD_FIELDS + _REPLICA_FIELDS


def _sg_arrays(sg: ShardedGraphArrays) -> dict:
    """The device arrays of ``sg`` as one pytree — what jitted solves take
    as ARGUMENTS (closed-over arrays would be baked into the program as
    constants)."""
    return {f: getattr(sg, f) for f in _ARRAY_FIELDS}


def place_shards(sg: ShardedGraphArrays) -> ShardedGraphArrays:
    """Put shard ``i``'s slice of every per-shard stack on device ``i`` of
    :func:`graph_mesh` (``NamedSharding(mesh, P(AXIS))``) and replicate the
    O(V) vectors, so no device holds another's shard.  Host (numpy) arrays
    go straight to their devices; device arrays already placed are left
    alone.  A layout built for more shards than this process has devices
    (host-side layout statistics) stays on the default device."""
    if sg.n_shards > len(jax.devices()):
        mesh = None
    else:
        mesh = graph_mesh(sg.n_shards)

    def put(tree, spec):
        if mesh is None:
            return jax.tree_util.tree_map(jnp.asarray, tree)
        sharding = NamedSharding(mesh, spec)
        return jax.tree_util.tree_map(
            lambda x: jax.device_put(x, sharding), tree)

    return dataclasses.replace(
        sg, **{f: put(getattr(sg, f), P(AXIS)) for f in _SHARD_FIELDS},
        **{f: put(getattr(sg, f), P()) for f in _REPLICA_FIELDS})


def _hot_mask(out_deg: np.ndarray, policy: str,
              num_hot_groups: int) -> Tuple[np.ndarray, int]:
    """(mask, n_hot_groups): vertices in the DBG hot degree-groups
    (everything at/above avg degree — the groups the paper packs into the
    fast level), plus how many of the spec's groups that covers."""
    if policy == "partition" or out_deg.size == 0:
        return np.zeros(out_deg.shape[0], dtype=bool), 0
    if policy != "replicate_hot":
        raise ValueError(policy)
    avg = max(1.0, float(out_deg.mean()))
    spec = reorder.dbg_spec(avg, num_hot_groups=num_hot_groups)
    groups = reorder._assign_groups(out_deg, spec.boundaries)
    # hot = every group whose degree range sits at/above A; count via the
    # boundary values (dbg_spec dedupes colliding boundaries on tiny A, so a
    # fixed "all but the last 2" offset would miscount)
    a_bound = max(1, int(np.ceil(avg)))
    n_hot = sum(1 for b in spec.boundaries if b >= a_bound)
    return groups < n_hot, n_hot


def _pad2d(rows, fill, dtype) -> np.ndarray:
    width = max(1, max((len(r) for r in rows), default=1))
    out = np.full((len(rows), width), fill, dtype=dtype)
    for i, r in enumerate(rows):
        out[i, : len(r)] = r
    return out


def _with_headroom(n: int, frac: float) -> int:
    return n + int(np.ceil(n * frac)) + 8


def _key_index(srcs: np.ndarray, dsts: np.ndarray,
               v_pad: int) -> Tuple[np.ndarray, np.ndarray]:
    """(sorted keys, argsort order) over ``src * v_pad + dst`` — the O(log E)
    deletion lookup the streaming path uses to find an edge's storage slot."""
    keys = srcs.astype(np.int64) * np.int64(v_pad) + dsts.astype(np.int64)
    order = np.argsort(keys, kind="stable")
    return keys[order], order


def _new_delta_buf(pull: bool, cap: int = 8) -> dict:
    """Capacity-doubling host master of one shard's delta buffer."""
    buf = {"dst": np.zeros(cap, np.int64), "w": np.zeros(cap, np.float32),
           "alive": np.zeros(cap, bool), "n": 0}
    if pull:
        buf["src"] = np.zeros(cap, np.int64)
        buf["slot"] = np.zeros(cap, np.int64)
    else:
        buf["srcl"] = np.zeros(cap, np.int64)
    return buf


def shard_graph(ga: GraphArrays, n_shards: int, *,
                policy: str = "replicate_hot",
                num_hot_groups: int = 6,
                backend: str = "flat",
                row_tile: int = 64,
                width_tile: int = 128,
                interpret: Optional[bool] = None,
                hot_override: Optional[np.ndarray] = None,
                remap_headroom: float = 0.25,
                track_remap: Optional[bool] = None,
                stream: bool = False) -> ShardedGraphArrays:
    """Partition ``GraphArrays`` for an ``n_shards``-device 1D mesh.

    ``backend`` selects the per-shard edge-map implementation (resolved
    against ``apps.engine.BACKENDS``; the sharded engine implements ``"flat"``
    and ``"ell"``).  ``hot_override`` replaces the DBG hot mask with an
    explicit hot-vertex id set (the full-re-shard counterpart of
    :func:`apply_remap`, and what a live ``stream.IncrementalDBG`` grouping
    maps to).  ``remap_headroom`` reserves slack hot/halo slots so later
    ``apply_remap`` calls can re-home group-crossers in place.
    ``track_remap`` keeps the O(E) host bookkeeping those calls patch
    (per-shard src index, slot masters, writable tile planes); default: only
    under ``replicate_hot`` — pass ``False`` for static/benchmark layouts
    that will never be remapped, dropping the host-memory overhead.

    ``stream=True`` builds the STREAMING layout ``repro.dist.stream``
    maintains in O(delta) per batch: per-shard delta buffers (pull side
    owner-partitioned by destination, push side by source), key-sorted
    deletion indexes over the base segments, and — on the ``"ell"`` backend —
    all-ones tombstone bitplanes plus push-side lane positions, so individual
    lanes can be killed or retargeted without repacking.  Implies
    ``track_remap``.
    """
    _check_backend(backend)
    if stream and track_remap is False:
        raise ValueError("stream=True requires the remap bookkeeping "
                         "(track_remap must not be False)")
    if stream:
        track_remap = True
    v = int(ga.in_deg.shape[0])
    d = int(n_shards)
    v_blk = -(-v // d)
    in_src = np.asarray(ga.in_src)
    in_dst = np.asarray(ga.in_dst)
    in_w = np.asarray(ga.in_w)
    out_src = np.asarray(ga.out_src)
    out_dst = np.asarray(ga.out_dst)
    out_w = np.asarray(ga.out_w)
    out_deg = np.asarray(ga.out_deg)
    weighted = not (ga.in_w is ga.out_w)  # unweighted graphs share ONE plane

    hot, hgc = _hot_mask(out_deg, policy, num_hot_groups)
    if hot_override is not None:
        if policy != "replicate_hot":
            raise ValueError("hot_override requires policy='replicate_hot'")
        hot = np.zeros(v, dtype=bool)
        hot[np.asarray(hot_override, dtype=np.int64)] = True
    hot_ids = np.nonzero(hot)[0].astype(np.int32)
    n_hot = int(hot_ids.shape[0])
    hot_cap = (_with_headroom(n_hot, remap_headroom)
               if policy == "replicate_hot" else max(1, n_hot))
    hot_pos = np.full(v, -1, np.int64)
    hot_pos[hot_ids] = np.arange(n_hot)

    owner_of = lambda ids: ids // v_blk

    # ---- pull side: split in-edges by destination owner (dst-sorted) -------
    edge_owner = owner_of(in_dst)
    bounds = np.searchsorted(edge_owner, np.arange(d + 1))

    # halo: per shard, the remote non-hot sources it reads, grouped by owner
    need: list = []  # need[dst_shard][owner] = sorted unique global ids
    for i in range(d):
        srcs = in_src[bounds[i]:bounds[i + 1]]
        remote = srcs[(owner_of(srcs) != i) & (hot_pos[srcs] < 0)]
        uniq = np.unique(remote)
        need.append([uniq[owner_of(uniq) == o] for o in range(d)])
    halo_used = max(1, max((len(ids) for row in need for ids in row),
                           default=1))
    halo_cap = (_with_headroom(halo_used, remap_headroom)
                if policy == "replicate_hot" else halo_used)

    # sender view: send_idx[o, i] = owner-local indices o ships to shard i
    send_idx = np.zeros((d, d, halo_cap), np.int32)
    need_len = np.zeros((d, d), np.int64)
    halo_slots = 0
    for o in range(d):
        for i in range(d):
            ids = need[i][o]
            send_idx[o, i, : len(ids)] = (ids - o * v_blk).astype(np.int32)
            need_len[i, o] = len(ids)
            halo_slots += len(ids)

    # receiver view: edge slots into the [local | hot | halo] value table
    slot_rows, dstl_rows, w_rows = [], [], []
    for i in range(d):
        sl = slice(bounds[i], bounds[i + 1])
        srcs = in_src[sl]
        slots = np.empty(srcs.shape[0], np.int64)
        is_hot = hot_pos[srcs] >= 0
        is_local = (owner_of(srcs) == i) & ~is_hot
        is_remote = ~is_hot & ~is_local
        slots[is_local] = srcs[is_local] - i * v_blk
        slots[is_hot] = v_blk + hot_pos[srcs[is_hot]]
        rem = srcs[is_remote]
        ro = owner_of(rem)
        pos = np.empty(rem.shape[0], np.int64)
        for o in range(d):
            m = ro == o
            pos[m] = np.searchsorted(need[i][o], rem[m])
        slots[is_remote] = v_blk + hot_cap + ro * halo_cap + pos
        slot_rows.append(slots)
        dstl_rows.append(in_dst[sl] - i * v_blk)
        w_rows.append(in_w[sl])

    in_slot = _pad2d(slot_rows, 0, np.int32)
    in_dst_local = _pad2d(dstl_rows, v_blk - 1, np.int32)  # keeps sortedness
    in_w_p = _pad2d(w_rows, 0.0, np.float32)
    e_blk = in_slot.shape[1]
    in_mask = np.zeros((d, e_blk), bool)
    for i in range(d):
        in_mask[i, : bounds[i + 1] - bounds[i]] = True

    # ---- push side: split out-edges by source owner (src-sorted) -----------
    pedge_owner = owner_of(out_src)
    pbounds = np.searchsorted(pedge_owner, np.arange(d + 1))
    srcl_rows, pdst_rows, pw_rows = [], [], []
    for i in range(d):
        sl = slice(pbounds[i], pbounds[i + 1])
        srcl_rows.append(out_src[sl] - i * v_blk)
        pdst_rows.append(out_dst[sl])
        pw_rows.append(out_w[sl])
    out_src_local = _pad2d(srcl_rows, 0, np.int32)
    out_dst_p = _pad2d(pdst_rows, 0, np.int32)
    out_w_p = _pad2d(pw_rows, 0.0, np.float32)
    out_mask = np.zeros(out_src_local.shape, bool)
    for i in range(d):
        out_mask[i, : pbounds[i + 1] - pbounds[i]] = True

    # ---- fused per-shard tiles (backend "ell") ------------------------------
    if track_remap is None:
        track_remap = policy == "replicate_hot"
    pull_tiles = push_tiles = None
    tile_pos = push_pos = None
    table_len = v_blk + hot_cap + d * halo_cap
    if backend == "ell":
        pulled = ell_tiles_sharded(
            [(dstl_rows[i].astype(np.int64), slot_rows[i],
              w_rows[i] if weighted else None) for i in range(d)],
            id_upper=table_len, row_tile=row_tile, width_tile=width_tile,
            with_positions=track_remap, with_alive=stream)
        pull_tiles, tile_pos = pulled if track_remap else (pulled, None)
        pushed = ell_tiles_sharded(
            [(pdst_rows[i].astype(np.int64), srcl_rows[i].astype(np.int64),
              pw_rows[i] if weighted else None) for i in range(d)],
            id_upper=v_blk, row_tile=row_tile, width_tile=width_tile,
            with_positions=stream, with_alive=stream)
        push_tiles, push_pos = pushed if stream else (pushed, None)

    stats = {
        "policy": policy,
        "backend": backend,
        "n_hot": n_hot,
        "hot_frac": n_hot / max(1, v),
        "halo_slots": int(halo_slots),
        "halo_max": int(halo_cap),
        # bytes one pull moves device-to-device (f32 halo payload, padded)
        "halo_bytes_padded": int(d * d * halo_cap * 4),
        "edges_per_shard_max": int(e_blk),
    }
    hot_ids_pad = np.zeros(hot_cap, np.int32)
    hot_ids_pad[:n_hot] = hot_ids
    host = None
    if track_remap:
        shard_srcs = [in_src[bounds[i]:bounds[i + 1]] for i in range(d)]
        # src-sorted edge-position index per shard: apply_remap finds a
        # mover's edges in O(log E + deg) instead of scanning the segment
        src_order = []
        for s in shard_srcs:
            order = np.argsort(s, kind="stable")
            src_order.append((s[order], order))
        host = {
            "in_src": [np.asarray(s) for s in shard_srcs],
            "src_order": src_order,
            "slot": [s.copy() for s in slot_rows],
            "need0": need,                   # original sorted halo id lists
            "need_len": need_len,            # used entries per (i, o)
            "halo_entry": {},                # (i, src) -> appended position
            "send_idx": send_idx,            # master copy
            "hot_ids": hot_ids_pad.copy(),
            "hot_pos": hot_pos,
            "hot_free": list(range(n_hot, hot_cap)),
            "tile_pos": tile_pos,
            "tile_idx": (None if pull_tiles is None
                         else [np.array(t.idx)            # writable copies
                               for t in pull_tiles]),
            "halo_slots": int(halo_slots),
        }
        if stream:
            vp = d * v_blk
            in_dst_rows = [in_dst[bounds[i]:bounds[i + 1]].astype(np.int64)
                           for i in range(d)]
            out_src_rows = [out_src[pbounds[i]:pbounds[i + 1]]
                            .astype(np.int64) for i in range(d)]
            host["stream"] = {
                "weighted": weighted,
                # pull base segments (dst-sorted) + key-sorted (src,dst)
                # deletion index per shard
                "in_dst": in_dst_rows,
                "in_wv": [np.asarray(w, np.float32) for w in w_rows],
                "in_alive": [np.ones(r.shape[0], bool) for r in in_dst_rows],
                "in_key": [_key_index(shard_srcs[i], in_dst_rows[i], vp)
                           for i in range(d)],
                "in_dead": np.zeros(d, np.int64),
                # push base segments (src-partitioned)
                "out_src": out_src_rows,
                "out_dst": [np.asarray(r, np.int64) for r in pdst_rows],
                "out_wv": [np.asarray(w, np.float32) for w in pw_rows],
                "out_alive": [np.ones(r.shape[0], bool)
                              for r in out_src_rows],
                "out_key": [_key_index(out_src_rows[i],
                                       np.asarray(pdst_rows[i], np.int64),
                                       vp) for i in range(d)],
                "out_dead": np.zeros(d, np.int64),
                # per-shard delta buffers (host masters; device copies are
                # rebuilt by dist.stream.sync_delta when dirty)
                "d": [_new_delta_buf(True) for _ in range(d)],
                "p": [_new_delta_buf(False) for _ in range(d)],
                "delta_dirty": True,
                "caps": {"c": 8, "cp": 8, "pr": (0, 0), "pp": (0, 0)},
                "push_tile_pos": push_pos,
                # writable tombstone bitplane masters (backend "ell")
                "pull_alive": (None if pull_tiles is None else
                               [np.ones(tuple(t.idx.shape), np.int8)
                                for t in pull_tiles]),
                "push_alive": (None if push_tiles is None else
                               [np.ones(tuple(t.idx.shape), np.int8)
                                for t in push_tiles]),
                "push_tile_idx": (None if push_tiles is None else
                                  [np.array(t.idx) for t in push_tiles]),
                "push_tile_w": (None if push_tiles is None or not weighted
                                else [np.array(t.w) for t in push_tiles]),
            }
    return place_shards(ShardedGraphArrays(
        n_shards=d, num_vertices=v, v_blk=v_blk, halo_max=halo_cap,
        policy=policy,
        in_slot=in_slot, in_dst_local=in_dst_local, in_w=in_w_p,
        in_mask=in_mask, send_idx=send_idx.copy(), hot_ids=hot_ids_pad,
        out_src_local=out_src_local, out_dst=out_dst_p, out_w=out_w_p,
        out_mask=out_mask,
        in_deg=np.asarray(ga.in_deg), out_deg=np.asarray(ga.out_deg),
        backend=backend, hot_cap=hot_cap, hot_group_count=hgc,
        weighted=weighted, row_tile=row_tile, width_tile=width_tile,
        interpret=interpret,
        pull_tiles=pull_tiles, push_tiles=push_tiles,
        stats=stats, host=host,
    ))


def _check_backend(backend: str) -> str:
    """Resolve a backend name through the engine's single registry, then
    narrow to what the sharded engine implements."""
    apps_engine.resolve_backend(backend)  # clear error on unknown names
    if backend not in SHARDED_BACKENDS:
        raise ValueError(
            f"backend {backend!r} is not supported by the sharded engine; "
            f"choose one of {'|'.join(SHARDED_BACKENDS)}")
    return backend


def _resolve_backend(sg: ShardedGraphArrays, backend: Optional[str]) -> str:
    backend = _check_backend(backend or sg.backend)
    if backend == "ell" and sg.pull_tiles is None:
        raise ValueError(
            "sharded ELL backend requires shard_graph(..., backend='ell') "
            "(per-shard tiles were not packed)")
    return backend


def _flatten_tiles(tiles) -> Tuple[list, list]:
    """EllTileGroups -> flat arg list + per-group (has_w, has_alive) meta
    (shard_map needs positional array args to split on the leading shard
    dim)."""
    args, meta = [], []
    for t in tiles:
        args += [t.rows, t.idx, t.deg]
        if t.w is not None:
            args.append(t.w)
        if t.alive is not None:
            args.append(t.alive)
        meta.append((t.w is not None, t.alive is not None))
    return args, meta


def _unflatten_tiles(flat, meta):
    out, i = [], 0
    for has_w, has_alive in meta:
        rows, idx, deg = flat[i:i + 3]
        i += 3
        w = flat[i] if has_w else None
        i += int(has_w)
        alive = flat[i] if has_alive else None
        i += int(has_alive)
        out.append((rows, idx, deg, w, alive))
    return out


def _pad_prop(sg: ShardedGraphArrays, prop: jnp.ndarray) -> jnp.ndarray:
    return jnp.pad(prop, (0, sg.v_pad - sg.num_vertices))


def edge_map_pull_sharded(sg: ShardedGraphArrays, prop: jnp.ndarray, mesh, *,
                          reduce: str = "sum", use_weights: bool = False,
                          neutral: Optional[float] = None,
                          backend: Optional[str] = None) -> jnp.ndarray:
    """dst <- REDUCE over in-edges of f(prop[src]), sharded over ``mesh``.

    Matches single-device :func:`repro.apps.engine.edge_map_pull` numerics
    (min/max bitwise; sum to fp association on the fused backend).
    ``prop``: (V,) global; returns (V,) global.  The only cross-device traffic
    is the cold-halo all_to_all (+ the small hot-table gather), identical for
    both backends; ``backend=None`` uses the layout's own.
    """
    backend = _resolve_backend(sg, backend)
    hook = apps_engine.get_edge_map_hook()
    if hook is not None:
        hook.on_pass(sg, "pull", prop, {"reduce": reduce,
                                        "use_weights": use_weights})
    red = "max" if reduce == "or" else reduce
    if neutral is None:
        # pad slots and empty rows take the identity of the REWRITTEN
        # reduction ("or" lowers to max), exactly like the flat engine's
        # empty segment_max fills — padding can never leak a value
        neutral = reduce_identity(red)
    v_blk = sg.v_blk
    d = sg.n_shards
    prop_blocks = _pad_prop(sg, prop).reshape(d, v_blk)
    hot_tab = _pad_prop(sg, prop)[sg.hot_ids]  # replicated hot panel

    def exchange(local, send_idx):
        halo = local[send_idx[0]]                      # (D, halo_max)
        if d > 1:
            halo = jax.lax.all_to_all(halo, AXIS, split_axis=0, concat_axis=0)
        return halo

    delta = sg.delta
    if backend == "flat":
        dargs = () if delta is None else (delta.slot, delta.dstl, delta.w,
                                          delta.alive)

        def ranked(blocks, hot, send_idx, slot, dstl, w, mask, *dflat):
            local = blocks[0]
            halo = exchange(local, send_idx)
            table = jnp.concatenate([local, hot, halo.reshape(-1)])
            vals = table[slot[0]]
            if use_weights:
                vals = vals + w[0]
            vals = jnp.where(mask[0], vals, jnp.asarray(neutral, vals.dtype))
            seg = dict(num_segments=v_blk, indices_are_sorted=True)
            if reduce == "sum":
                out = jax.ops.segment_sum(vals, dstl[0], **seg)
            elif reduce == "min":
                out = jax.ops.segment_min(vals, dstl[0], **seg)
            elif reduce in ("max", "or"):
                out = jax.ops.segment_max(vals, dstl[0], **seg)
            else:
                raise ValueError(reduce)
            if dflat:
                # streaming delta segment: same gather table, scatter-combine
                # (delta destinations duplicate base rows)
                dslot, ddstl, dw, dalive = dflat
                dv = table[dslot[0]]
                if use_weights:
                    dv = dv + dw[0]
                dv = jnp.where(dalive[0], dv, jnp.asarray(neutral, dv.dtype))
                out = _scatter_combine(out, ddstl[0], dv, red)
            return out[None]

        a = P(AXIS)
        fn = jax.shard_map(
            ranked, mesh=mesh,
            in_specs=(a, P(), a, a, a, a, a) + (a,) * len(dargs),
            out_specs=a, check_vma=False)
        with jax.named_scope("dist.edge_map_pull"):
            out = fn(prop_blocks, hot_tab, sg.send_idx, sg.in_slot,
                     sg.in_dst_local, sg.in_w, sg.in_mask, *dargs)
        return out.reshape(-1)[: sg.num_vertices]

    # fused per-shard DBG-ELL path: one kernel pass per width class over the
    # same gather table, then an O(v_blk) combine — no O(E) intermediates
    identity = reduce_identity(red)
    tile_args, meta = _flatten_tiles(sg.pull_tiles)
    dtiles = () if delta is None or delta.pull_tiles is None \
        else delta.pull_tiles
    dtile_args, dmeta = _flatten_tiles(dtiles)
    n_base = len(tile_args)

    def ranked_ell(blocks, hot, send_idx, *flat_tiles):
        local = blocks[0]
        halo = exchange(local, send_idx)
        table = jnp.concatenate([local, hot, halo.reshape(-1)])
        out = jnp.full((v_blk,), identity, table.dtype)
        groups = (_unflatten_tiles(flat_tiles[:n_base], meta)
                  + _unflatten_tiles(flat_tiles[n_base:], dmeta))
        for rows, idx, deg, w, alive in groups:
            r_pad, w_pad = idx.shape[1], idx.shape[2]
            y = ell_edge_map_pallas(
                table, idx[0], deg[0], reduce=red,
                w=w[0] if (use_weights and w is not None) else None,
                unit_weights=use_weights,
                alive=alive[0] if alive is not None else None,
                neutral=neutral, identity=identity,
                row_tile=_tile_of(r_pad, sg.row_tile),
                width_tile=_tile_of(w_pad, sg.width_tile),
                interpret=sg.interpret)
            out = _scatter_combine(out, rows[0], y, red)
        return out[None]

    a = P(AXIS)
    fn = jax.shard_map(
        ranked_ell, mesh=mesh,
        in_specs=(a, P(), a) + (a,) * (n_base + len(dtile_args)),
        out_specs=a, check_vma=False)
    with jax.named_scope("dist.edge_map_pull"):
        out = fn(prop_blocks, hot_tab, sg.send_idx, *tile_args, *dtile_args)
    return out.reshape(-1)[: sg.num_vertices]


def edge_map_push_sharded(sg: ShardedGraphArrays, prop: jnp.ndarray, mesh, *,
                          reduce: str = "sum", use_weights: bool = False,
                          init: Optional[jnp.ndarray] = None,
                          backend: Optional[str] = None) -> jnp.ndarray:
    """dst <- REDUCE over pushes from sources, sharded over ``mesh``.

    Sources read their owner-local property block (no input communication);
    the cross-device reduction of partial destination vectors is the
    collective (``psum_scatter`` for sum, ``pmin``/``pmax`` otherwise).  On
    the ``"ell"`` backend the per-shard partial is computed as the transposed
    pull over dst-grouped tiles — no scatter at all before the collective.
    """
    backend = _resolve_backend(sg, backend)
    hook = apps_engine.get_edge_map_hook()
    if hook is not None:
        hook.on_pass(sg, "push", prop, {"reduce": reduce,
                                        "use_weights": use_weights})
    v_blk = sg.v_blk
    v_pad = sg.v_pad
    d = sg.n_shards
    prop_blocks = _pad_prop(sg, prop).reshape(d, v_blk)
    fill = reduce_identity(reduce)  # untouched rows match the 1-device init

    def collect(partial):
        """Combine per-shard (v_pad,) partials into each shard's own block."""
        if reduce == "sum":
            if d > 1:
                return jax.lax.psum_scatter(partial, AXIS,
                                            scatter_dimension=0, tiled=True)
            return partial
        if d > 1:
            partial = (jax.lax.pmin if reduce == "min"
                       else jax.lax.pmax)(partial, AXIS)
        i = jax.lax.axis_index(AXIS)
        return jax.lax.dynamic_slice_in_dim(partial, i * v_blk, v_blk)

    delta = sg.delta
    red = "max" if reduce == "or" else reduce
    if backend == "flat":
        dargs = () if delta is None else (delta.p_srcl, delta.p_dst,
                                          delta.p_w, delta.p_alive)

        def ranked(blocks, srcl, dst, w, mask, *dflat):
            local = blocks[0]
            vals = local[srcl[0]]
            if use_weights:
                vals = vals + w[0]
            vals = jnp.where(mask[0], vals, jnp.asarray(fill, vals.dtype))
            partial = jnp.full((v_pad,), fill, vals.dtype)
            if reduce == "sum":
                partial = partial.at[dst[0]].add(vals)
            elif reduce == "min":
                partial = partial.at[dst[0]].min(vals)
            elif reduce in ("max", "or"):
                partial = partial.at[dst[0]].max(vals)
            else:
                raise ValueError(reduce)
            if dflat:
                ps, pd, pw, pa = dflat
                dv = local[ps[0]]
                if use_weights:
                    dv = dv + pw[0]
                dv = jnp.where(pa[0], dv, jnp.asarray(fill, dv.dtype))
                partial = _scatter_combine(partial, pd[0], dv, red)
            return collect(partial)[None]

        a = P(AXIS)
        fn = jax.shard_map(ranked, mesh=mesh,
                           in_specs=(a, a, a, a, a) + (a,) * len(dargs),
                           out_specs=a, check_vma=False)
        with jax.named_scope("dist.edge_map_push"):
            out = fn(prop_blocks, sg.out_src_local, sg.out_dst, sg.out_w,
                     sg.out_mask, *dargs)
    else:
        identity = reduce_identity(red)  # masked lanes can never win a max
        tile_args, meta = _flatten_tiles(sg.push_tiles)
        dtiles = () if delta is None or delta.push_tiles is None \
            else delta.push_tiles
        dtile_args, dmeta = _flatten_tiles(dtiles)
        n_base = len(tile_args)

        def ranked_ell(blocks, *flat_tiles):
            local = blocks[0]
            partial = jnp.full((v_pad,), fill, local.dtype)
            groups = (_unflatten_tiles(flat_tiles[:n_base], meta)
                      + _unflatten_tiles(flat_tiles[n_base:], dmeta))
            for rows, idx, deg, w, alive in groups:
                r_pad, w_pad = idx.shape[1], idx.shape[2]
                y = ell_edge_map_pallas(
                    local, idx[0], deg[0], reduce=red,
                    w=w[0] if (use_weights and w is not None) else None,
                    unit_weights=use_weights,
                    alive=alive[0] if alive is not None else None,
                    neutral=fill, identity=identity,
                    row_tile=_tile_of(r_pad, sg.row_tile),
                    width_tile=_tile_of(w_pad, sg.width_tile),
                    interpret=sg.interpret)
                partial = _scatter_combine(partial, rows[0], y, red)
            return collect(partial)[None]

        a = P(AXIS)
        fn = jax.shard_map(ranked_ell, mesh=mesh,
                           in_specs=(a,) + (a,) * (n_base + len(dtile_args)),
                           out_specs=a, check_vma=False)
        with jax.named_scope("dist.edge_map_push"):
            out = fn(prop_blocks, *tile_args, *dtile_args)

    out = out.reshape(-1)[: sg.num_vertices]
    if init is not None:
        if reduce == "sum":
            out = init + out
        elif reduce == "min":
            out = jnp.minimum(init, out)
        else:
            out = jnp.maximum(init, out)
    return out.astype(prop.dtype)


# ---------------------------------------------------------------------------
# per-iteration HBM byte model (the BENCH_dist fused-vs-flat column)
# ---------------------------------------------------------------------------

def edge_map_bytes_sharded(sg: ShardedGraphArrays, *, mode: str = "pull",
                           use_weights: bool = False,
                           backend: Optional[str] = None) -> int:
    """Analytic single-pass HBM bytes of one sharded edge map, PER SHARD.

    Mirrors ``benchmarks.edge_map_perf._flat_model_bytes`` for the flat path
    (idx read + table gather + edge-value materialize, then the segment /
    scatter pass re-reads values + owner ids and writes the block) and the
    kernels' ``pl.CostEstimate`` accounting for the fused path (tile planes +
    gather-table residency, one pass, no O(E) intermediates).  The halo
    all_to_all payload is identical on both backends and excluded.
    """
    backend = _resolve_backend(sg, backend)
    e = int(sg.in_slot.shape[1] if mode == "pull" else sg.out_dst.shape[1])
    table = sg.table_len if mode == "pull" else sg.v_blk
    out_len = sg.v_blk if mode == "pull" else sg.v_pad
    delta = sg.delta
    if backend == "flat":
        b = e * 4 + e * 4 + e * 4      # slot ids, table gather, vals write
        if use_weights:
            b += e * 4 + 2 * e * 4     # w plane read + vals rmw
        b += e * 1 + 2 * e * 4         # pad mask + vals rmw
        b += e * 4 + e * 4 + out_len * 4  # reduce/scatter pass + out write
        b += table * 4                 # gather-table materialize
        if delta is not None:
            c = int(delta.slot.shape[1] if mode == "pull"
                    else delta.p_dst.shape[1])
            # slot/src read + gather + alive byte + dst read + scatter rmw
            b += c * 4 + c * 4 + c * 1 + c * 4 + 2 * c * 4
            if use_weights:
                b += c * 4
        return b
    tiles = sg.pull_tiles if mode == "pull" else sg.push_tiles
    dtiles = ()
    if delta is not None:
        dtiles = (delta.pull_tiles if mode == "pull"
                  else delta.push_tiles) or ()
    total = out_len * 4                # combine write
    for t in tuple(tiles) + tuple(dtiles):
        r_pad, w_pad = int(t.idx.shape[1]), int(t.idx.shape[2])
        total += edge_map_tile_bytes(
            r_pad, w_pad,
            weighted=use_weights and t.w is not None,
            frontier=False, alive=t.alive is not None, init=False,
            idx_itemsize=t.idx.dtype.itemsize)
    return total


# ---------------------------------------------------------------------------
# shard-aware update routing (stream.RemapDelta -> patched layout)
# ---------------------------------------------------------------------------

def _halo_slot(sg: ShardedGraphArrays, i: int, src: int,
               exc=RemapOverflow) -> int:
    """Table slot of remote cold ``src`` on shard ``i`` (stable allocation).

    Build-time halo members resolve through the sorted ``need0`` lists; later
    arrivals (remap movers, streamed edge inserts) append into the reserved
    headroom and are memoized in ``halo_entry`` so every (shard, src) pair
    gets exactly one slot.  Raises ``exc`` when the halo segment for the
    owning shard pair is full (:class:`RemapOverflow` from apply_remap,
    :class:`HaloOverflow` from the streaming delta router).
    """
    host = sg.host
    v_blk, hot_cap, halo_cap = sg.v_blk, sg.hot_cap, sg.halo_max
    o = src // v_blk
    base = v_blk + hot_cap + o * halo_cap
    lst = host["need0"][i][o]
    p = np.searchsorted(lst, src)
    if p < len(lst) and lst[p] == src:
        return base + int(p)
    key = (i, src)
    p = host["halo_entry"].get(key)
    if p is None:
        p = int(host["need_len"][i, o])
        if p >= halo_cap:
            raise exc(
                f"halo capacity {halo_cap} exhausted for shard pair "
                f"({o}->{i})")
        host["need_len"][i, o] = p + 1
        host["send_idx"][o, i, p] = src - o * v_blk
        host["halo_entry"][key] = p
        host["halo_slots"] += 1
    return base + p


def _retarget_delta_slots(sg: ShardedGraphArrays, movers: np.ndarray) -> None:
    """Recompute the pull-delta slots of ``movers``' streamed edges (host
    masters only — the device delta segment is rebuilt at the next
    ``dist.stream.sync_delta``), so a regroup remap and the batch's edge
    deltas land in one patch."""
    host = sg.host
    st = host.get("stream")
    if st is None:
        return
    hot_pos = host["hot_pos"]
    v_blk = sg.v_blk
    for i in range(sg.n_shards):
        db = st["d"][i]
        n = db["n"]
        if n == 0:
            continue
        srcs_d = db["src"][:n]
        m = np.isin(srcs_d, movers) & db["alive"][:n]
        if not m.any():
            continue
        src_t = srcs_d[m]
        new_slots = np.empty(src_t.shape[0], np.int64)
        hp = hot_pos[src_t]
        m_hot = hp >= 0
        new_slots[m_hot] = v_blk + hp[m_hot]
        m_local = ~m_hot & (src_t // v_blk == i)
        new_slots[m_local] = src_t[m_local] - i * v_blk
        m_halo = ~m_hot & ~m_local
        if m_halo.any():
            u, inv = np.unique(src_t[m_halo], return_inverse=True)
            u_slots = np.array([_halo_slot(sg, i, int(s)) for s in u],
                               np.int64)
            new_slots[m_halo] = u_slots[inv]
        db["slot"][: n][m] = new_slots
        st["delta_dirty"] = True


def apply_remap(sg: ShardedGraphArrays, delta) -> ShardedGraphArrays:
    """Re-home ONLY the vertices whose degree group changed.

    ``delta`` is a ``stream.RemapDelta`` (or anything with ``moved`` /
    ``new_group`` arrays; merge several with ``RemapDelta.merge`` first).  A
    vertex whose new group is hot (``new_group < sg.hot_group_count``) moves
    into the replicated hot table; one that left the hot groups moves back to
    owner-local / halo slots.  Only the edge slots (and, on the ``"ell"``
    backend, the individual tile lanes) referencing the movers are patched —
    the rest of the layout, including every untouched shard row, is reused
    as-is.  Raises :class:`RemapOverflow` when the reserved hot/halo headroom
    is exhausted; the caller should then fall back to a full
    :func:`shard_graph` (which is what this routine replaces in the common,
    small-drift case).

    The returned layout SHARES host bookkeeping with ``sg`` (patching moves
    it forward); treat the input as consumed.
    """
    if sg.policy != "replicate_hot":
        return sg  # grouping does not affect a pure partition layout
    host = sg.host
    if host is None:
        raise ValueError("layout carries no remap bookkeeping "
                         "(shard_graph(..., track_remap=True))")
    if getattr(delta, "spec_rebuilt", False):
        # the regrouper re-derived its boundary spec: the delta's group ids
        # are numbered under the NEW spec while hot_group_count was counted
        # under the layout's build-time spec — comparing them would mis-home
        # vertices.  Force the full re-shard the caller already handles.
        raise RemapOverflow(
            "grouping spec was rebuilt (boundary drift) — group ids are not "
            "comparable to this layout's hot_group_count; re-shard with "
            "hot_override=<live hot set>")
    moved = np.asarray(delta.moved, dtype=np.int64).ravel()
    new_group = np.asarray(delta.new_group, dtype=np.int64).ravel()
    if moved.size == 0:
        return sg
    hot_pos = host["hot_pos"]
    wants_hot = new_group < sg.hot_group_count
    newly_hot = moved[wants_hot & (hot_pos[moved] < 0)]
    newly_cold = moved[~wants_hot & (hot_pos[moved] >= 0)]
    if newly_hot.size == 0 and newly_cold.size == 0:
        return sg

    d, v_blk, v = sg.n_shards, sg.v_blk, sg.num_vertices
    hot_cap, halo_cap = sg.hot_cap, sg.halo_max
    free = host["hot_free"]
    if newly_hot.size > len(free):
        raise RemapOverflow(
            f"{newly_hot.size} vertices turned hot but only {len(free)} "
            f"reserved hot slots remain (cap {hot_cap})")

    # allocate hot slots; release the cold movers' slots afterwards so one
    # delta cannot hand a slot to two owners mid-patch
    hot_slot_of = np.full(v, -1, np.int64)
    for vid in newly_hot.tolist():
        p = free.pop()
        hot_slot_of[vid] = p
        hot_pos[vid] = p
        host["hot_ids"][p] = vid

    send_master = host["send_idx"]
    dirty_shards: List[int] = []
    dirty_rows: List[np.ndarray] = []
    dirty_tiles: Dict[int, set] = {}
    e_blk = int(sg.in_slot.shape[1])
    movers = np.concatenate([newly_hot, newly_cold])
    for i in range(d):
        srcs = host["in_src"][i]
        srcs_sorted, order = host["src_order"][i]
        lo = np.searchsorted(srcs_sorted, movers, "left")
        hi = np.searchsorted(srcs_sorted, movers, "right")
        if not np.any(hi > lo):
            continue
        touched = np.concatenate(
            [order[a:b] for a, b in zip(lo, hi) if b > a])
        if touched.size == 0:
            continue
        # vectorized retarget: per-edge work is pure numpy; only NEW halo
        # entries (one per unique (shard, src) pair) allocate sequentially
        slots = host["slot"][i]
        src_t = srcs[touched]
        new_slots = np.empty(touched.shape[0], np.int64)
        m_hot = hot_slot_of[src_t] >= 0
        new_slots[m_hot] = v_blk + hot_slot_of[src_t[m_hot]]
        m_local = ~m_hot & (src_t // v_blk == i)
        new_slots[m_local] = src_t[m_local] - i * v_blk
        m_halo = ~m_hot & ~m_local
        if m_halo.any():
            u, inv = np.unique(src_t[m_halo], return_inverse=True)
            u_slots = np.array([_halo_slot(sg, i, int(s)) for s in u],
                               np.int64)
            new_slots[m_halo] = u_slots[inv]
        slots[touched] = new_slots
        if host["tile_pos"] is not None:
            pos = host["tile_pos"][i][touched]
            for c in np.unique(pos[:, 0]):
                m = pos[:, 0] == c
                host["tile_idx"][c][i, pos[m, 1], pos[m, 2]] = new_slots[m]
                dirty_tiles.setdefault(int(c), set()).add(i)
        row = np.zeros(e_blk, np.int32)
        row[: slots.shape[0]] = slots
        dirty_shards.append(i)
        dirty_rows.append(row)

    # release the hot slots the cold movers held (ids stay in the table —
    # nothing references them, and the gather just reads a stale value)
    for vid in newly_cold.tolist():
        free.append(int(hot_pos[vid]))
        hot_pos[vid] = -1

    # streamed (not-yet-compacted) edges of the movers re-home too, so the
    # regroup remap and the edge deltas land in ONE patch
    _retarget_delta_slots(sg, movers)

    in_slot = sg.in_slot
    if dirty_shards:
        in_slot = in_slot.at[jnp.asarray(dirty_shards)].set(
            jnp.asarray(np.stack(dirty_rows)))
    pull_tiles = sg.pull_tiles
    if pull_tiles is not None and dirty_tiles:
        new_tiles = list(pull_tiles)
        for c, shards in dirty_tiles.items():
            idx = new_tiles[c].idx
            rows = sorted(shards)
            idx = idx.at[jnp.asarray(rows)].set(
                jnp.asarray(host["tile_idx"][c][rows]))
            new_tiles[c] = new_tiles[c]._replace(idx=idx)
        pull_tiles = tuple(new_tiles)

    stats = dict(sg.stats)
    stats["halo_slots"] = int(host["halo_slots"])
    stats["n_hot"] = int(np.sum(hot_pos >= 0))
    stats["hot_frac"] = stats["n_hot"] / max(1, v)
    return place_shards(dataclasses.replace(
        sg,
        in_slot=in_slot,
        send_idx=send_master.copy(),
        hot_ids=host["hot_ids"].copy(),
        pull_tiles=pull_tiles,
        stats=stats,
    ))


# ---------------------------------------------------------------------------
# sharded PageRank (the apps/ wiring target; benchmarked by dist_scaling)
# ---------------------------------------------------------------------------

_PR_CACHE: Dict[Tuple[Any, ...], Any] = {}
_PR_CACHE_MAX = 32


def pagerank_sharded(sg: ShardedGraphArrays, mesh, *, damping: float = 0.85,
                     max_iters: int = 64, tol: float = 1e-7):
    """Sharded PageRank matching :func:`repro.apps.pagerank.pagerank`.

    Runs on whichever edge-map backend ``sg`` was built with — the loop body
    is backend-agnostic.  Compiles once per (graph, mesh, hyperparams) —
    repeat calls (benchmark iterations) reuse the cached executable.  The
    cache is identity-keyed and bounded: oldest entries (which pin their
    graph's device arrays) are evicted past ``_PR_CACHE_MAX`` distinct
    configurations.
    """
    key = (id(sg), id(mesh), sg.policy, sg.backend, damping, max_iters, tol)
    if key not in _PR_CACHE:
        while len(_PR_CACHE) >= _PR_CACHE_MAX:
            _PR_CACHE.pop(next(iter(_PR_CACHE)))
        sg0 = sg

        def run(arrs):
            sgt = dataclasses.replace(sg0, **arrs)
            v = sg0.num_vertices
            out_deg = jnp.maximum(1, sgt.out_deg).astype(jnp.float32)
            dangling = (sgt.out_deg == 0).astype(jnp.float32)

            def cond(state):
                _, it, err = state
                return jnp.logical_and(it < max_iters, err > tol)

            def body(state):
                rank, it, _ = state
                contrib = rank / out_deg
                pulled = edge_map_pull_sharded(sgt, contrib, mesh)
                dangling_mass = jnp.sum(rank * dangling) / v
                new = (1.0 - damping) / v + damping * (pulled + dangling_mass)
                err = jnp.sum(jnp.abs(new - rank))
                return new, it + 1, err

            rank0 = jnp.full((v,), 1.0 / v, jnp.float32)
            return jax.lax.while_loop(cond, body, (rank0, 0, jnp.inf))

        _PR_CACHE[key] = jax.jit(run)
    with obs_trace.span("dist.pagerank", cat="dist", backend=sg.backend,
                        shards=sg.n_shards) as sp:
        rank, iters, _ = jax.block_until_ready(_PR_CACHE[key](_sg_arrays(sg)))
        sp.add(iters=int(iters))
    hook = apps_engine.get_edge_map_hook()
    if hook is not None and hasattr(hook, "record_iters"):
        hook.record_iters("pagerank_sharded", np.asarray([int(iters)]))
    return rank, iters
