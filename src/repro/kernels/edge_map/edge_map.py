"""Pallas TPU kernel: fused edge map over DBG-ELL tiles — kernel family K5.

Generalizes ``csr_spmv.ell_spmv_pallas`` from sum-only SpMV into the engine's
full edge-map primitive: one pass over a group's ELL tiles fuses the four
separate O(E) HBM passes the flat engine lowers to (gather ``prop[src]`` →
weight add → frontier mask → segment reduce / scatter) into one XLA gather
and a single kernel:

  * ``reduce`` in {sum, min, max} — min is SSSP's relaxation, max is the
    Radii/BC reachability OR (over {0,1} lanes);
  * additive edge weights ride in as an optional (TR, TW) plane, or — when the
    graph is unweighted — as a constant ``+1`` folded into the kernel with NO
    plane read at all (half the edge bytes of the weighted path);
  * the frontier is a (V,) byte vector gathered alongside ``x`` —
    inactive sources contribute the caller's ``neutral``;
  * padding lanes (ELL slots past the row's true degree) contribute the
    reduction's exact identity element, so results match the flat engine's
    segment reductions bit-for-bit for min/max;
  * ``init_rows`` seeds the accumulator for push-style relaxation
    (``dst <- min(init[dst], ...)``), fusing the flat path's separate
    ``init.at[dst].min`` scatter into the same pass;
  * an optional alive bitplane masks tombstoned edges (the ``repro.stream``
    base segment) without rebuilding tiles per batch;
  * the property may be a 2D **plane** ``(V, K)`` — K queries (personalized-
    PageRank vectors, SSSP roots, BFS sources) ride one pass, amortizing the
    tile/idx/frontier traffic across all K lanes (the ``repro.serve`` batched
    serving path); the frontier may then be per-query ``(V, K)`` so finished
    queries stop contributing work.

Push mode needs no scatter at all: a push with a reduction into destinations
is the pull of the transposed direction, so the same in-direction tiles serve
both primitives — the irregular-WRITE mode of the paper's §VI-C becomes a
regular gather over the very layout DBG builds.

Layout (what Mosaic compiles): the irregular gathers ``x[idx]`` and
``frontier[idx]`` run in XLA, which writes them as lane-dense ``(K, W, R)``
blocks — rows on the 128-wide lane axis, ELL slots on sublanes, K query
lanes leading.  The kernel fuses everything after the gather: weight add,
frontier mask, padding/alive mask, init seeding and the reduction over the
slot axis.  Grid (row blocks, width tiles); y is revisited across width
tiles (index map ignores the width coordinate, init on the first width
step).  Degrees and row results are ``(1, R)`` / ``(K, R)``, so no operand
has a minor dimension narrower than a lane.  Whether to gather in the kernel
instead (hot groups pinned in VMEM, cold sources by DMA) is a measured
choice for later; the attached ``pl.CostEstimate`` counts the gather's
traffic too.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ..mode import interpret_mode

__all__ = ["REDUCE_IDENTITY", "reduce_identity", "ell_edge_map_pallas"]

REDUCE_IDENTITY = {"sum": 0.0, "min": float("inf"), "max": float("-inf")}


def reduce_identity(reduce: str) -> float:
    """Identity element of an engine reduction — THE canonical table.

    Every layer that pads (ELL lanes, halo slots, delta buffers) must fill
    with this exact value so padding can never leak into a combiner: engine
    fills, the sharded pmin/pmax partials, stream tombstone masking and the
    packed slot tables all resolve through here.  ``"or"`` is the engine's
    max over {0,1} reachability lanes; its identity is 0 (no bit set).
    """
    if reduce == "or":
        return 0.0
    return REDUCE_IDENTITY[reduce]




def _make_kernel(reduce: str, has_w: bool, unit_weights: bool,
                 has_frontier: bool, has_alive: bool, has_init: bool,
                 neutral: float, identity: float):
    """Build the fused kernel for one static configuration of the edge map.

    Every operand is lane-dense over rows: a ``(K, TW, TR)`` block of
    gathered values (K query lanes, TW ELL slots on sublanes, TR rows on
    lanes), ``(TW, TR)`` tile planes, ``(1, TR)`` degrees and ``(K, TR)``
    row results.  The reduction runs over the slot (sublane) axis."""

    def kernel(*refs):
        vals_ref, deg_ref = refs[:2]
        pos = 2
        w_ref = fr_ref = al_ref = init_ref = None
        if has_w:
            w_ref = refs[pos]
            pos += 1
        if has_frontier:
            fr_ref = refs[pos]
            pos += 1
        if has_alive:
            al_ref = refs[pos]
            pos += 1
        if has_init:
            init_ref = refs[pos]
            pos += 1
        y_ref = refs[pos]
        wi = pl.program_id(1)

        @pl.when(wi == 0)
        def _init():
            if has_init:
                y_ref[...] = init_ref[...]
            else:
                y_ref[...] = jnp.full_like(y_ref, identity)

        vals = vals_ref[...]  # (K, TW, TR): x[idx] for all K query lanes
        _, tw, tr = vals.shape
        if has_w:
            vals = vals + w_ref[...][None]  # weights shared across lanes
        elif unit_weights:
            vals = vals + jnp.asarray(1.0, vals.dtype)  # no plane read
        if has_frontier:
            # (1, TW, TR) shared or (K, TW, TR) per-query source frontier
            # int8 in HBM; compared as int32 (v5e has no int8 vector compare)
            vals = jnp.where(fr_ref[...].astype(jnp.int32) > 0, vals,
                             neutral)
        cols = jax.lax.broadcasted_iota(jnp.int32, (tw, tr), 0) + wi * tw
        valid = cols < deg_ref[...]  # ELL padding lanes
        if has_alive:
            valid = jnp.logical_and(valid,
                                    al_ref[...].astype(jnp.int32) > 0)
        vals = jnp.where(valid[None], vals, identity)
        if reduce == "sum":
            y_ref[...] += jnp.sum(vals, axis=1)
        elif reduce == "min":
            y_ref[...] = jnp.minimum(y_ref[...], jnp.min(vals, axis=1))
        else:
            y_ref[...] = jnp.maximum(y_ref[...], jnp.max(vals, axis=1))

    return kernel


def edge_map_tile_bytes(r_pad: int, w_pad: int, *,
                        weighted: bool, frontier: bool, alive: bool,
                        init: bool, idx_itemsize: int = 4,
                        plane_k: int = 1,
                        frontier_planar: bool = False) -> int:
    """HBM bytes of one fused tile call: the XLA gather plus the kernel.

    The gather reads the idx plane and one property element per slot and
    lane, and writes the ``(K, W_pad, R_pad)`` value block the kernel then
    reads (so each slot-lane costs three 4-byte moves); a frontier rides the
    same way as int8.  The kernel adds the weight / alive planes, degrees,
    optional init rows and the output.  ``plane_k`` is the batched-query
    lane count: value/frontier/init/output bytes scale with K while the tile
    structure (idx/w/alive/deg) is read ONCE for all K lanes — the
    amortization ``repro.serve`` banks on.  ``frontier_planar`` marks a
    per-query (V, K) frontier vs one shared (V,) vector.
    """
    slots = r_pad * w_pad
    b = slots * idx_itemsize  # idx plane (minimal-width ids)
    b += 3 * slots * 4 * plane_k  # gather read, value write, kernel read
    if frontier:
        b += 3 * slots * (plane_k if frontier_planar else 1)  # int8
    if weighted:
        b += slots * 4  # w plane
    if alive:
        b += slots  # int8 alive plane
    b += r_pad * 4  # deg
    if init:
        b += r_pad * 4 * plane_k
    b += r_pad * 4 * plane_k  # y
    return b


#: Lane width of a TPU vector register: row blocks are a multiple of it.
_LANES = 128


def _row_block(r: int, row_tile: int) -> int:
    """Rows per grid step on the lane axis: the whole (8-aligned) row dim
    when it is below one lane width, else ``row_tile`` rounded up to a
    multiple of 128.  The last block may overhang ``r``: its extra lanes
    reduce garbage that is never written back."""
    if r <= _LANES:
        return r
    return -(-row_tile // _LANES) * _LANES


def ell_edge_map_pallas(
    x: jnp.ndarray,
    idx: jnp.ndarray,
    deg: jnp.ndarray,
    *,
    reduce: str = "sum",
    w: Optional[jnp.ndarray] = None,
    unit_weights: bool = False,
    frontier: Optional[jnp.ndarray] = None,
    alive: Optional[jnp.ndarray] = None,
    init_rows: Optional[jnp.ndarray] = None,
    neutral: float = 0.0,
    identity: Optional[float] = None,
    row_tile: int = 64,
    width_tile: int = 128,
    interpret: Optional[bool] = None,
) -> jnp.ndarray:
    """y (R,) = REDUCE over valid lanes of masked(x[idx] (+ w)) [seeded by init].

    ``idx``/``deg`` as in the ELL packers (R % row_tile == 0, W % width_tile
    == 0; ops.py pads).  ``frontier`` is a (V,) vector (nonzero == active
    source); ``alive`` an optional (R, W) bitplane.  ``identity`` defaults to
    the reduction's identity — integer-sourced callers pass a finite one.

    Batched mode: ``x`` may be a (V, K) plane, in which case ``y`` is (R, K),
    ``init_rows`` (when given) is (R, K), and ``frontier`` may be either the
    shared (V,) vector or a per-query (V, K) plane — K queries share one pass
    over the tile structure.

    ``interpret=None`` compiles the kernel on a TPU and interprets it
    elsewhere (:func:`repro.kernels.mode.interpret_mode`).
    """
    if reduce not in REDUCE_IDENTITY:
        raise ValueError(reduce)
    r, width = idx.shape
    assert r % row_tile == 0 and width % width_tile == 0, (
        idx.shape, row_tile, width_tile)
    if identity is None:
        identity = REDUCE_IDENTITY[reduce]
    planar = x.ndim == 2
    # lane-dense transposed views: K property lanes lead, rows go on lanes
    xt = x.T if planar else x[None, :]  # (K, V)
    k = xt.shape[0]
    idx_t = idx.T.astype(jnp.int32)  # (W, R); storage may be minimal-width
    rb = _row_block(r, row_tile)
    grid = (pl.cdiv(r, rb), width // width_tile)
    block = (k, width_tile, rb)
    plane_spec = pl.BlockSpec((width_tile, rb), lambda i, j: (j, i))
    row_spec = pl.BlockSpec((k, rb), lambda i, j: (0, i))

    with jax.named_scope("edge_map.gather"):  # the gather runs in XLA
        args = [xt[:, idx_t], deg.reshape(1, r)]
    in_specs = [pl.BlockSpec(block, lambda i, j: (0, j, i)),
                pl.BlockSpec((1, rb), lambda i, j: (0, i))]
    if w is not None:
        args.append(w.T)
        in_specs.append(plane_spec)
    if frontier is not None:
        ft = frontier.T if frontier.ndim == 2 else frontier[None, :]
        with jax.named_scope("edge_map.gather"):
            args.append(ft[:, idx_t])
        in_specs.append(pl.BlockSpec((ft.shape[0],) + block[1:],
                                     lambda i, j: (0, j, i)))
    if alive is not None:
        args.append(alive.T)
        in_specs.append(plane_spec)
    if init_rows is not None:
        args.append(init_rows.T if planar else init_rows[None, :])
        in_specs.append(row_spec)

    kernel = _make_kernel(
        reduce, w is not None, unit_weights and w is None,
        frontier is not None, alive is not None, init_rows is not None,
        float(neutral), float(identity))
    cost = pl.CostEstimate(
        flops=2 * r * width * k,
        bytes_accessed=edge_map_tile_bytes(
            r, width, weighted=w is not None,
            frontier=frontier is not None, alive=alive is not None,
            init=init_rows is not None,
            idx_itemsize=idx.dtype.itemsize,
            plane_k=k,
            frontier_planar=frontier is not None and frontier.ndim == 2),
        transcendentals=0)
    y = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=row_spec,
        out_shape=jax.ShapeDtypeStruct((k, r), x.dtype),
        cost_estimate=cost,
        interpret=interpret_mode(interpret),
        name="ell_edge_map",
    )(*args)
    return y.T if planar else y[0]
