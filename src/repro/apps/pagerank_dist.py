"""Sharded PageRank — the apps-level entry to the repro.dist.graph engine.

Single-device ``apps.pagerank`` numerics on a multi-device mesh: destination-
sharded edges, DBG-hot property replication (policy ``"replicate_hot"``) or
pure owner-partitioning (``"partition"``).
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax

from ..dist import graph as dist_graph
from ..graph import csr
from .engine import GraphArrays, to_arrays

__all__ = ["pagerank_dist", "make_graph_mesh"]


def make_graph_mesh(n_shards: Optional[int] = None):
    """1D ``("graph",)`` mesh over the first ``n_shards`` devices
    (:func:`repro.dist.graph.graph_mesh`, cached per size)."""
    devs = jax.devices()
    n = len(devs) if n_shards is None else min(n_shards, len(devs))
    return dist_graph.graph_mesh(n)


def pagerank_dist(
    g,
    *,
    mesh=None,
    n_shards: Optional[int] = None,
    policy: str = "replicate_hot",
    backend: str = "flat",
    damping: float = 0.85,
    max_iters: int = 64,
    tol: float = 1e-7,
) -> Tuple[jax.Array, jax.Array, dist_graph.ShardedGraphArrays]:
    """Run sharded PageRank on ``g`` (a ``csr.Graph`` or ``GraphArrays``).

    ``backend`` picks the per-shard edge-map implementation (``"flat"`` |
    ``"ell"``, resolved through ``apps.engine.BACKENDS``); the PageRank loop
    itself is backend-agnostic.  Returns (ranks, iterations, sharded_graph) —
    the sharded graph carries the partition/replication stats the scaling
    benchmark reports.  For repeated runs on the same graph, keep the
    returned ``sharded_graph`` and call
    :func:`repro.dist.graph.pagerank_sharded` with it directly — the compiled
    executable is cached per (graph, mesh) identity.
    """
    if isinstance(g, GraphArrays):
        ga = g
    elif hasattr(g, "ga"):  # an engine backend (FlatBackend / EllBackend)
        ga = g.ga
    else:
        ga = to_arrays(g, backend="arrays")
    if mesh is None:
        mesh = make_graph_mesh(n_shards)
    sg = dist_graph.shard_graph(ga, mesh.devices.size, policy=policy,
                                backend=backend)
    ranks, iters = dist_graph.pagerank_sharded(
        sg, mesh, damping=damping, max_iters=max_iters, tol=tol)
    return ranks, iters, sg
