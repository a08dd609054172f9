"""PageRank (PR) — pull-only (Table VIII), iterated to convergence."""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from .engine import GraphArrays, edge_map_pull

__all__ = ["pagerank"]


@partial(jax.jit, static_argnames=("max_iters",))
def pagerank(
    ga: GraphArrays,
    *,
    damping: float = 0.85,
    max_iters: int = 64,
    tol: float = 1e-7,
):
    """Returns (ranks, iterations). Dangling mass redistributed uniformly."""
    v = ga.in_deg.shape[0]
    out_deg = jnp.maximum(1, ga.out_deg).astype(jnp.float32)
    dangling = (ga.out_deg == 0).astype(jnp.float32)

    def cond(state):
        _, it, err = state
        return jnp.logical_and(it < max_iters, err > tol)

    @jax.named_scope("pagerank.iter")
    def body(state):
        rank, it, _ = state
        contrib = rank / out_deg
        pulled = edge_map_pull(ga, contrib, reduce="sum")
        dangling_mass = jnp.sum(rank * dangling) / v
        new = (1.0 - damping) / v + damping * (pulled + dangling_mass)
        err = jnp.sum(jnp.abs(new - rank))
        return new, it + 1, err

    rank0 = jnp.full((v,), 1.0 / v, jnp.float32)
    rank, iters, _ = jax.lax.while_loop(cond, body, (rank0, 0, jnp.inf))
    return rank, iters
