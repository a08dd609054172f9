"""Single-Source Shortest Path (SSSP) — frontier Bellman-Ford (Table VIII).

Direction-optimizing (Ligra's pull/push switch): each round the engine
inspects ``frontier_density`` — a sparse frontier relaxes by PUSH (scatter
from the few active sources), a dense one by PULL (every destination reduces
over its in-edges, the regular-read mode the paper's reorderings optimize).
Both directions relax the identical edge set with a min-reduction, so the
result is bit-identical either way — the switch is purely a traffic choice.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from .engine import edge_map_pull, edge_map_push, switch_by_density

__all__ = ["sssp"]


@partial(jax.jit, static_argnames=("max_iters", "direction_optimizing",
                                   "density_threshold"))
def sssp(ga, root: jnp.ndarray, *, max_iters: int = 0,
         direction_optimizing: bool = True,
         density_threshold: float = None):
    """Returns (dist, iterations). Unreachable vertices keep +inf.

    Relaxations only from the changed frontier (Ligra semantics): each round,
    active sources push dist[src] + w to out-neighbors with a min-scatter, or
    — when the frontier is dense — destinations pull the same relaxation.
    ``density_threshold`` (static; tuned plans set it) overrides the engine's
    Ligra-default switch point; any value is bit-identical, only traffic
    differs.
    """
    v = ga.in_deg.shape[0]
    max_iters = max_iters or v  # Bellman-Ford bound

    dist0 = jnp.full((v,), jnp.inf, jnp.float32).at[root].set(0.0)
    frontier0 = jnp.zeros((v,), bool).at[root].set(True)

    def push_step(args):
        dist, frontier = args
        # inactive sources push +inf (neutral for min)
        return edge_map_push(
            ga, dist, reduce="min", src_frontier=frontier,
            use_weights=True, neutral=jnp.inf, init=dist,
        )

    def pull_step(args):
        dist, frontier = args
        pulled = edge_map_pull(
            ga, dist, reduce="min", src_frontier=frontier,
            use_weights=True, neutral=jnp.inf,
        )
        return jnp.minimum(dist, pulled)

    def cond(state):
        _, frontier, it = state
        return jnp.logical_and(it < max_iters, jnp.any(frontier))

    @jax.named_scope("sssp.iter")
    def body(state):
        dist, frontier, it = state
        if direction_optimizing:
            cand = switch_by_density(ga, frontier, pull_step, push_step,
                                     (dist, frontier),
                                     threshold=density_threshold)
        else:
            cand = push_step((dist, frontier))
        frontier = cand < dist
        return cand, frontier, it + 1

    dist, _, iters = jax.lax.while_loop(cond, body, (dist0, frontier0, 0))
    return dist, iters
