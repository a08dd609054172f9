"""Plain numpy references, independent of the program under test.

They read the generated edge list in the generator's own vertex ids; the
program's answers are mapped back to those ids before they are judged.
Adapted from the host references of the repo's chip smoke (``HostGraph``):
the float64 PageRank fixed-point residual and a level-synchronous BFS.
"""
from __future__ import annotations

import numpy as np

__all__ = ["EdgeList", "pagerank_control"]


class EdgeList:
    """The generated directed graph: (src, dst) int32 arrays over ``v``
    vertices, with an out-CSR built on first use (BFS only)."""

    def __init__(self, src: np.ndarray, dst: np.ndarray, v: int):
        self.src = np.asarray(src, np.int64)
        self.dst = np.asarray(dst, np.int64)
        self.v = int(v)
        self.out_deg = np.bincount(self.src, minlength=self.v)
        self._csr = None

    def pr_residual(self, rank: np.ndarray, damping: float) -> float:
        """``|P(r) - r|_1`` in float64 for global PageRank with dangling
        mass spread uniformly, as ``apps.pagerank`` defines it."""
        x = np.asarray(rank, np.float64)
        contrib = x / np.maximum(self.out_deg, 1)
        pulled = np.bincount(self.dst, weights=contrib[self.src],
                             minlength=self.v)
        dangling = x[self.out_deg == 0].sum()
        new = (1 - damping) / self.v + damping * (pulled + dangling / self.v)
        return float(np.abs(new - x).sum())

    def out_csr(self):
        if self._csr is None:
            order = np.argsort(self.src, kind="stable")
            indptr = np.zeros(self.v + 1, np.int64)
            np.cumsum(self.out_deg, out=indptr[1:])
            self._csr = indptr, self.dst[order]
        return self._csr

    def bfs(self, root: int) -> np.ndarray:
        """BFS levels as float32 (inf = unreachable): unit-weight SSSP."""
        indptr, indices = self.out_csr()
        level = np.full(self.v, np.inf, np.float32)
        level[root] = 0.0
        frontier = np.array([root], np.int64)
        depth = 0
        while frontier.size:
            depth += 1
            starts = indptr[frontier]
            counts = indptr[frontier + 1] - starts
            total = int(counts.sum())
            if total == 0:
                break
            offs = (np.repeat(starts - (np.cumsum(counts) - counts), counts)
                    + np.arange(total))
            nbr = indices[offs]
            level[nbr[np.isinf(level[nbr])]] = depth
            frontier = np.flatnonzero(level == depth)
        return level

    def reached_edges(self, level: np.ndarray) -> int:
        """Out-edges of the vertices a traversal reached."""
        return int(self.out_deg[np.isfinite(level)].sum())


def pagerank_control(src, dst, v: int, *, damping: float, tolerance: float,
                     max_iterations: int, dtype):
    """The reference PageRank iteration from the uniform vector to GAP's
    rule (stop once an iteration changes the ranks by less than
    ``tolerance`` in L1, or after ``max_iterations``), computed on the
    device in ``dtype``: in bfloat16, the lower-precision control that the
    check has to refuse.  Returns the ranks as float64 numpy."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def solve(src, dst):
        out_deg = jnp.maximum(jnp.bincount(src, length=v), 1).astype(dtype)
        dangling = (jnp.bincount(src, length=v) == 0).astype(dtype)

        def body(s):
            x, it, _ = s
            pulled = jax.ops.segment_sum((x / out_deg)[src], dst,
                                         num_segments=v)
            new = ((1 - damping) / v
                   + damping * (pulled + jnp.sum(x * dangling) / v))
            new = new.astype(dtype)
            change = jnp.sum(jnp.abs(new.astype(jnp.float32)
                                     - x.astype(jnp.float32)))
            return new, it + 1, change

        def more(s):
            _, it, change = s
            return (it < max_iterations) & (change > tolerance)

        x, _, _ = jax.lax.while_loop(
            more, body, (jnp.full((v,), 1.0 / v, dtype), 0, jnp.inf))
        return x

    x = solve(jnp.asarray(src), jnp.asarray(dst))
    return np.asarray(x.astype(jnp.float32), np.float64)
