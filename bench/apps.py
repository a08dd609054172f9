"""The solves a traffic mix can ask for, and how each is judged.

A traffic file names one of ``APPS`` under ``"app"`` with that app's
parameters.  An app is built from the traffic, the configuration, the
program's arrays ``ga``, the program's ``mapping`` (``mapping[v]`` is the id
the program gave generated vertex ``v``, so ``answer[mapping]`` is in
generated ids) and the generator's ``perm`` (``perm[c]`` is the generated id
of drawn vertex ``c``).  It compiles the program's own solve ahead of time
for the cell's arrays, runs one solve per call of ``solve`` (``i`` counts
the solves of the run), which returns ``answers`` answers, and judges them
against ``reference.EdgeList`` in generated ids.
"""
from __future__ import annotations

import numpy as np

from . import roofline

__all__ = ["APPS"]


class PageRank:
    """Global PageRank solves, each from the uniform vector to GAP's rule:
    the program's own stop once an iteration changes the ranks by less than
    ``tolerance`` in L1, or after ``max_iterations``.  Nothing is carried
    between solves."""

    answers = 1

    def __init__(self, traffic: dict, cfg: dict, ga, mapping, perm,
                 out_deg):
        self.ga, self.mapping = ga, mapping
        self.kw = dict(damping=float(traffic["damping"]),
                       tol=float(traffic["tolerance"]))
        self.iters = int(traffic["max_iterations"])
        self.residual_limit = float(traffic["residual_limit"])

    def compile(self):
        from repro.apps import pagerank

        self.exe = pagerank.lower(self.ga, max_iters=self.iters,
                                  **self.kw).compile()

    def solve(self, i: int):
        return self.exe(self.ga, **self.kw)

    @staticmethod
    def iterations(out) -> int:
        return int(out[1])

    def judge(self, outs, ref) -> tuple[dict, int]:
        """Returns (checks, failed answers) over ``outs``, (solve index,
        answer) pairs: each answer's float64 residual ``|P(r) - r|_1`` in
        generated ids."""
        res = [ref.pr_residual(np.asarray(rank)[self.mapping],
                               self.kw["damping"]) for _, (rank, _) in outs]
        failed = sum(not r <= self.residual_limit for r in res)
        return {"pr_residual": (max(res), self.residual_limit)}, failed

    def compulsory_bytes(self, out, ref) -> int:
        return roofline.pagerank_bytes(ref.v, ref.src.shape[0],
                                       self.iterations(out))


def search_keys(cfg: dict, traffic: dict, perm, out_deg) -> np.ndarray:
    """The BFS roots, in generated ids: ``traffic["keys"]`` drawn vertices
    of out-degree >= 1, chosen by the configuration's ``graph_seed``."""
    rng = np.random.default_rng(cfg["graph_seed"])
    drawn = rng.choice(np.flatnonzero(out_deg[perm] > 0),
                       int(traffic["keys"]), replace=False)
    return perm[drawn]


class Bfs:
    """BFS (unit-weight ``apps.sssp``) from Graph500-style search keys: a
    solve traverses from each of ``keys`` drawn vertices with out-degree
    >= 1, chosen by the configuration's ``graph_seed``, so that every run
    does the same traversals, in its own labelling."""

    def __init__(self, traffic: dict, cfg: dict, ga, mapping, perm,
                 out_deg):
        self.ga, self.mapping = ga, mapping
        self.roots = search_keys(cfg, traffic, perm, out_deg)
        self.program_roots = np.asarray(mapping)[self.roots]
        self.answers = len(self.roots)

    def compile(self):
        import jax.numpy as jnp
        from repro.apps import sssp

        self.jnp = jnp
        self.exe = sssp.lower(self.ga, jnp.int32(0)).compile()

    def solve(self, i: int):
        """One traversal from each search key, in order."""
        return [self.exe(self.ga, self.jnp.int32(r))
                for r in self.program_roots]

    @staticmethod
    def iterations(out) -> int:
        return sum(int(it) for _, it in out)

    def judge(self, outs, ref) -> tuple[dict, int]:
        """Returns (checks, failed answers) over ``outs``, (solve index,
        answers) pairs: each traversal's levels, in generated ids, against
        the reference BFS from the same key; the comparison is exact."""
        worst, failed = 0, 0
        for _, answers in outs:
            for root, (dist, _) in zip(self.roots, answers):
                got = np.asarray(dist)[self.mapping]
                bad = int(np.sum(got != ref.bfs(int(root))))
                worst = max(worst, bad)
                failed += bad > 0
        return {"bfs_mismatched_levels": (worst, 0)}, failed

    def compulsory_bytes(self, out, ref) -> int:
        return sum(roofline.bfs_bytes(
            ref.v, ref.reached_edges(np.asarray(dist)[self.mapping]))
            for dist, _ in out)


APPS = {"pagerank": PageRank, "bfs": Bfs}
