"""Chip smoke: DBG graph analytics, query serving and the sharded engine on
a TPU, every answer checked against a host reference.

Run from the root of a checkout, on a machine with a TPU:

    python chip_smoke.py               # one chip
    python chip_smoke.py --four-chips  # sharded engine on four chips vs one

The graph is Graph500's Kronecker graph at scale 22 (V = 4,194,304), edge
factor 16 (67,108,864 edges), A/B/C = 0.57/0.19/0.19, generated from
``--seed`` by ``graph.generators.rmat`` and relabelled by DBG.  All phases
share it.  One chip runs ``apps.pagerank`` and unit-weight ``apps.sssp`` on
the ``flat`` and ``ell`` backends, then a ``GraphServeService`` takes one
churn batch and answers 16 mixed personalized-PageRank and SSSP queries.
``--four-chips`` runs only ``pagerank_dist`` (flat and ell) and one
``ShardedStreamService`` batch + SSSP, each against its one-chip
counterpart (one-chip ``apps.pagerank``; ``StreamService.sssp`` on the
service's own single-device plane).

Host references (numpy, independent of the engine): BFS levels for SSSP,
compared bitwise; the float64 fixed-point residual ``|P(r) - r|_1`` for
every PageRank answer.  PageRank is a ``damping``-contraction in L1, so two
answers with residuals ``a`` and ``b`` lie within ``(a + b) / (1 - damping)``
of each other: that is the bound for flat-vs-ell and sharded-vs-one-chip.

Earlier lines: one ``[check]`` line per check with its limit, ``[time]``
per phase (wall clock, with the XLA/Mosaic compile seconds inside it kept
apart), ``[memory]`` per device.  The last line is the JSON contract,
printed only when every check passed.  A failed check or phase exits
non-zero, and so does a run where JAX finds no TPU.  One process drives
every chip; the compile cache is ``repro.compile_cache``.
"""
from __future__ import annotations

import argparse
import contextlib
import dataclasses
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

#: Graph500 Kronecker parameters (graph500.org specification, section 3)
SCALE = 22
EDGE_FACTOR = 16
KRONECKER_ABC = (0.57, 0.19, 0.19)
DAMPING = 0.85
#: L1 fixed-point residual every PageRank answer must reach: 100x the
#: solvers' stopping tolerance (1e-7), room for float32 rounding over 4M
#: ranks of ~2.4e-7 each
PR_RESIDUAL_LIMIT = 1e-5
CHURN = 4096  # inserts and deletes in the one ingest batch
QUERIES_PER_KIND = 8  # = the service's batch width K


class Smoke:
    """Prints checks, phase times and device memory; exits on a failure."""

    def __init__(self):
        self.t0 = time.perf_counter()
        self.compile_s = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, name, secs, **_):
        if name == "/jax/core/compile/backend_compile_duration":
            self.compile_s += secs

    @staticmethod
    def log(msg: str) -> None:
        print(msg, flush=True)

    def check(self, name: str, value, limit, ok: bool) -> None:
        self.log(f"[check] {name}: {value} (limit {limit}) "
                 f"{'ok' if ok else 'FAILED'}")
        if not ok:
            sys.exit(f"chip smoke: check failed: {name}")

    def at_most(self, name: str, value, limit) -> None:
        self.check(name, value, limit, bool(value <= limit))

    def equal(self, name: str, value, expected) -> None:
        self.check(name, value, expected, bool(value == expected))

    @contextlib.contextmanager
    def phase(self, name: str):
        c0, t = self.compile_s, time.perf_counter()
        yield
        wall = time.perf_counter() - t
        self.log(f"[time] {name}: {wall:.3f} s wall, of which "
                 f"{self.compile_s - c0:.3f} s compile")

    def memory(self, label: str) -> None:
        for d in jax.devices():
            st = d.memory_stats() or {}
            self.log(f"[memory] {label} {d}: bytes_in_use="
                     f"{st.get('bytes_in_use')} peak_bytes_in_use="
                     f"{st.get('peak_bytes_in_use')}")


# ---------------------------------------------------------------------------
# host references (numpy only)
# ---------------------------------------------------------------------------

class HostGraph:
    """Out-CSR plus the (src, dst) edge list of one graph version."""

    def __init__(self, indptr: np.ndarray, indices: np.ndarray):
        self.indptr = indptr.astype(np.int64)
        self.indices = indices.astype(np.int32)
        self.v = self.indptr.shape[0] - 1
        self.out_deg = np.diff(self.indptr)
        self.src = np.repeat(np.arange(self.v, dtype=np.int32), self.out_deg)

    def bfs(self, root: int) -> np.ndarray:
        """BFS levels as float32 (inf = unreachable): unit-weight SSSP."""
        level = np.full(self.v, np.inf, np.float32)
        level[root] = 0.0
        frontier = np.array([root], np.int64)
        depth = 0
        while frontier.size:
            depth += 1
            starts = self.indptr[frontier]
            counts = self.indptr[frontier + 1] - starts
            total = int(counts.sum())
            if total == 0:
                break
            offs = (np.repeat(starts - (np.cumsum(counts) - counts), counts)
                    + np.arange(total))
            nbr = self.indices[offs]
            level[nbr[np.isinf(level[nbr])]] = depth
            frontier = np.flatnonzero(level == depth)
        return level

    def pr_residual(self, rank: np.ndarray, root=None) -> float:
        """``|P(r) - r|_1`` in float64; ``root`` makes the teleport one-hot
        (personalized PageRank, dangling mass teleporting the same way)."""
        x = np.asarray(rank, np.float64)
        contrib = x / np.maximum(self.out_deg, 1)
        pulled = np.bincount(self.indices, weights=contrib[self.src],
                             minlength=self.v)
        dangling = x[self.out_deg == 0].sum()
        if root is None:
            new = (1 - DAMPING) / self.v + DAMPING * (pulled + dangling / self.v)
        else:
            new = DAMPING * pulled
            new[root] += (1 - DAMPING) + DAMPING * dangling
        return float(np.abs(new - x).sum())

    def churn(self, rng: np.random.Generator, n: int):
        """``n`` distinct existing edges to delete and ``n`` distinct new
        non-loop edges to insert, plus the graph they leave behind."""
        e = self.indices.shape[0]
        pos = np.sort(rng.choice(e, n, replace=False))
        del_src, del_dst = self.src[pos].astype(np.int64), self.indices[pos]
        ins = set()
        while len(ins) < n:
            u, w = (int(x) for x in rng.integers(0, self.v, 2))
            if u != w and (u, w) not in ins and not np.any(
                    self.indices[self.indptr[u]:self.indptr[u + 1]] == w):
                ins.add((u, w))
        ins_src, ins_dst = (np.array(c, np.int64) for c in zip(*sorted(ins)))
        keep = np.ones(e, bool)
        keep[pos] = False
        counts = (self.out_deg - np.bincount(del_src, minlength=self.v)
                  + np.bincount(ins_src, minlength=self.v))
        kept_ptr = np.concatenate(
            [[0], np.cumsum(self.out_deg
                            - np.bincount(del_src, minlength=self.v))])
        indices = np.insert(self.indices[keep], kept_ptr[ins_src + 1],
                            ins_dst.astype(np.int32))
        after = HostGraph(np.concatenate([[0], np.cumsum(counts)]), indices)
        return (ins_src, ins_dst, del_src, del_dst.astype(np.int64)), after


def pick_roots(host: HostGraph, rng: np.random.Generator, n: int):
    return [int(r) for r in rng.choice(np.flatnonzero(host.out_deg > 0), n,
                                       replace=False)]


# ---------------------------------------------------------------------------
# phases
# ---------------------------------------------------------------------------

def build_graph(smoke: Smoke, seed: int):
    from repro.core import reorder
    from repro.graph import generators

    v, e = 1 << SCALE, EDGE_FACTOR << SCALE
    a, b, c = KRONECKER_ABC
    with smoke.phase(f"host: generate Graph500 Kronecker scale {SCALE}"):
        g = generators.rmat(v, e, a=a, b=b, c=c, seed=seed,
                            name=f"kron{SCALE}")
    smoke.equal("vertices", g.num_vertices, v)
    smoke.equal("edges", g.num_edges, e)
    with smoke.phase("host: DBG reorder (degrees, mapping, CSR rebuild)"):
        g, res = reorder.reorder_graph(g, "dbg")
    out_deg = g.out_degrees()
    spec = reorder.dbg_spec(float(out_deg.mean()))
    groups = reorder._assign_groups(out_deg, spec.boundaries)
    smoke.equal("DBG groups out of id order",
                int(np.sum(np.diff(groups) < 0)), 0)
    smoke.log(f"[graph] V={g.num_vertices} E={g.num_edges} "
              f"max_in_degree={int(g.in_degrees().max())} "
              f"dbg_groups={len(spec.boundaries)}")
    with smoke.phase("host: reference adjacency"):
        host = HostGraph(g.out_csr.indptr, g.out_csr.indices)
    return g, host


def aot(smoke: Smoke, label: str, fn, *args):
    """Compile ``fn`` for ``args`` ahead of time, then run it: the two
    times are reported apart."""
    t = time.perf_counter()
    compiled = fn.lower(*args).compile()
    smoke.log(f"[compile] {label}: {time.perf_counter() - t:.3f} s")
    with smoke.phase(f"run {label}"):
        out = jax.block_until_ready(compiled(*args))
    return compiled, out


def analytics(smoke: Smoke, g, host: HostGraph, root: int) -> None:
    from repro.apps import pagerank, sssp, to_arrays

    with smoke.phase("host: BFS reference"):
        levels = host.bfs(root)
    smoke.log(f"[analytics] root={root} reached="
              f"{int(np.isfinite(levels).sum())} depth="
              f"{int(levels[np.isfinite(levels)].max())}")
    flat_rank = flat_res = None
    for backend in ("flat", "ell"):
        with smoke.phase(f"set-up to_arrays({backend})"):
            ga = jax.block_until_ready(to_arrays(g, backend=backend))
        smoke.memory(f"after to_arrays({backend})")
        pr, (rank, iters) = aot(smoke, f"pagerank {backend}", pagerank, ga)
        if backend == "ell":
            smoke.equal("ell pagerank step has tpu_custom_call",
                        "tpu_custom_call" in pr.as_text(), True)
        sp, (dist, sssp_iters) = aot(smoke, f"sssp {backend}", sssp, ga,
                                     jnp.asarray(root, jnp.int32))
        if backend == "ell":
            smoke.equal("ell sssp step has tpu_custom_call",
                        "tpu_custom_call" in sp.as_text(), True)
        smoke.memory(f"after apps({backend})")
        rank = np.asarray(rank)
        res = host.pr_residual(rank)
        smoke.at_most(f"{backend} pagerank iterations", int(iters), 63)
        smoke.at_most(f"{backend} pagerank L1 residual |P(r) - r|", res,
                      PR_RESIDUAL_LIMIT)
        if flat_rank is None:
            flat_rank, flat_res = rank, res
        else:
            smoke.at_most(f"{backend} pagerank vs flat L1 distance",
                          float(np.abs(rank.astype(np.float64)
                                       - flat_rank).sum()),
                          (res + flat_res) / (1 - DAMPING))
        smoke.equal(f"{backend} sssp vs host BFS mismatched entries",
                    int(np.sum(np.asarray(dist) != levels)), 0)
        smoke.log(f"[analytics] {backend}: pagerank iters={int(iters)} "
                  f"sssp iters={int(sssp_iters)}")
        del ga, pr, sp, rank, dist


def serving(smoke: Smoke, g, host: HostGraph, rng) -> None:
    from repro.serve import GraphServeService, Query

    with smoke.phase("set-up GraphServeService (default config)"):
        svc = GraphServeService(g)
    k = svc.config.max_width
    with smoke.phase("host: churn batch + reference graph"):
        (ins_s, ins_d, del_s, del_d), after = host.churn(rng, CHURN)
    with smoke.phase("ingest churn batch + publish"):
        svc.ingest(add_src=ins_s, add_dst=ins_d, del_src=del_s,
                   del_dst=del_d)
    smoke.equal("snapshot version after one ingest batch",
                svc.snapshot_version, 1)
    root_of = {}  # qid -> root; the kinds alternate in the queue
    for s, p in zip(pick_roots(after, rng, QUERIES_PER_KIND),
                    pick_roots(after, rng, QUERIES_PER_KIND)):
        root_of[svc.submit(Query(kind="sssp", root=s))] = s
        root_of[svc.submit(Query(kind="pagerank", root=p))] = p
    with smoke.phase(f"drain {2 * QUERIES_PER_KIND} queries at K={k}"):
        results = svc.drain()
    smoke.memory("after serving")
    smoke.equal("queries answered", len(results), 2 * QUERIES_PER_KIND)
    with smoke.phase("host: serving references"):
        for r in results:
            smoke.equal(f"query {r.qid} snapshot version",
                        r.snapshot_version, 1)
            root = root_of[r.qid]
            if r.kind == "sssp":
                smoke.equal(f"serve sssp root {root} vs host BFS on version "
                            "1 mismatched entries",
                            int(np.sum(r.value != after.bfs(root))), 0)
            else:
                smoke.at_most(f"serve ppr root {root} iterations", r.iters,
                              svc.config.pr_max_iters - 1)
                smoke.at_most(f"serve ppr root {root} L1 residual "
                              "|P(r) - r| on version 1",
                              after.pr_residual(r.value, root=root),
                              PR_RESIDUAL_LIMIT)


def shard_bytes(sg) -> dict:
    """Bytes of the sharded state's device arrays on each device."""
    per: dict = {}
    fields = [getattr(sg, f.name) for f in dataclasses.fields(sg)
              if f.name != "host"]
    for leaf in jax.tree_util.tree_leaves(fields):
        if isinstance(leaf, jax.Array):
            for s in leaf.addressable_shards:
                per[s.device.id] = per.get(s.device.id, 0) + s.data.nbytes
    return per


def check_split(smoke: Smoke, label: str, sg, n: int) -> None:
    per = shard_bytes(sg)
    smoke.log(f"[memory] {label} shard state bytes per device: "
              + " ".join(f"{d}={b}" for d, b in sorted(per.items())))
    smoke.equal(f"{label} devices holding shard state", len(per), n)
    smoke.at_most(f"{label} shard state max/min bytes per device",
                  max(per.values()) / min(per.values()), 1.0)


def four_chips(smoke: Smoke, g, host: HostGraph, rng) -> None:
    from repro.apps import make_graph_mesh, pagerank, pagerank_dist, to_arrays
    from repro.stream import StreamService
    from repro.stream.sharded import ShardedStreamService

    n = 4
    smoke.equal("devices", len(jax.devices()), n)
    mesh = make_graph_mesh(n)
    ga = to_arrays(g, backend="flat")
    _, (rank1, _) = aot(smoke, "pagerank one chip (flat, device 0)",
                        pagerank, ga)
    rank1 = np.asarray(rank1, np.float64)
    res1 = host.pr_residual(rank1)
    smoke.at_most("one-chip pagerank L1 residual |P(r) - r|", res1,
                  PR_RESIDUAL_LIMIT)
    del ga  # the one-chip reference leaves device 0 before the sharded runs
    for backend in ("flat", "ell"):
        with smoke.phase(f"pagerank_dist {backend} (shard + compile + run)"):
            rank, iters, sg = pagerank_dist(g, mesh=mesh, backend=backend)
            rank = np.asarray(jax.block_until_ready(rank))
        smoke.equal(f"pagerank_dist {backend} shards", sg.n_shards, n)
        smoke.log(f"[sharded] {backend}: iters={int(iters)} "
                  f"n_hot={sg.stats['n_hot']} halo_max={sg.halo_max}")
        check_split(smoke, f"pagerank_dist {backend}", sg, n)
        smoke.memory(f"after pagerank_dist {backend}")
        res = host.pr_residual(rank)
        smoke.at_most(f"pagerank_dist {backend} L1 residual |P(r) - r|",
                      res, PR_RESIDUAL_LIMIT)
        smoke.at_most(f"pagerank_dist {backend} vs one chip L1 distance",
                      float(np.abs(rank - rank1).sum()),
                      (res + res1) / (1 - DAMPING))
        del rank, sg
    # a ShardedStreamService is a StreamService whose queries run on the
    # shards: its own one-device plane (the same DeltaGraph the batch is
    # routed from) answers through StreamService.sssp on device 0
    with smoke.phase("set-up ShardedStreamService"):
        sharded = ShardedStreamService(g, mesh=mesh)
    with smoke.phase("host: churn batch + reference graph"):
        (ins_s, ins_d, del_s, del_d), after = host.churn(rng, CHURN)
    with smoke.phase("ingest churn batch (one-chip plane + shards)"):
        sharded.ingest(add_src=ins_s, add_dst=ins_d, del_src=del_s,
                       del_dst=del_d)
    smoke.equal("sharded stream full re-shards", sharded.full_rebuilds, 0)
    root = pick_roots(after, rng, 1)[0]
    with smoke.phase("sssp one chip (StreamService.sssp)"):
        d1 = StreamService.sssp(sharded, root)
    with smoke.phase("sssp sharded"):
        d4 = sharded.sssp(root)
    check_split(smoke, "sharded stream", sharded.sg, n)
    smoke.memory("after sharded stream (device 0 also holds the one-chip "
                 "SSSP reference)")
    smoke.equal(f"sharded stream sssp root {root} vs one chip mismatched "
                "entries", int(np.sum(d4 != d1)), 0)
    smoke.equal(f"one-chip stream sssp root {root} vs host BFS mismatched "
                "entries", int(np.sum(d1 != after.bfs(root))), 0)


def require_tpu() -> None:
    """No TPU, no result: a CPU or GPU run is not a chip run."""
    platform = jax.devices()[0].platform
    if platform != "tpu":
        sys.exit(f"chip smoke: JAX finds no TPU (platform {platform!r})")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the sharded engine on four chips, each "
                         "phase against one chip")
    args = ap.parse_args(argv)
    require_tpu()
    from repro.compile_cache import enable_compile_cache

    smoke = Smoke()
    devs = jax.devices()
    smoke.log(f"[device] {devs[0].platform} {devs[0].device_kind} "
              f"x{len(devs)}; compile cache {enable_compile_cache()}")
    g, host = build_graph(smoke, args.seed)
    rng = np.random.default_rng(args.seed + 1)
    if args.four_chips:
        four_chips(smoke, g, host, rng)
    else:
        smoke.equal("devices", len(devs), 1)
        analytics(smoke, g, host, pick_roots(host, rng, 1)[0])
        serving(smoke, g, host, rng)
    smoke.log(f"[time] total: {time.perf_counter() - smoke.t0:.3f} s")
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
