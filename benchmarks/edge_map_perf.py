"""Edge-map hot-path benchmark: flat vs fused backends → BENCH_apps.json.

The first wall-clock + HBM-byte harness that connects reordering to
END-TO-END iteration time (cf. BOBA's reorder-to-runtime evaluation): every
iteration of every app is an ``edge_map_pull``/``edge_map_push``, so this
measures exactly that primitive under both engine backends, across the
orderings the paper evaluates, on the Table IX/X registry graphs.

Per (dataset, ordering) cell:

  * **pull** (PR-style sum) and **push** (SSSP-style min-relaxation with a
    ~10%-dense frontier) per-iteration wall time for ``FlatBackend`` (the
    XLA gather/segment/scatter path) and ``EllBackend`` (fused Pallas kernels
    over DBG-ELL tiles, interpret mode on CPU — compiled-mode Mosaic numbers
    are a ROADMAP item, so fused wall-clock here reflects the interpreter,
    reported honestly);
  * **HBM bytes per iteration**: the flat path measured by XLA
    ``cost_analysis()`` (plus an analytic pass-model cross-check), the fused
    path from the kernels' ``pl.CostEstimate`` accounting
    (``fused_edge_map_bytes``) — the XLA gather of ``x[idx]`` into
    slot-sized value tiles, then one kernel pass over them and the planes.

Per dataset (DBG ordering), every app runs on BOTH backends: per-iteration
time, iteration counts, and max result deviation (min/max reductions are
bit-identical; sums differ in fp association only).

Usage:
  PYTHONPATH=src python benchmarks/edge_map_perf.py [--scale small]
      [--datasets all|kr,lj,...] [--orderings original,sort,hubcluster,dbg]
      [--reps 3] [--out BENCH_apps.json] [--smoke]
"""
import argparse
import json
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "src"))

import jax
import jax.numpy as jnp
import numpy as np

from repro.apps import bc, pagerank, pagerank_delta, radii, sssp, to_arrays
from repro.apps.engine import edge_map_pull, edge_map_push
from repro.core import reorder
from repro.graph import csr as csr_mod
from repro.graph import datasets
from repro.kernels.edge_map.ops import fused_edge_map_bytes
from repro.obs.counters import flat_edge_map_bytes

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from common import time_jitted  # noqa: E402

ORDERINGS = ("original", "sort", "hubcluster", "dbg")
SKEWED = ("kr", "pl", "tw", "sd", "lj", "wl", "fr", "mp")


def _xla_bytes(fn, *args) -> float:
    cost = jax.jit(fn).lower(*args).compile().cost_analysis()
    if isinstance(cost, list):  # older jax returns a one-element list
        cost = cost[0]
    return float(cost.get("bytes accessed", 0.0))


# analytic pass model of the flat edge map — now the shared cost model the
# observability counters charge per pass (repro.obs.counters); identical to
# the former local _flat_model_bytes at plane_k=1
_flat_model_bytes = flat_edge_map_bytes


def _agree(a, b) -> float:
    """Max relative deviation over finite entries (inf patterns must match)."""
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    mask = np.isfinite(a)
    if not np.array_equal(mask, np.isfinite(b)):
        return float("inf")
    if not mask.any():
        return 0.0
    scale = 1.0 + np.abs(a[mask]).max(initial=0.0)
    return float(np.abs(a[mask] - b[mask]).max(initial=0.0) / scale)


def bench_cell(g2, *, reps: int) -> dict:
    """Edge-map microbench (pull + push) for one relabeled graph."""
    v, e = g2.num_vertices, g2.num_edges
    fb = to_arrays(g2)
    eb = to_arrays(g2, backend="ell")
    rng = np.random.default_rng(7)
    x = jnp.asarray(rng.random(v).astype(np.float32))
    dist = jnp.asarray(
        np.where(rng.random(v) < 0.5, rng.random(v), np.inf).astype(np.float32))
    frontier = jnp.asarray(rng.random(v) < 0.1)

    def pull_flat(xx):
        return edge_map_pull(fb, xx, reduce="sum")

    def pull_fused(xx):
        return edge_map_pull(eb, xx, reduce="sum")

    def push_flat(dd, ff):
        return edge_map_push(fb, dd, reduce="min", src_frontier=ff,
                             use_weights=True, neutral=jnp.inf, init=dd)

    def push_fused(dd, ff):
        return edge_map_push(eb, dd, reduce="min", src_frontier=ff,
                             use_weights=True, neutral=jnp.inf, init=dd)

    # one jitted wrapper per op, shared by the agreement gate and the timing
    j_pull_flat, j_pull_fused = jax.jit(pull_flat), jax.jit(pull_fused)
    j_push_flat, j_push_fused = jax.jit(push_flat), jax.jit(push_fused)

    # agreement gate (the CI smoke check rides on this)
    pull_err = _agree(j_pull_flat(x), j_pull_fused(x))
    push_err = _agree(j_push_flat(dist, frontier), j_push_fused(dist, frontier))
    if pull_err > 1e-5 or push_err > 0.0:  # sum: fp association; min: bitwise
        raise SystemExit(
            f"flat-vs-fused disagreement: pull {pull_err} push {push_err}")

    cell = {
        "pull": {
            "flat_ms": time_jitted(j_pull_flat, x, reps=reps,
                                   warmup=False) * 1e3,
            "fused_ms": time_jitted(j_pull_fused, x, reps=reps,
                                    warmup=False) * 1e3,
            "flat_xla_bytes": _xla_bytes(pull_flat, x),
            "flat_model_bytes": _flat_model_bytes(
                e, v, weighted=False, frontier=False, push_init=False),
            "fused_bytes": fused_edge_map_bytes(eb.in_tiles, v),
            "max_err": pull_err,
        },
        "push": {
            "flat_ms": time_jitted(j_push_flat, dist, frontier, reps=reps,
                                   warmup=False) * 1e3,
            "fused_ms": time_jitted(j_push_fused, dist, frontier, reps=reps,
                                    warmup=False) * 1e3,
            "flat_xla_bytes": _xla_bytes(push_flat, dist, frontier),
            "flat_model_bytes": _flat_model_bytes(
                e, v, weighted=True, frontier=True, push_init=True),
            "fused_bytes": fused_edge_map_bytes(
                eb.in_tiles, v, use_weights=True, frontier=True,
                push_init=True),
            "max_err": push_err,
        },
        "ell_groups": len(eb.in_tiles),
        "ell_slots": int(sum(int(np.prod(t.idx.shape)) for t in eb.in_tiles)),
    }
    return cell


def bench_apps(g2, gw2, *, reps: int, backend_names=("flat", "ell")) -> dict:
    """All five apps on both backends (per-iteration wall time, agreement).

    Backend names resolve through ``apps.engine.BACKENDS`` — the same table
    ``to_arrays`` and the sharded engine use — so an unknown name fails with
    the registry's error instead of silently benchmarking nothing.
    """
    from repro.apps.engine import resolve_backend

    out = {}
    backends = {name: (resolve_backend(name)(g2), resolve_backend(name)(gw2))
                for name in backend_names}
    runs = {
        "pr": lambda b, bw: pagerank(b),
        "prd": lambda b, bw: pagerank_delta(b),
        "sssp": lambda b, bw: sssp(bw, jnp.int32(0)),
        "bc": lambda b, bw: bc(b, jnp.int32(0)),
        "radii": lambda b, bw: radii(b, jnp.int32(0), num_samples=4),
    }
    results = {}
    for app, fn in runs.items():
        row = {}
        for name, (b, bw) in backends.items():
            res = fn(b, bw)  # compiles + yields the result for the agreement
            jax.block_until_ready(res)
            secs = time_jitted(fn, b, bw, reps=reps, warmup=False)
            iters = max(1, int(res[-1]))
            row[name] = {"iters": iters, "ms_per_iter": secs * 1e3 / iters}
            results[(app, name)] = np.asarray(res[0], np.float64)
        row["max_dev"] = _agree(results[(app, "flat")], results[(app, "ell")])
        out[app] = row
    return out


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--datasets", default="all",
                    help="comma list or 'all' (Table IX/X registry)")
    ap.add_argument("--orderings", default=",".join(ORDERINGS))
    ap.add_argument("--scale", default="small")
    ap.add_argument("--reps", type=int, default=3)
    ap.add_argument("--smoke", action="store_true",
                    help="tiny CI config: test scale, kr+road, 1 rep")
    ap.add_argument("--out", default=os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "BENCH_apps.json"))
    args = ap.parse_args()
    if args.smoke:
        args.scale, args.datasets, args.reps = "test", "kr,road", 1
    keys = (list(datasets.REGISTRY) if args.datasets == "all"
            else args.datasets.split(","))
    orderings = args.orderings.split(",")

    out = {"schema": 1, "scale": args.scale, "orderings": orderings,
           "cells": []}
    from repro.tune import plan as tplan

    for key in keys:
        g = datasets.load(key, args.scale, seed=0)
        gw = datasets.load_weighted(key, args.scale, seed=0)
        cell = {"dataset": key, "vertices": g.num_vertices,
                "edges": g.num_edges, "orderings": {}}
        # best-known-config column: what backend="auto" (the committed
        # PLAN_tuned.json, benchmarks/autotune.py) resolves for this graph
        active = tplan.get_active_plan()
        if active is not None:
            _, family = active.lookup(tplan.graph_features(g))
            cell["best_known"] = {"family": family,
                                  "config": tplan.auto_config(g)}
        else:
            cell["best_known"] = None
        for ordering in orderings:
            if ordering == "original":
                g2, gw2 = g, gw
            else:
                m = reorder.TECHNIQUES[ordering](g.out_degrees()).mapping
                g2 = csr_mod.relabel(g, m)
                gw2 = csr_mod.relabel(gw, m)
            c = bench_cell(g2, reps=args.reps)
            cell["orderings"][ordering] = c
            if ordering == "dbg":
                cell["apps"] = bench_apps(g2, gw2, reps=args.reps)
        p = cell["orderings"].get("dbg", next(iter(cell["orderings"].values())))
        print(f"[edge_map_perf] {key}: pull flat {p['pull']['flat_ms']:.2f} ms "
              f"/ {p['pull']['flat_xla_bytes']/1e6:.1f} MB -> fused "
              f"{p['pull']['fused_ms']:.2f} ms / "
              f"{p['pull']['fused_bytes']/1e6:.1f} MB | push flat "
              f"{p['push']['flat_xla_bytes']/1e6:.1f} MB -> fused "
              f"{p['push']['fused_bytes']/1e6:.1f} MB", flush=True)
        out["cells"].append(cell)

    # acceptance summary: fused must cut HBM bytes on every skewed graph
    summary = {"per_dataset": {}}
    for cell in out["cells"]:
        rats = []
        for oc in cell["orderings"].values():
            for op in ("pull", "push"):
                flat_b = min(oc[op]["flat_xla_bytes"],
                             oc[op]["flat_model_bytes"])
                rats.append(oc[op]["fused_bytes"] / max(1.0, flat_b))
        summary["per_dataset"][cell["dataset"]] = {
            "fused_over_flat_bytes_worst": max(rats),
            "fused_reduces_bytes": max(rats) < 1.0,
        }
    skew = [d for d in summary["per_dataset"] if d in SKEWED]
    summary["all_skewed_reduced"] = all(
        summary["per_dataset"][d]["fused_reduces_bytes"] for d in skew) \
        if skew else None
    out["summary"] = summary
    with open(args.out, "w") as f:
        json.dump(out, f, indent=1)
    print(f"[edge_map_perf] wrote {args.out} "
          f"(all_skewed_reduced={summary['all_skewed_reduced']})", flush=True)


if __name__ == "__main__":
    main()
