"""Autotune driver: sweep the registry, write the plan → BENCH_tune.json.

For every registry graph this runs the full ``repro.tune`` loop:

  1. **price** the engine configuration space analytically (``tune.cost`` —
     the repo's own byte models through ``repro.roofline.HW``);
  2. **measure** the top-k shortlist plus deliberately-sampled non-shortlist
     probes under successive halving (``tune.search``), PageRank on the
     unweighted graph and SSSP on the weighted one;
  3. **select** the fastest byte-feasible candidate (never more modeled HBM
     bytes than the hand-tuned default — wall clock may win, the byte
     objective may not regress), refine SSSP's pull/push switch point, and
     choose the remaining apps' configs analytically (min modeled bytes,
     fully deterministic);
  4. **verify** the chosen backend against the flat oracle (min-reduction
     apps bitwise, sums to fp-association tolerance) — a plan that changes
     answers must never be written;
  5. **record** the honesty verdicts: ``honest_strict`` — the measured
     winner itself was shortlisted — and ``honest``, which also accepts a
     shortlisted candidate within 5% of the winner (tie-class noise).
     Logged per graph x app, summarized over the registry.

Pricing defaults to the ``cpu-interpret`` hardware profile (override via
``REPRO_HW_PROFILE``) because that is what the sweep measures on: under
the Pallas interpreter, per-grid-step dispatch dominates small-graph wall
clock, so the ranker must price it or its shortlist is uncorrelated with
the measurements it feeds.

Outputs: ``BENCH_tune.json`` (per-graph audit + plan-vs-default speedups)
and ``PLAN_tuned.json`` — the committed plan ``backend="auto"`` resolves.

``--select bytes`` makes selection purely analytic (modeled bytes, no
wall-clock in the decision) — the deterministic CI smoke mode gated by
``check_regression.py tune``.

Usage:
  PYTHONPATH=src python benchmarks/autotune.py [--scale small]
      [--datasets all|kr,lj,...] [--top-k 5] [--extras 4]
      [--select measured|bytes] [--smoke]
      [--out BENCH_tune.json] [--plan-out PLAN_tuned.json]
"""
import argparse
import datetime
import json
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "src"))

import jax.numpy as jnp
import numpy as np

from repro.apps import pagerank, sssp, to_arrays
from repro.graph import datasets
from repro.roofline import HW
from repro.tune import cost as tcost
from repro.tune import plan as tplan
from repro.tune import search as tsearch
from repro.tune import space as tspace

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

#: apps the sweep actually measures -> (graph flavor, runner app name)
MEASURED_APPS = ("pr", "sssp")
#: apps priced analytically only (min modeled bytes, deterministic)
ANALYTIC_APPS = ("prd", "bc", "radii")


def _max_dev(a, b) -> float:
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    mask = np.isfinite(a)
    if not np.array_equal(mask, np.isfinite(b)):
        return float("inf")
    if not mask.any():
        return 0.0
    scale = 1.0 + np.abs(a[mask]).max(initial=0.0)
    return float(np.abs(a[mask] - b[mask]).max(initial=0.0) / scale)


def _min_bytes_config(gc, grid, app: str) -> tcost.Scored:
    """Deterministic analytic choice: least modeled bytes, key tie-break."""
    ranked = tcost.rank(gc, grid, app=app, hw=HW.profile("cpu-interpret"))
    return min(ranked, key=lambda s: (s.model_bytes,
                                      tcost.config_key(s.config)))


def _verify(g, gw, engine_cfg: dict, oracle) -> dict:
    """Chosen backend vs the flat oracle: SSSP (min) bitwise, PR ~fp-assoc.
    Raises on disagreement — a wrong plan must never be written."""
    cfg = dict(engine_cfg)
    backend = cfg.pop("backend")
    ga, gaw = to_arrays(g, backend=backend, **cfg), \
        to_arrays(gw, backend=backend, **cfg)
    pr_flat, d_flat = oracle
    pr_dev = _max_dev(pr_flat, pagerank(ga)[0])
    d_cfg = np.asarray(sssp(gaw, jnp.int32(0))[0])
    sssp_bitwise = bool(np.array_equal(d_flat, d_cfg))
    if pr_dev > 1e-5 or not sssp_bitwise:
        raise SystemExit(
            f"tuned config {engine_cfg} disagrees with the flat oracle "
            f"(pr_dev={pr_dev}, sssp_bitwise={sssp_bitwise})")
    return {"pr_max_dev": pr_dev, "sssp_bitwise": sssp_bitwise}


def tune_graph(key: str, *, scale: str, top_k: int, extras: int,
               select: str, seed: int, audit: bool,
               refine_density: bool) -> dict:
    g = datasets.load(key, scale, seed=0)
    gw = datasets.load_weighted(key, scale, seed=0)
    space = tspace.engine_space()
    grid = space.grid()
    cell = {
        "dataset": key,
        "vertices": g.num_vertices,
        "edges": g.num_edges,
        "features": tplan.graph_features(g),
        "apps": {},
    }
    configs = {}
    default_engine = tspace.split_config(tspace.DEFAULT_CONFIG)[0]
    # full default incl. app-scope knobs — "knob absent" means "default value"
    default_full = tspace.canonical(dict(tspace.DEFAULT_CONFIG))

    # -- measured apps: analytic shortlist -> successive-halving sweep ------
    # rank under the profile we actually measure on: interpret-mode wall
    # clock is dominated by per-grid-step dispatch, not HBM traffic
    hw = HW.profile(os.environ.get("REPRO_HW_PROFILE", "cpu-interpret"))
    for app in MEASURED_APPS:
        graph = gw if app == "sssp" else g
        res = tsearch.sweep(graph, app=app, space=space, top_k=top_k,
                            extras=extras, seed=seed, select=select, hw=hw)
        gc = tcost.GraphCost.from_graph(graph)
        chosen = dict(res.chosen)
        density_timings = None
        if app == "sssp" and refine_density:
            chosen, density_timings = tsearch.refine_density_threshold(
                gw, chosen)
        chosen_bytes = tcost.app_bytes(
            gc, tspace.split_config(chosen)[0], app)
        default_bytes = tcost.default_budget(gc, app)
        configs[app] = chosen
        chosen_full = tspace.canonical({**tspace.DEFAULT_CONFIG, **chosen})
        engine_differs = (
            tcost.config_key(tspace.split_config(chosen)[0])
            != tcost.config_key(default_engine))
        tuned_wins = bool(engine_differs and res.speedup_vs_default > 1.0)
        row = {
            "measured": True,
            "chosen": chosen,
            "model_bytes": int(chosen_bytes),
            "default_bytes": int(default_bytes),
            "bytes_ratio": round(chosen_bytes / max(1, default_bytes), 6),
            "chosen_ms": round(res.chosen_s * 1e3, 3),
            "default_ms": round(res.default_s * 1e3, 3),
            "speedup_vs_default": round(res.speedup_vs_default, 4),
            "honest": res.honest,
            "honest_strict": res.honest_strict,
            "num_candidates": res.num_candidates,
            "num_measured": res.num_measured,
            "tuned_differs": tcost.config_key(chosen_full)
            != tcost.config_key(default_full),
        }
        if density_timings:
            # audit evidence for a density-threshold win: every switch point
            # was measured on the SAME engine config, same graph
            row["density_timings_ms"] = [
                [dt, round(s * 1e3, 3)]
                for dt, s in sorted(density_timings.items())]
            dt_c = chosen_full.get("density_threshold")
            dt_d = default_full.get("density_threshold")
            if dt_c != dt_d and dt_c in density_timings \
                    and dt_d in density_timings:
                tuned_wins = bool(
                    tuned_wins
                    or density_timings[dt_c] < density_timings[dt_d])
        row["tuned_wins"] = tuned_wins
        cell["apps"][app] = row
        if audit:
            cell["apps"][app]["trials"] = [t.to_json() for t in res.trials]

    # -- analytic-only apps: least modeled bytes, no measurement ------------
    for app in ANALYTIC_APPS:
        gc = tcost.GraphCost.from_graph(gw if app == "sssp" else g)
        best = _min_bytes_config(gc, grid, app)
        default_bytes = tcost.default_budget(gc, app)
        configs[app] = dict(best.config)
        cell["apps"][app] = {
            "measured": False,
            "chosen": dict(best.config),
            "model_bytes": int(best.model_bytes),
            "default_bytes": int(default_bytes),
            "bytes_ratio": round(best.model_bytes / max(1, default_bytes), 6),
        }

    # "default" plan entry: the PR choice (pull-dominated, the common shape)
    configs["default"] = dict(configs["pr"])
    cell["configs"] = configs
    cell["family"] = key

    # -- oracle verification of everything the plan will serve --------------
    verify_cfgs = {tcost.config_key(tspace.split_config(c)[0]):
                   tspace.split_config(c)[0] for c in configs.values()}
    oracle = (np.asarray(pagerank(to_arrays(g))[0]),
              np.asarray(sssp(to_arrays(gw), jnp.int32(0))[0]))
    devs = [_verify(g, gw, c, oracle) for c in verify_cfgs.values()]
    cell["correctness"] = {
        "configs_verified": len(devs),
        "pr_max_dev": max(d["pr_max_dev"] for d in devs),
        "sssp_bitwise": all(d["sssp_bitwise"] for d in devs),
    }

    pr_row = cell["apps"]["pr"]
    cell["tuned_differs"] = any(
        cell["apps"][a]["tuned_differs"] for a in MEASURED_APPS)
    cell["tuned_wins_wall_clock"] = any(
        cell["apps"][a]["tuned_wins"] for a in MEASURED_APPS)
    print(f"[autotune] {key}: pr {pr_row['chosen']} "
          f"{pr_row['speedup_vs_default']}x vs default "
          f"(bytes x{pr_row['bytes_ratio']}, honest={pr_row['honest']}) | "
          f"sssp {cell['apps']['sssp']['chosen']}", flush=True)
    return cell


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--datasets", default="all",
                    help="comma list or 'all' (Table IX/X registry)")
    ap.add_argument("--scale", default="small")
    ap.add_argument("--top-k", type=int, default=5)
    ap.add_argument("--extras", type=int, default=4,
                    help="non-shortlist honesty probes measured per sweep")
    ap.add_argument("--select", choices=("measured", "bytes"),
                    default="measured")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--smoke", action="store_true",
                    help="tiny deterministic CI config: test scale, kr+road, "
                         "analytic (bytes) selection, no audit trail")
    ap.add_argument("--out", default=os.path.join(REPO_ROOT,
                                                  "BENCH_tune.json"))
    ap.add_argument("--plan-out", default=os.path.join(REPO_ROOT,
                                                       "PLAN_tuned.json"))
    args = ap.parse_args()
    audit, refine_density = True, args.select == "measured"
    if args.smoke:
        args.scale, args.datasets = "test", "kr,road"
        args.select, args.top_k, args.extras = "bytes", 3, 2
        audit, refine_density = False, False
    keys = (list(datasets.REGISTRY) if args.datasets == "all"
            else args.datasets.split(","))

    out = {"schema": 1, "scale": args.scale, "select": args.select,
           "top_k": args.top_k, "extras": args.extras, "cells": []}
    for key in keys:
        out["cells"].append(tune_graph(
            key, scale=args.scale, top_k=args.top_k, extras=args.extras,
            select=args.select, seed=args.seed, audit=audit,
            refine_density=refine_density))

    # -- summary: the acceptance criteria, computed where they are claimed --
    cells = out["cells"]
    honesty = {
        app: sum(1 for c in cells if c["apps"][app]["honest"])
        for app in MEASURED_APPS
    }
    honesty_strict = {
        app: sum(1 for c in cells if c["apps"][app]["honest_strict"])
        for app in MEASURED_APPS
    }
    bytes_never_worse = all(
        c["apps"][app]["bytes_ratio"] <= 1.0 + 1e-9
        for c in cells for app in c["apps"])
    out["summary"] = {
        "num_graphs": len(cells),
        "honesty": {app: f"{n}/{len(cells)}" for app, n in honesty.items()},
        "honesty_strict": {app: f"{n}/{len(cells)}"
                           for app, n in honesty_strict.items()},
        "honest_fraction": round(
            sum(honesty.values()) / max(1, len(cells) * len(MEASURED_APPS)),
            4),
        "bytes_never_worse_than_default": bytes_never_worse,
        "tuned_differs": [c["dataset"] for c in cells if c["tuned_differs"]],
        "tuned_differs_and_wins": [c["dataset"] for c in cells
                                   if c["tuned_wins_wall_clock"]],
    }

    with open(args.out, "w") as f:
        json.dump(out, f, indent=1)
    created = ("smoke" if args.smoke
               else datetime.datetime.now(datetime.timezone.utc)
               .strftime("%Y-%m-%dT%H:%M:%SZ"))
    plan = tplan.build_plan(
        cells, created=created,
        meta={"scale": args.scale, "select": args.select,
              "source": "benchmarks/autotune.py"})
    plan.save(args.plan_out)
    s = out["summary"]
    print(f"[autotune] wrote {args.out} and {args.plan_out} — "
          f"honesty {s['honesty']}, bytes_never_worse="
          f"{s['bytes_never_worse_than_default']}, tuned_differs_and_wins="
          f"{s['tuned_differs_and_wins']}", flush=True)


if __name__ == "__main__":
    main()
