"""The controls of the benchmark's correctness check, run on the chip.

    python3 bench/control.py --workload <cell> --seeds <n> [<n> ...]

For each seed the cell's graph is generated as a run makes it, the plain
reference solve is put in the program's place, and its answer is judged by
the same numbers and limits as a run's:

- PageRank: the reference iteration in bfloat16, the precision below the
  float32 the program states; it has to fail ``pr_residual``.  The same
  iteration in float32 is read beside it as a witness that the limit holds
  for the reference's own rounding.
- BFS: the reference traversal with levels in bfloat16, which holds the
  small integer levels exactly and so cannot fail an exact comparison, and
  the traversal stopped one round before its frontier empties, which breaks
  the guarantee that every reachable vertex gets its exact level; the
  latter has to fail ``bfs_mismatched_levels``.

One JSON line per seed on standard output.  The benchmark's own runs never
run this; it is how the limits in the traffic files were set.
"""
from __future__ import annotations

import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
if __name__ == "__main__":
    sys.path[0:1] = [str(ROOT), str(ROOT / "src")]


def bfs_control(src, dst, v: int, root: int, *, dtype, stop_early: bool):
    """Level-synchronous BFS over the edge list on the device, levels held
    in ``dtype``; ``stop_early`` ends it one round before the frontier
    empties.  Returns float32 numpy levels (inf = unreached)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    @jax.jit
    def rounds(src, dst, root):
        lvl0 = jnp.full((v,), jnp.inf, dtype).at[root].set(0)

        def body(s):
            lvl, _, n = s
            cand = jax.ops.segment_min(lvl[src] + jnp.asarray(1, dtype), dst,
                                       num_segments=v)
            new = jnp.minimum(lvl, cand)
            return new, jnp.any(new != lvl), n + 1

        lvl, _, n = jax.lax.while_loop(lambda s: s[1], body,
                                       (lvl0, True, 0))
        return lvl, n

    lvl, n = rounds(jnp.asarray(src), jnp.asarray(dst), jnp.int32(root))
    lvl = np.asarray(lvl.astype(jnp.float32))
    if stop_early and int(n) > 1:
        # the last round that changed a level (the final round changes
        # none) is undone: its vertices keep inf
        deepest = np.nanmax(np.where(np.isfinite(lvl), lvl, np.nan))
        lvl = np.where(lvl == deepest, np.inf, lvl).astype(np.float32)
    return lvl


def main(argv=None) -> int:
    import argparse

    import numpy as np

    from bench import apps, graphgen, harness, reference

    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    cell = harness.load_cell(args.workload)
    devices = harness.require_devices(cell.chips)
    import jax.numpy as jnp

    cfg, traffic = cell.config, cell.traffic
    v = 1 << cfg["scale"]
    for seed in args.seeds:
        t = time.perf_counter()
        src, dst, perm = graphgen.generate(cfg, seed)
        ref = reference.EdgeList(src, dst, v)
        line = {"workload": cell.name, "seed": seed,
                "device": devices[0].device_kind}
        if traffic["app"] == "pagerank":
            for name, dtype in (("bfloat16", jnp.bfloat16),
                                ("float32", jnp.float32)):
                rank = reference.pagerank_control(
                    src, dst, v, damping=traffic["damping"],
                    tolerance=traffic["tolerance"],
                    max_iterations=traffic["max_iterations"], dtype=dtype)
                line[name] = {
                    "pr_residual": ref.pr_residual(rank, traffic["damping"])}
            line["limit"] = traffic["residual_limit"]
        else:
            roots = apps.search_keys(cfg, traffic, perm, ref.out_deg)
            for name, kw in (("bfloat16", dict(dtype=jnp.bfloat16,
                                               stop_early=False)),
                             ("stopped_early", dict(dtype=jnp.float32,
                                                    stop_early=True))):
                line[name] = {"bfs_mismatched_levels": max(
                    int(np.sum(bfs_control(src, dst, v, int(r), **kw)
                               != ref.bfs(int(r)))) for r in roots)}
            line["limit"] = 0
        line["seconds"] = time.perf_counter() - t
        print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
