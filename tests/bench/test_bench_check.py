"""Whole runs of the harness on the CPU at a small scale: a sound program
is judged correct, and each fault planted in the program underneath the
timed path is judged not correct.  Only the harness's look for a chip is
skipped."""
import dataclasses
import json
import sys
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from bench import harness  # noqa: E402

SMALL = dict(scale=9, edge_factor=8)
SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())
# BFS traffic on kron, a cell that BENCHMARK.json does not list yet
SPEC["workloads"].append({"name": "kron-s22.bfs", "config": "gap-kron-s22",
                          "traffic": "bfs-roots", "chips": 1})
SPEC["end_to_end"].append({"name": "bfs_solve_s", "unit": "s",
                           "workloads": ["kron-s22.bfs"]})


def run_cell(name, seed=(1 << 35) + 17):
    cell = harness.load_cell(name, SPEC)
    cell.config = dict(cell.config, **SMALL)
    args = harness.parse(["--workload", name, "--seed", str(seed),
                          "--seconds", "0", "--trace", "0"])
    return harness.run(args, time.perf_counter(), cell, jax.devices()[:1])


def _wrap_solver(monkeypatch, name, change):
    """Replace ``repro.apps.<name>`` by a jitted solver whose answer passes
    through ``change(answer, ga)`` where it is produced."""
    import repro.apps as apps_pkg

    orig = getattr(apps_pkg, name)
    static = ("max_iters",) if name == "pagerank" else ()  # as the program

    def broken(ga, *args, **kw):
        out, it = orig(ga, *args, **kw)
        return change(out, ga), it

    monkeypatch.setattr(apps_pkg, name,
                        jax.jit(broken, static_argnames=static))


def _unchanged_state(rank, ga):
    return jnp.full_like(rank, 1.0 / rank.shape[0])


def _altered_answer(out, ga):
    i = jnp.argmax(ga.in_deg)
    return out.at[i].add(jnp.where(jnp.isfinite(out[i]), 1e-3, 0) + 1)


def _drop_half_the_rows(monkeypatch, request):
    """Half of every ELL group's rows are left out of the edge map."""
    from repro.kernels.edge_map import ops

    orig = ops.ell_edge_map_pallas
    # the jitted solvers were traced with the sound kernel: trace afresh,
    # with the broken one now and with the sound one after the test
    jax.clear_caches()
    request.addfinalizer(jax.clear_caches)

    def broken(x, idx, deg, **kw):
        half = jnp.arange(deg.shape[0]) >= deg.shape[0] // 2
        return orig(x, idx, jnp.where(half, 0, deg), **kw)

    monkeypatch.setattr(ops, "ell_edge_map_pallas", broken)


def _wrong_mapping(monkeypatch):
    """The program relabels by one mapping and reports another."""
    from repro.core import reorder

    orig = reorder.reorder_graph

    def broken(g, technique, **kw):
        g2, res = orig(g, technique, **kw)
        return g2, dataclasses.replace(res, mapping=np.roll(res.mapping, 1))

    monkeypatch.setattr(reorder, "reorder_graph", broken)


FAULTS = {
    "answer_altered": lambda mp, rq, app: _wrap_solver(mp, app,
                                                       _altered_answer),
    "half_rows_dropped": lambda mp, rq, app: _drop_half_the_rows(mp, rq),
    "wrong_dbg_mapping": lambda mp, rq, app: _wrong_mapping(mp),
}


@pytest.mark.parametrize("cell", ["kron-s22.pr", "urand-s22.pr",
                                  "kron-s22.bfs"])
def test_sound_program_is_correct(cell):
    res = run_cell(cell)
    assert res["correct"] is True, res["checks"]
    assert res["failed"] == 0 and res["attempted"] >= 1
    assert list(res)[-1] == "checks"
    for c in res["checks"].values():
        assert c["value"] <= c["limit"]


def test_bfs_solve_traverses_from_every_key():
    """A BFS solve is one traversal from each of the traffic's search keys,
    and its time is the window's over the traversals."""
    res = run_cell("kron-s22.bfs")
    keys = harness.load_cell("kron-s22.bfs", SPEC).traffic["keys"]
    assert keys >= 2 and res["attempted"] == keys
    assert set(res["metrics"]) == {"bfs_solve_s", "setup_s"}


@pytest.mark.parametrize("fault", sorted(FAULTS) + ["state_unchanged"])
def test_pagerank_fault_is_not_correct(monkeypatch, request, fault):
    if fault == "state_unchanged":
        _wrap_solver(monkeypatch, "pagerank", _unchanged_state)
    else:
        FAULTS[fault](monkeypatch, request, "pagerank")
    res = run_cell("kron-s22.pr")
    assert res["correct"] is False, res["checks"]
    assert res["failed"] == res["attempted"] >= 1


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_bfs_fault_is_not_correct(monkeypatch, request, fault):
    FAULTS[fault](monkeypatch, request, "sssp")
    res = run_cell("kron-s22.bfs")
    assert res["correct"] is False, res["checks"]
    assert res["checks"]["bfs_mismatched_levels"]["value"] > 0
