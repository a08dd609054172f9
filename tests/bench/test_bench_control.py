"""The controls of the correctness check, at scale 10 on the CPU: each has to
come out as not correct under the limits that the runs use."""
import json
import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

import jax.numpy as jnp  # noqa: E402

from bench import control, graphgen, reference  # noqa: E402

CFG = dict(scale=10, edge_factor=16, abc=[0.57, 0.19, 0.19], symmetric=True,
           graph_seed=3)
PR = json.loads((ROOT / "bench" / "traffic" / "pr-solves.json").read_text())


@pytest.fixture(scope="module")
def graph():
    src, dst, _ = graphgen.generate(CFG, (1 << 36) + 5)
    return src, dst, reference.EdgeList(src, dst, 1 << CFG["scale"])


def _residual(graph, dtype):
    src, dst, ref = graph
    rank = reference.pagerank_control(
        src, dst, ref.v, damping=PR["damping"], tolerance=PR["tolerance"],
        max_iterations=PR["max_iterations"], dtype=dtype)
    return ref.pr_residual(rank, PR["damping"])


def test_bfloat16_pagerank_fails_the_residual_limit(graph):
    assert _residual(graph, jnp.bfloat16) > 3 * PR["residual_limit"]


def test_float32_reference_pagerank_meets_the_limit(graph):
    assert _residual(graph, jnp.float32) < PR["residual_limit"]


def test_bfs_controls(graph):
    src, dst, ref = graph
    root = int(np.flatnonzero(ref.out_deg > 0)[0])
    want = ref.bfs(root)
    kw = dict(src=src, dst=dst, v=ref.v, root=root)
    # bfloat16 holds small integer levels exactly: no mismatch to find
    exact = control.bfs_control(**kw, dtype=jnp.bfloat16, stop_early=False)
    assert np.array_equal(exact, want)
    early = control.bfs_control(**kw, dtype=jnp.float32, stop_early=True)
    assert np.sum(early != want) > 0
