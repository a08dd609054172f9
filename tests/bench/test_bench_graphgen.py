"""The device Kronecker generator against the Graph500 specification's
statistics, at scale 10 on the CPU."""
import sys
from math import comb
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from bench import graphgen  # noqa: E402

SCALE = 10
KRON = dict(scale=SCALE, edge_factor=16, abc=[0.57, 0.19, 0.19],
            symmetric=True, graph_seed=7)
URAND = dict(KRON, abc=[0.25, 0.25, 0.25])
SEED = (1 << 40) + 12345  # past 32 bits, as the benchmark's seeds are


@pytest.fixture(scope="module")
def kron():
    return graphgen.generate(KRON, SEED)


def _arc_keys(src, dst, v):
    return src.astype(np.int64) * v + dst


@pytest.mark.parametrize("cfg", [KRON, URAND], ids=["kron", "urand"])
def test_exact_edge_count_no_loops_no_repeats(cfg):
    """GAP's builder: both arcs of every drawn pair, no self-loops, no
    repeats; the arcs are what 16 V draws leave."""
    src, dst, perm = graphgen.generate(cfg, SEED)
    v, draws = 1 << SCALE, 16 << SCALE
    assert np.array_equal(np.sort(perm), np.arange(v))
    assert src.shape == dst.shape and src.dtype == np.int32
    assert 0 < src.shape[0] <= 2 * draws and src.shape[0] % 2 == 0
    assert 0 <= min(src.min(), dst.min()) and max(src.max(), dst.max()) < v
    assert not np.any(src == dst)
    keys = _arc_keys(src, dst, v)
    assert np.unique(keys).size == keys.size
    assert np.array_equal(np.sort(keys), np.sort(_arc_keys(dst, src, v)))


@pytest.mark.parametrize("symmetric", [False, None])
def test_a_directed_configuration_is_refused(symmetric):
    cfg = dict(URAND, symmetric=symmetric)
    if symmetric is None:
        del cfg["symmetric"]
    with pytest.raises(ValueError, match="symmetric"):
        graphgen.generate(cfg, SEED)


def _canonical(src, dst, perm):
    """The edge set in drawn ids, sorted: ``perm[c]`` is the id of drawn
    vertex ``c``."""
    inv = np.argsort(perm)
    return np.sort(inv[src].astype(np.int64) * len(perm) + inv[dst])


def test_seed_relabels_the_same_graph(kron):
    again = graphgen.generate(KRON, SEED)
    assert all(np.array_equal(a, b) for a, b in zip(again, kron))
    for seed in (SEED + 1, SEED + (1 << 33)):  # the high word counts too
        other = graphgen.generate(KRON, seed)
        assert not np.array_equal(other[0], kron[0])
        assert np.array_equal(_canonical(*other), _canonical(*kron))
    # another graph_seed draws another graph
    drawn = graphgen.generate(dict(KRON, graph_seed=8), SEED)
    assert not np.array_equal(_canonical(*drawn), _canonical(*kron))


def test_seed_out_of_range_is_refused():
    with pytest.raises(ValueError):
        graphgen.seed_key(-1)
    with pytest.raises(ValueError):
        graphgen.seed_key(1 << 64)


def _expected_isolated(abc, draws):
    """Share of vertices that no non-loop draw touches.  A vertex whose id
    has h one-bits is a draw's source with probability p = (A+B)^(s-h)
    (C+D)^h, its target with the same p (B = C), and both with q =
    A^(s-h) D^h; the relabelling permutes ids and leaves the share."""
    a, b, c = abc
    d = 1 - a - b - c
    top = a + b
    touch = [2 * top ** (SCALE - h) * (1 - top) ** h
             - 2 * a ** (SCALE - h) * d ** h for h in range(SCALE + 1)]
    return sum(comb(SCALE, h) * (1 - touch[h]) ** draws
               for h in range(SCALE + 1)) / (1 << SCALE)


def test_kron_skew_matches_the_specification(kron):
    src, dst, _ = kron
    v = 1 << SCALE
    deg = np.bincount(src, minlength=v)
    assert np.array_equal(deg, np.bincount(dst, minlength=v))
    want = _expected_isolated(KRON["abc"], 16 << SCALE)
    got = np.mean(deg == 0)
    # binomial standard error of a share over v vertices, five of them
    assert abs(got - want) < 5 * np.sqrt(want * (1 - want) / v) + 0.01
    # heavy tail: the hottest 10% of vertices hold most of the arcs (at
    # scale 10 the dropped repeats flatten the hubs; urand's hottest tenth
    # holds under a fifth)
    assert np.sort(deg)[::-1][: v // 10].sum() / src.size > 0.5


def test_urand_degrees_are_near_poisson():
    src, dst, _ = graphgen.generate(URAND, SEED)
    v = 1 << SCALE
    deg = np.bincount(src, minlength=v)
    assert np.array_equal(deg, np.bincount(dst, minlength=v))
    # each vertex ends 2 x 16 draws on average: Poisson(32), the few
    # repeats and loops dropped
    assert 31 < deg.mean() <= 32
    assert 26 < deg.var() < 38
    assert np.sort(deg)[::-1][: v // 10].sum() / deg.sum() < 0.2
