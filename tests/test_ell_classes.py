"""ELL width classes chosen from the degree histogram (``ops.width_classes``).

The chooser splits the distinct padded widths the rows need into at most
``MAX_WIDTH_CLASSES`` contiguous runs with the fewest allocated slots; each
row lands in the narrowest class at least as wide as its degree.  Checked
against a brute-force optimum on small histograms, against the packer's
planes, and against the DBG-bound classes it replaced.
"""
import itertools

import numpy as np
import pytest

from repro.core.reorder import _assign_groups, dbg_spec
from repro.graph import csr
from repro.kernels.edge_map.ops import (CLASS_COST_SLOTS, MAX_WIDTH_CLASSES,
                                        _fewest_slot_runs, _pad_dim,
                                        _pad_dims, ell_tiles,
                                        ell_tiles_sharded, width_classes)


def _slots(classes, row_tile):
    return sum(w * _pad_dim(rows.size, row_tile) for w, rows in classes)


def _needs(deg, width_tile):
    d = deg[deg > 0]
    return np.unique(_pad_dims(d, width_tile), return_counts=True)


def _brute_force_slots(widths, counts, row_tile, max_runs, run_cost=0):
    """The fewest slots, each run charged ``run_cost`` more, over every
    split into at most ``max_runs`` runs."""
    n = len(widths)
    best = None
    for k in range(min(max_runs, n)):
        for cuts in itertools.combinations(range(1, n), k):
            ends = list(cuts) + [n]
            total, a = run_cost * len(ends), 0
            for b in ends:
                total += int(widths[b - 1]) * _pad_dim(
                    int(counts[a:b].sum()), row_tile)
                a = b
            best = total if best is None else min(best, total)
    return best


def _dbg_bound_slots(deg, row_tile, width_tile):
    """Slots of the classes the packer used to take from DBG's group bounds
    (extended by doubling up to the largest degree), bins merged by padded
    width."""
    mean = max(1.0, float(deg.mean()))
    bounds = list(dbg_spec(mean).boundaries)
    while bounds[0] * 2 <= int(deg.max(initial=0)):
        bounds.insert(0, bounds[0] * 2)
    grp = _assign_groups(deg, bounds)
    by_width = {}
    for k in range(len(bounds)):
        sel = (grp == k) & (deg > 0)
        if sel.any():
            w = _pad_dim(int(deg[sel].max()), width_tile)
            by_width[w] = by_width.get(w, 0) + int(sel.sum())
    return sum(w * _pad_dim(n, row_tile) for w, n in by_width.items())


def _degrees(kind, n, seed):
    rng = np.random.default_rng(seed)
    if kind == "power_law":
        return np.minimum(rng.zipf(1.9, n), 20_000).astype(np.int64) - 1
    if kind == "poisson":
        return rng.poisson(16, n).astype(np.int64)
    # every width from 1 to 3000 present: more candidates than classes
    return rng.integers(0, 3000, n).astype(np.int64)


def _csr_of(deg, seed):
    rng = np.random.default_rng(seed)
    indptr = np.concatenate([[0], np.cumsum(deg)]).astype(np.int64)
    indices = rng.integers(0, deg.size, int(indptr[-1])).astype(np.int32)
    return csr.CSR(indptr=indptr, indices=indices)


KINDS = ("power_law", "poisson", "spread")


@pytest.mark.parametrize("kind", KINDS)
@pytest.mark.parametrize("row_tile,width_tile", [(64, 128), (16, 32)])
def test_rows_land_in_narrowest_class_at_least_their_degree(
        kind, row_tile, width_tile):
    deg = _degrees(kind, 20_000, 1)
    classes = width_classes(deg, row_tile=row_tile, width_tile=width_tile)
    widths = sorted(w for w, _ in classes)
    assert [w for w, _ in classes] == widths[::-1]  # widest first
    assert 1 <= len(classes) <= MAX_WIDTH_CLASSES
    seen = np.zeros(deg.size, np.int64)
    for w, rows in classes:
        assert np.all(np.diff(rows) > 0)  # ascending, for the sorted combine
        seen[rows] += 1
        d = deg[rows]
        assert np.all(d <= w)
        narrower = [v for v in widths if v < w]
        if narrower:
            assert np.all(d > max(narrower))
        # every class width is a shape the kernel takes
        assert w == _pad_dim(w, width_tile)
    np.testing.assert_array_equal(seen, (deg > 0).astype(np.int64))


def test_classes_are_capped_where_needs_outnumber_them():
    deg = _degrees("spread", 50_000, 2)
    assert _needs(deg, 128)[0].size > MAX_WIDTH_CLASSES
    assert len(width_classes(deg)) == MAX_WIDTH_CLASSES


@pytest.mark.parametrize("kind", KINDS)
@pytest.mark.parametrize("row_tile,width_tile", [(64, 128), (32, 64)])
def test_allocated_slots_equal_the_planes(kind, row_tile, width_tile):
    deg = _degrees(kind, 3000, 3)
    adj = _csr_of(deg, 3)
    tiles = ell_tiles(adj, row_tile=row_tile, width_tile=width_tile)
    classes = width_classes(deg, row_tile=row_tile, width_tile=width_tile)
    assert sum(int(t.idx.size) for t in tiles) == _slots(classes, row_tile)
    for t, (w, rows) in zip(tiles, classes):
        assert t.idx.shape == (_pad_dim(rows.size, row_tile), w)
        np.testing.assert_array_equal(np.asarray(t.rows), rows)
        np.testing.assert_array_equal(np.asarray(t.deg)[: rows.size],
                                      deg[rows])
        # each row's lanes hold its CSR neighbors in storage order
        r = int(rows[-1])
        np.testing.assert_array_equal(
            np.asarray(t.idx)[rows.size - 1, : deg[r]], adj.neighbors(r))


@pytest.mark.parametrize("seed", range(12))
@pytest.mark.parametrize("max_runs,run_cost", [(1, 0), (2, 0), (3, 0),
                                               (MAX_WIDTH_CLASSES, 0),
                                               (MAX_WIDTH_CLASSES, 2000)])
def test_choice_equals_brute_force_optimum(seed, max_runs, run_cost):
    rng = np.random.default_rng(seed)
    n = int(rng.integers(1, 11))
    widths = np.unique(rng.integers(1, 40, n) * 8)
    counts = rng.integers(1, 200, widths.size)
    row_tile = int(rng.choice([8, 64]))
    chosen = _fewest_slot_runs(widths, counts, row_tile, max_runs, run_cost)
    assert len(chosen) <= max_runs and chosen[-1] == widths[-1]
    assert list(chosen) == sorted(set(chosen))
    cls = np.searchsorted(np.asarray(chosen), widths)
    got = sum(chosen[k] * _pad_dim(int(counts[cls == k].sum()), row_tile)
              for k in range(len(chosen)))
    assert got + run_cost * len(chosen) == _brute_force_slots(
        widths, counts, row_tile, max_runs, run_cost)


def test_width_classes_match_brute_force_on_small_degree_vectors():
    for seed in range(8):
        deg = np.random.default_rng(seed).integers(0, 100, 400)
        widths, counts = _needs(deg, 128)
        classes = width_classes(deg)
        assert _slots(classes, 64) + CLASS_COST_SLOTS * len(classes) == \
            _brute_force_slots(widths, counts, 64, MAX_WIDTH_CLASSES,
                               CLASS_COST_SLOTS)


@pytest.mark.parametrize("kind", ("power_law", "poisson"))
@pytest.mark.parametrize("seed", range(3))
def test_slots_never_exceed_the_dbg_bound_classes(kind, seed):
    deg = _degrees(kind, 100_000, seed)
    new = _slots(width_classes(deg), 64)
    assert new <= _dbg_bound_slots(deg, 64, 128)
    assert new >= int(deg.sum())


def test_no_rows_no_classes():
    assert width_classes(np.zeros(5, np.int64)) == ()
    assert ell_tiles(_csr_of(np.zeros(5, np.int64), 0)) == ()


def _shards_of(deg, d, seed):
    """``d`` shard edge lists whose rows hold the degrees ``deg`` between
    them (row r in shard r % d, under a shard-local id)."""
    rng = np.random.default_rng(seed)
    out = []
    for i in range(d):
        rows = np.arange(i, deg.size, d)
        own = np.repeat(np.arange(rows.size), deg[rows])
        perm = rng.permutation(own.size)  # the packer sorts them itself
        out.append((own[perm].astype(np.int64),
                    rng.integers(0, 1000, own.size)[perm], None))
    return out


@pytest.mark.parametrize("kind", ("power_law", "poisson"))
@pytest.mark.parametrize("shards", [1, 2, 4])
def test_sharded_tiles_take_the_pooled_width_classes(kind, shards):
    """The sharded packer classes rows by the same rule over every shard's
    degrees pooled; each shard's rows land in the narrowest class at least
    their degree, ascending, and one shard packs exactly as ``ell_tiles``."""
    deg = _degrees(kind, 4000, 5)
    tiles, pos = ell_tiles_sharded(_shards_of(deg, shards, 5), id_upper=1000,
                                   with_positions=True)
    classes = width_classes(deg)
    assert [t.idx.shape[2] for t in tiles] == [w for w, _ in classes]
    widths = sorted(w for w, _ in classes)
    for i in range(shards):
        rows = np.arange(i, deg.size, shards)  # shard-local id -> row
        seen = 0
        for t in tiles:
            n = int((t.deg[i] > 0).sum())
            r = t.rows[i, :n]
            assert np.all(np.diff(r) > 0) and np.all(t.deg[i, n:] == 0)
            d = t.deg[i, :n]
            np.testing.assert_array_equal(d, deg[rows[r]])
            w = t.idx.shape[2]
            assert np.all(d <= w)
            narrower = [v for v in widths if v < w]
            if narrower:
                assert np.all(d > max(narrower))
            seen += n
        assert seen == int((deg[rows] > 0).sum())
        assert np.all(pos[i][:, 0] >= 0)  # every edge has its slot
    if shards == 1:
        for t, (w, rows) in zip(tiles, classes):
            assert t.idx.shape[1:] == (_pad_dim(rows.size, 64), w)
