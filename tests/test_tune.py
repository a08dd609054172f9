"""repro.tune: space validity, cost-model parity, plan round-trips, auto.

The load-bearing contracts:

  * the analytic geometry mirrors in ``tune.cost`` price EXACTLY what the
    built backends execute (``fused_edge_map_bytes`` over the real tiles) —
    property-tested across tile geometries for both ell and packed;
  * plans persist/load bit-equal (property over sampled configs) and
    ``backend="auto"`` ALWAYS resolves to a valid ``BACKENDS`` entry, plan
    or no plan;
  * tuned-backend app results agree with the flat oracle (min reductions
    bitwise, sums to fp association);
  * the density threshold is a pure traffic choice: results are bitwise
    invariant to it;
  * ``to_arrays`` rejects unknown knobs and warns on (or, strict, rejects)
    knobs its backend cannot consume.
"""
import dataclasses
import json
import math
import os
import warnings

import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.apps import bc, pagerank, sssp, to_arrays
from repro.apps.engine import BACKENDS, EllBackend, FlatBackend
from repro.graph import csr
from repro.kernels.edge_map.ops import ell_tiles, fused_edge_map_bytes
from repro.obs.counters import flat_edge_map_bytes
from repro.roofline import DEVICE_KIND_PROFILES, HW, HW_PROFILES
from repro.tune import cost as tcost
from repro.tune import plan as tplan
from repro.tune import search as tsearch
from repro.tune import space as tspace

BASELINES = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmarks", "baselines")


def _rand_graph(n, e, seed, weighted=False):
    rng = np.random.default_rng(seed)
    src = rng.integers(0, n, e)
    dst = rng.integers(0, n, e)
    w = rng.random(e).astype(np.float32) + 0.01 if weighted else None
    return csr.from_edges(src, dst, n, weights=w)


@pytest.fixture(scope="module")
def g():
    return _rand_graph(300, 3600, seed=7)


@pytest.fixture(scope="module")
def gw():
    return _rand_graph(300, 3600, seed=7, weighted=True)


# ---------------------------------------------------------------------------
# space
# ---------------------------------------------------------------------------

def test_grid_configs_canonical_and_valid():
    space = tspace.engine_space()
    grid = space.grid()
    assert len(grid) > 50
    seen = set()
    for cfg in grid:
        assert cfg == tspace.canonical(cfg)
        assert cfg["backend"] in BACKENDS and cfg["backend"] != "auto"
        extra = set(cfg) - {"backend"}
        assert extra <= tspace.backend_knobs(cfg["backend"])
        key = tcost.config_key(cfg)
        assert key not in seen  # canonical dedupe: no no-op dimensions
        seen.add(key)
    # the knob-free flat backend collapses to exactly ONE candidate
    assert sum(1 for c in grid if c["backend"] == "flat") == 1


def test_sampled_configs_are_contained():
    space = tspace.full_space()
    for cfg in space.sample(40, seed=3):
        assert space.contains(cfg)
    assert space.sample(10, seed=5) == space.sample(10, seed=5)


def test_default_config_is_a_grid_point():
    keys = {tcost.config_key(c) for c in tspace.engine_space().grid()}
    assert tcost.config_key(
        tspace.split_config(tspace.DEFAULT_CONFIG)[0]) in keys


def test_canonical_drops_inapplicable_knobs():
    a = tspace.canonical({"backend": "flat", "row_tile": 32})
    assert a == {"backend": "flat"}
    # app/stream-scope knobs survive any backend
    b = tspace.canonical({"backend": "flat", "density_threshold": 0.1,
                          "hysteresis": 0.5})
    assert b["density_threshold"] == 0.1 and b["hysteresis"] == 0.5


def test_split_config_scopes():
    eng, app, stream = tspace.split_config(
        {"backend": "ell", "row_tile": 32, "density_threshold": 0.02,
         "hysteresis": 0.25})
    assert eng == {"backend": "ell", "row_tile": 32}
    assert app == {"density_threshold": 0.02}
    assert stream == {"hysteresis": 0.25}


def test_validate_knobs():
    acc, ign = tspace.validate_knobs("ell", {"row_tile": 32, "slot_align": 8})
    assert acc == {"row_tile": 32} and ign == {"slot_align": 8}
    with pytest.raises(ValueError, match="unknown backend knob"):
        tspace.validate_knobs("ell", {"bogus": 1})
    with pytest.raises(ValueError, match="no-ops on backend"):
        tspace.validate_knobs("flat", {"row_tile": 32}, strict=True)
    with pytest.raises(ValueError, match="unknown edge-map backend"):
        tspace.validate_knobs("nope", {})


# ---------------------------------------------------------------------------
# roofline HW profiles (satellite)
# ---------------------------------------------------------------------------

def test_hw_profiles(monkeypatch):
    assert HW.profile("v5e").name == "v5e"
    # no name: the profile of the device JAX runs on; a CPU has none
    monkeypatch.delenv("REPRO_HW_PROFILE", raising=False)
    with pytest.raises(ValueError, match="no hardware profile"):
        HW.profile()
    assert HW_PROFILES[DEVICE_KIND_PROFILES["TPU v5 lite"]].name == "v5e"
    cpu = HW.profile("cpu-interpret")
    assert math.isinf(cpu.peak_flops)
    assert "v5e" in HW_PROFILES and "cpu-interpret" in HW_PROFILES
    with pytest.raises(ValueError, match="unknown hardware profile"):
        HW.profile("nope")


def test_hw_profile_env_override(monkeypatch):
    monkeypatch.setenv("REPRO_HW_PROFILE", "cpu-interpret")
    assert HW.profile().name == "cpu-interpret"


def test_dispatch_free_ranking_is_pure_bytes(g):
    # with an infinite FLOP peak and no dispatch cost, the three-term price
    # collapses to the memory term: ranking must be pure modeled bytes
    hw = dataclasses.replace(HW.profile("cpu-interpret"),
                             dispatch_overhead=0.0)
    gc = tcost.GraphCost.from_graph(g)
    cfgs = tspace.engine_space().grid()
    ranked = tcost.rank(gc, cfgs, app="pr", hw=hw)
    bytes_order = [s.model_bytes for s in ranked]
    assert bytes_order == sorted(bytes_order)


def test_cpu_interpret_prices_dispatch(g):
    # the interpreter profile charges per grid step, so a coarse tiling
    # (fewer steps) must rank ahead of a fine tiling of the same backend
    # even when the fine tiling models fewer bytes
    hw = HW.profile("cpu-interpret")
    assert hw.dispatch_overhead > 0.0
    assert HW.profile("v5e").dispatch_overhead == 0.0
    gc = tcost.GraphCost.from_graph(g)
    coarse = {"backend": "ell", "row_tile": 128, "width_tile": 256}
    fine = {"backend": "ell", "row_tile": 16, "width_tile": 32}
    s_coarse = tcost.config_steps(gc, coarse, app="pr")
    s_fine = tcost.config_steps(gc, fine, app="pr")
    assert s_coarse < s_fine
    ranked = tcost.rank(gc, [coarse, fine], app="pr", hw=hw)
    assert ranked[0].config["row_tile"] == 128
    # flat/arrays launch no Pallas grid: zero dispatch steps
    assert tcost.config_steps(gc, {"backend": "flat"}, app="pr") == 0


# ---------------------------------------------------------------------------
# cost-model parity with the built backends
# ---------------------------------------------------------------------------

@settings(max_examples=10, deadline=None)
@given(st.sampled_from([16, 32, 64, 128]), st.sampled_from([32, 64, 128]),
       st.integers(0, 1000))
def test_ell_cost_parity(row_tile, width_tile, seed):
    """The degree-vector geometry mirror prices EXACTLY what ell_tiles
    builds, for every pass shape the apps use."""
    gg = _rand_graph(200, 2400, seed)
    tiles = ell_tiles(gg.in_csr, row_tile=row_tile, width_tile=width_tile)
    gc = tcost.GraphCost.from_graph(gg)
    cfg = {"backend": "ell", "row_tile": row_tile, "width_tile": width_tile}
    for profile in [p for ps in tcost.APP_PROFILES.values() for p in ps]:
        actual = fused_edge_map_bytes(
            tiles, gg.num_vertices,
            use_weights=profile.use_weights and gc.weighted,
            frontier=profile.frontier,
            push_init=profile.direction == "push",
            plane_k=profile.plane_k,
            frontier_planar=profile.frontier_planar)
        assert tcost.pass_bytes(gc, cfg, profile) == actual


@pytest.mark.parametrize("knobs", [
    {"row_tile": 64, "width_tile": 128},
    {"row_tile": 32, "width_tile": 64, "slot_align": 8},
    {"row_tile": 64, "width_tile": 128, "slot_align": 32, "hot_groups": 2},
])
def test_packed_cost_parity(g, knobs):
    pb = to_arrays(g, backend="packed", **knobs)
    actual = fused_edge_map_bytes(pb.in_tiles, g.num_vertices)
    cfg = {"backend": "packed", **knobs}
    got = tcost.pass_bytes(gc := tcost.GraphCost.from_graph(g), cfg,
                           tcost.APP_PROFILES["pr"][0])
    assert got == actual
    assert gc.num_edges == g.num_edges


def test_flat_cost_is_the_counters_model(g):
    gc = tcost.GraphCost.from_graph(g)
    p = tcost.PassProfile("push", use_weights=True, frontier=True)
    assert tcost.pass_bytes(gc, {"backend": "flat"}, p) == \
        flat_edge_map_bytes(g.num_edges, g.num_vertices, weighted=False,
                            frontier=True, push_init=True)


def test_rank_and_shortlist_keep_incumbent(g):
    gc = tcost.GraphCost.from_graph(g)
    v5e = HW.profile("v5e")
    ranked = tcost.rank(gc, tspace.engine_space().grid(), app="pr", hw=v5e)
    assert ranked == tcost.rank(gc, tspace.engine_space().grid(), app="pr",
                                hw=v5e)
    sl = tcost.shortlist(ranked, 3, must_include=tspace.DEFAULT_CONFIG)
    want = tcost.config_key(tspace.split_config(tspace.DEFAULT_CONFIG)[0])
    assert any(tcost.config_key(s.config) == want for s in sl)
    assert len(sl) <= 4


# ---------------------------------------------------------------------------
# plans: persistence, lookup, auto resolution
# ---------------------------------------------------------------------------

@st.composite
def _plan_configs(draw):
    space = tspace.engine_space()
    grid = space.grid()
    cfg = dict(grid[draw(st.integers(0, len(grid) - 1))])
    if draw(st.integers(0, 1)):
        cfg["density_threshold"] = draw(
            st.sampled_from([0.01, 0.05, 0.2]))
    return cfg


@settings(max_examples=15, deadline=None)
@given(st.lists(_plan_configs(), min_size=1, max_size=4), st.integers(0, 99))
def test_plan_roundtrip_bit_equal_and_resolves(configs, seed):
    # no pytest fixtures here: the hypothesis fallback stub cannot inject
    # them alongside drawn values
    import tempfile
    entries = [{"family": f"fam{i}",
                "features": tplan.graph_features(
                    _rand_graph(50 + 10 * i, 500, seed + i)),
                "configs": {"default": c}}
               for i, c in enumerate(configs)]
    plan = tplan.build_plan(entries)
    with tempfile.TemporaryDirectory() as tmp:
        path = os.path.join(tmp, f"plan{seed}.json")
        plan.save(path)
        loaded = tplan.ExecutionPlan.load(path)
        assert loaded.to_json() == plan.to_json()
        with open(path) as fh:  # byte-level: re-saving the load is identity
            first = fh.read()
        loaded.save(path)
        with open(path) as fh:
            assert fh.read() == first
    # whatever family a graph lands on, auto resolves to a buildable config
    gg = _rand_graph(120, 1200, seed)
    name, kw = tplan.resolve_auto(gg, plan=loaded)
    assert name in BACKENDS and name != "auto"
    acc, ign = tspace.validate_knobs(name, kw)
    assert not ign


def test_plan_schema_mismatch_raises(tmp_path):
    p = os.path.join(str(tmp_path), "bad.json")
    with open(p, "w") as fh:
        json.dump({"schema": 99, "entries": []}, fh)
    with pytest.raises(tplan.PlanError, match="schema"):
        tplan.ExecutionPlan.load(p)


def test_nearest_family_lookup(g):
    far = tplan.graph_features(_rand_graph(5000, 10000, 1))
    near = tplan.graph_features(g)
    plan = tplan.build_plan([
        {"family": "far", "features": far,
         "configs": {"default": {"backend": "flat"}}},
        {"family": "near", "features": near,
         "configs": {"default": {"backend": "packed"},
                     "sssp": {"backend": "ell", "row_tile": 32}}},
    ])
    cfg, fam = plan.lookup(tplan.graph_features(g))
    assert fam == "near" and cfg["backend"] == "packed"
    cfg, _ = plan.lookup(tplan.graph_features(g), app="sssp")
    assert cfg == {"backend": "ell", "row_tile": 32}


def test_auto_without_plan_is_the_default(g):
    # conftest disables plans: auto must fall back to the hand-tuned default
    assert tplan.auto_config(g) == tspace.canonical(
        dict(tspace.DEFAULT_CONFIG))
    assert isinstance(to_arrays(g, backend="auto"), EllBackend)


def test_auto_resolves_active_plan(g):
    plan = tplan.build_plan([{
        "family": "f", "features": tplan.graph_features(g),
        "configs": {"default": {"backend": "flat"},
                    "sssp": {"backend": "ell", "row_tile": 32,
                             "density_threshold": 0.2}}}])
    tplan.set_active_plan(plan)
    assert isinstance(to_arrays(g, backend="auto"), FlatBackend)
    assert isinstance(to_arrays(g, backend="auto", app="sssp"), EllBackend)
    assert tplan.auto_config(g, app="sssp")["density_threshold"] == 0.2
    # explicit kwargs override the plan
    eb = to_arrays(g, backend="auto", app="sssp", row_tile=16)
    assert eb.row_tile == 16


def test_env_plan_discovery(tmp_path, monkeypatch, g):
    plan = tplan.build_plan([{
        "family": "f", "features": tplan.graph_features(g),
        "configs": {"default": {"backend": "packed", "row_tile": 32}}}])
    path = os.path.join(str(tmp_path), "env_plan.json")
    plan.save(path)
    monkeypatch.setenv("REPRO_TUNE_PLAN", path)
    tplan.set_active_plan()  # restore discovery (conftest disabled plans)
    got = tplan.get_active_plan()
    assert got is not None and got.entries[0].family == "f"
    assert tplan.auto_config(g)["backend"] == "packed"


def test_auto_app_results_match_flat_oracle(g, gw):
    plan = tplan.build_plan([{
        "family": "f", "features": tplan.graph_features(g),
        "configs": {"default": {"backend": "packed", "row_tile": 32,
                                "width_tile": 64},
                    "sssp": {"backend": "ell", "row_tile": 16,
                             "density_threshold": 0.1}}}])
    tplan.set_active_plan(plan)
    fa, faw = to_arrays(g), to_arrays(gw)
    aa = to_arrays(g, backend="auto")
    aaw = to_arrays(gw, backend="auto", app="sssp")
    # sum reduction: fp association only
    r_flat, _ = pagerank(fa)
    r_auto, _ = pagerank(aa)
    np.testing.assert_allclose(np.asarray(r_flat), np.asarray(r_auto),
                               atol=2e-6)
    # min reduction: bitwise, including the tuned density threshold
    dt = tplan.auto_config(gw, app="sssp").get("density_threshold")
    d_flat, _ = sssp(faw, jnp.int32(0))
    d_auto, _ = sssp(aaw, jnp.int32(0), density_threshold=dt)
    np.testing.assert_array_equal(np.asarray(d_flat), np.asarray(d_auto))


# ---------------------------------------------------------------------------
# to_arrays knob validation (satellite)
# ---------------------------------------------------------------------------

def test_to_arrays_warns_and_drops_ignored_knobs(g):
    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        ga = to_arrays(g, backend="flat", row_tile=32)
    assert isinstance(ga, FlatBackend)
    assert any("ignoring knob" in str(x.message) for x in w)


def test_to_arrays_strict_and_unknown(g):
    with pytest.raises(ValueError, match="no-ops on backend"):
        to_arrays(g, backend="flat", row_tile=32, strict=True)
    with pytest.raises(ValueError, match="unknown backend knob"):
        to_arrays(g, backend="ell", bogus=1)
    with warnings.catch_warnings():
        warnings.simplefilter("error")  # valid knobs must NOT warn
        to_arrays(g, backend="packed", slot_align=8, hot_groups=2)


# ---------------------------------------------------------------------------
# density threshold: a pure traffic choice
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("dt", [0.01, 0.5])
def test_density_threshold_bitwise_invariance(g, gw, dt):
    gaw = to_arrays(gw)
    d0, _ = sssp(gaw, jnp.int32(0))
    d1, _ = sssp(gaw, jnp.int32(0), density_threshold=dt)
    np.testing.assert_array_equal(np.asarray(d0), np.asarray(d1))
    ga = to_arrays(g)
    c0, dist0, _ = bc(ga, jnp.int32(0))
    c1, dist1, _ = bc(ga, jnp.int32(0), density_threshold=dt)
    np.testing.assert_array_equal(np.asarray(dist0), np.asarray(dist1))
    np.testing.assert_allclose(np.asarray(c0), np.asarray(c1), atol=1e-5)


def test_batched_sssp_density_threshold(gw):
    from repro.serve.batched import batched_sssp
    ga = to_arrays(gw)
    roots = jnp.asarray([0, 5, 9], jnp.int32)
    d0, _ = batched_sssp(ga, roots)
    d1, _ = batched_sssp(ga, roots, density_threshold=0.5)
    np.testing.assert_array_equal(np.asarray(d0), np.asarray(d1))


# ---------------------------------------------------------------------------
# the measured sweep
# ---------------------------------------------------------------------------

def test_sweep_audit_trail_and_feasibility(g):
    res = tsearch.sweep(g, app="pr", top_k=3, extras=2,
                        reps_schedule=(1, 1), select="bytes",
                        hw=HW.profile("v5e"))
    gc = tcost.GraphCost.from_graph(g)
    budget = tcost.default_budget(gc, "pr")
    # selection is byte-feasible: never more modeled traffic than default
    assert tcost.app_bytes(
        gc, tspace.split_config(res.chosen)[0], "pr") <= budget
    assert res.num_measured >= 4  # shortlist + extras (+ incumbent)
    sources = {t.source for t in res.trials}
    assert "extra" in sources and ("default" in sources or
                                   "shortlist" in sources)
    for t in res.trials:
        assert t.rounds or t.error  # every candidate left a trail
    # halving eliminated someone in round 0
    assert any(t.eliminated_round == 0 for t in res.trials)
    json.dumps(res.to_json())  # the audit trail is JSON-able


def test_committed_smoke_plan_loads_and_resolves(g):
    path = os.path.join(BASELINES, "PLAN_smoke.json")
    plan = tplan.ExecutionPlan.load(path)
    assert plan.entries
    for entry in plan.entries:
        for cfg in entry.configs.values():
            eng = tspace.split_config(cfg)[0]
            assert eng["backend"] in BACKENDS and eng["backend"] != "auto"
    name, kw = tplan.resolve_auto(g, plan=plan)
    to_arrays(g, backend=name, **kw)  # buildable, no warning path


# ---------------------------------------------------------------------------
# serve integration: backend="auto" end to end
# ---------------------------------------------------------------------------

def test_serve_auto_backend(g):
    from repro.serve import GraphServeService, Query, ServeConfig
    svc = GraphServeService(g, ServeConfig(max_width=2, backend="auto"))
    svc.submit(Query(kind="pagerank"))
    svc.submit(Query(kind="pagerank"))
    res = svc.drain()
    assert len(res) == 2
    ref, _ = pagerank(to_arrays(svc.stream.snapshot()))
    np.testing.assert_allclose(res[0].value, np.asarray(ref), atol=1e-5)
