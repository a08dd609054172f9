"""The program's own instrumentation as the benchmark reads it: tracer spans
on the ``jax.profiler`` clock, the set-up spans of the CSR build, reorder
and layout, the layout's slot gauges, and the named scopes that the compiled
solve carries in its HLO metadata."""
import re
import tempfile
from pathlib import Path

import jax
import numpy as np
import pytest

from repro.apps import pagerank, to_arrays
from repro.core import reorder
from repro.graph import csr, generators
from repro.obs import flight as obs_flight
from repro.obs import trace as obs_trace
from repro.obs.metrics import reset_registry
from repro.obs.trace import Tracer


def _host_events(body):
    """(name, start_ns, end_ns) of every event on the ``/host:`` planes of
    a ``jax.profiler`` trace taken around ``body()``."""
    with tempfile.TemporaryDirectory() as tmp:
        jax.profiler.start_trace(tmp)
        try:
            body()
        finally:
            jax.profiler.stop_trace()
        data = jax.profiler.ProfileData.from_file(
            str(next(Path(tmp).rglob("*.xplane.pb"))))
        return [(ev.name, int(ev.start_ns),
                 int(ev.start_ns) + int(ev.duration_ns))
                for plane in data.planes if plane.name.startswith("/host:")
                for line in plane.lines for ev in line.events]


def _spans():
    with jax.profiler.TraceAnnotation("test.outer"):
        with obs_trace.span("obs.a", "t"):
            with obs_trace.span("obs.b", "t"):
                np.sort(np.arange(1000)[::-1])


def test_live_tracer_spans_land_on_the_profiler_host_plane():
    tr = obs_trace.enable()
    try:
        events = _host_events(_spans)
    finally:
        obs_trace.disable()
    by = {n: (s, e) for n, s, e in events}
    assert {"test.outer", "obs.a", "obs.b"} <= set(by)
    # nested as the spans nest, inside the enclosing annotation
    assert by["test.outer"][0] <= by["obs.a"][0] <= by["obs.b"][0]
    assert by["obs.b"][1] <= by["obs.a"][1] <= by["test.outer"][1]
    # the tracer's own Chrome export is unchanged
    assert [e["name"] for e in tr.export()["traceEvents"]] == ["obs.b",
                                                               "obs.a"]


@pytest.mark.parametrize("mode", ["disabled", "flight_ring_only"])
def test_spans_stay_off_the_profiler_unless_a_tracer_is_live(mode):
    assert not obs_trace.enabled()
    ring = obs_flight.install(capacity=16) if mode == "flight_ring_only" \
        else None
    try:
        names = {n for n, _, _ in _host_events(_spans)}
    finally:
        if ring is not None:
            obs_flight.uninstall()
    assert "test.outer" in names
    assert not {"obs.a", "obs.b"} & names
    if ring is not None:  # the ring still records, as before
        assert [e["name"] for e in ring.snapshot_events()] == ["obs.b",
                                                               "obs.a"]


def test_disabled_span_is_the_shared_null_span():
    assert obs_trace.span("csr.sort", "setup") is obs_trace._NULL_SPAN
    assert Tracer(annotate=False).span("x")._annotation is None


def _graph():
    return generators.rmat(1 << 9, 1 << 12, seed=3)


def test_reorder_records_map_then_relabel_with_its_sorts_inside():
    g = _graph()
    tr = obs_trace.enable()
    try:
        reorder.reorder_graph(g, "dbg")
    finally:
        obs_trace.disable()
    evs = [e for e in tr.export()["traceEvents"] if e["ph"] == "X"]
    top = [e["name"] for e in evs if e["name"].startswith("reorder.")]
    assert top == ["reorder.map", "reorder.relabel"]
    relabel = next(e for e in evs if e["name"] == "reorder.relabel")
    inside = [e["name"] for e in evs
              if e["ts"] >= relabel["ts"]
              and e["ts"] + e["dur"] <= relabel["ts"] + relabel["dur"] + 1e-3
              and e is not relabel]
    assert inside.count("csr.sort") == 2
    assert inside.count("csr.from_edges") == 1
    assert all(e["cat"] == "setup" for e in evs)


def test_csr_build_sorts_each_direction_once():
    g = _graph()
    src, dst, _ = csr.to_edges(g)
    tr = obs_trace.enable()
    try:
        csr.from_edges(src, dst, g.num_vertices)
    finally:
        obs_trace.disable()
    names = [e["name"] for e in tr.export()["traceEvents"]]
    assert names == ["csr.sort", "csr.sort", "csr.from_edges"]


def test_ell_layout_sets_its_slot_gauges():
    registry = reset_registry()
    g, _ = reorder.reorder_graph(_graph(), "dbg")
    tr = obs_trace.enable()
    try:
        ga = to_arrays(g, backend="ell")
    finally:
        obs_trace.disable()
    snap = registry.snapshot()
    assert snap["layout.ell_slots"] == sum(
        t.idx.shape[0] * t.idx.shape[1] for t in ga.in_tiles)
    assert snap["layout.arcs"] == g.num_edges
    assert snap["layout.ell_slots"] >= g.num_edges
    names = [e["name"] for e in tr.export()["traceEvents"]]
    assert names.count("layout.ell_pack") == 1
    assert names.count("layout.graph_arrays") == 1
    # the flat layout gathers no ELL slots and sets no gauge
    registry = reset_registry()
    to_arrays(g, backend="flat")
    assert "layout.ell_slots" not in registry.snapshot()


def test_compiled_pagerank_names_its_gather_by_scope():
    """Each ELL width group's gather carries ``pagerank.iter/ell.w<W>/
    edge_map.gather`` in its HLO metadata, and so does the fusion that
    holds it, so a trace's device time sums by scope."""
    g, _ = reorder.reorder_graph(_graph(), "dbg")
    ga = to_arrays(g, backend="ell")
    text = pagerank.lower(ga, max_iters=4).compile().as_text()
    op_names = dict(re.findall(
        r'%([\w.-]+) = .*?metadata=\{[^}]*?op_name="([^"]*)"', text))
    gathers = {n: p for n, p in op_names.items()
               if p.endswith("edge_map.gather/gather")}
    assert gathers
    for p in gathers.values():
        parts = p.split("/")
        assert parts.index("pagerank.iter") < parts.index("edge_map.gather")
    widths = {int(w) for p in gathers.values()
              for w in re.findall(r"/ell\.w(\d+)/", p)}
    assert widths == {t.idx.shape[1] for t in ga.in_tiles}
    assert any("fusion" in n for n in gathers), sorted(gathers)
    combines = [p for p in op_names.values() if "/edge_map.combine/" in p]
    assert combines and all("/pagerank.iter/ell.w" in p for p in combines)
