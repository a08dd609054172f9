"""Device time by the program's named scopes (``bench/scopes.py``) and the
layout gauge reader ``ell_slots_per_arc``: on hand-made traces and HLO text
with known answers, on a scale-12 solve recorded on a TPU v5e with the
program's scopes, spans and gauges, and in a whole traced run of the
harness on the CPU."""
import gzip
import json
import math
import re
import sys
import time
from pathlib import Path
from types import SimpleNamespace as NS

import jax
import pytest

ROOT = Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from bench import devtrace, harness, scopes  # noqa: E402
from repro.obs.metrics import reset_registry  # noqa: E402

MS = 1_000_000
# a scale-12 ``kron`` PageRank solve recorded on a TPU v5e through the
# harness's set-up with ``repro.obs.trace`` enabled: its profile
# (``bench.window`` around one solve), the text of its compiled solve, and
# the run's phases, iterations, set-up spans and registry snapshot
SCOPED = Path(__file__).with_name("data") / "pr-s12-scoped"


def ev(name, start_ms, dur_ms):
    return NS(name=name, start_ns=start_ms * MS, duration_ns=dur_ms * MS)


def profile(device_lines):
    planes = [NS(name="/host:CPU", lines=[NS(name="python", events=[
        ev("bench.window", 10, 100), ev("bench.dispatch", 10, 20)])])]
    for i, events in enumerate(device_lines):
        planes.append(NS(name=f"/device:TPU:{i}",
                         lines=[NS(name="XLA Ops", events=events)]))
    return NS(planes=planes)


def test_op_seconds_keeps_every_operation_by_name():
    """Clipped to the window, averaged over the device planes, control
    flow left out, no top-N cut."""
    fusion = "%fusion.1 = f32[8]{0} fusion(f32[8]{0} %p)"
    a = [ev(fusion, 5, 10), ev(fusion, 40, 10),
         ev("%while.2 = (f32[8]{0}) while((f32[8]{0}) %t)", 10, 100)]
    b = [ev(fusion, 40, 20)]
    b += [ev(f"%op.{i} = f32[8]{{0}} add(f32[8]{{0}} %p)", 70 + i, 1)
          for i in range(12)]
    op_s = scopes.op_seconds(profile([a, b]))
    assert op_s["fusion.1"] == pytest.approx((0.005 + 0.010 + 0.020) / 2)
    assert len(op_s) == 13 and "while.2" not in op_s
    assert op_s["op.11"] == pytest.approx(0.0005)
    s = devtrace.summarize(profile([a, b]))
    assert sum(op_s.values()) == pytest.approx(s.busy_s)
    with pytest.raises(ValueError, match="device plane"):
        scopes.op_seconds(profile([]))


HLO = """\
ENTRY %main.1 (p: f32[8]) -> f32[8] {
  %fusion.7 = f32[1,64]{1,0} fusion(f32[8]{0} %p), kind=kLoop, calls=%f.7, metadata={op_name="jit(pagerank)/while/body/pagerank.iter/ell.w8/edge_map.gather/gather" source_file="x.py" source_line=3}
  %ell_edge_map.2 = f32[1,8]{1,0} custom-call(f32[1,64]{1,0} %fusion.7), custom_call_target="tpu_custom_call", metadata={op_name="jit(pagerank)/while/body/pagerank.iter/ell.w8/ell_edge_map/pallas_call"}
  ROOT %copy.3 = f32[8]{0} copy(f32[8]{0} %p)
}
"""


def test_scope_map_reads_op_name_metadata():
    m = scopes.scope_map(HLO)
    it = "jit(pagerank)/while/body/pagerank.iter/ell.w8"
    assert m == {"fusion.7": f"{it}/edge_map.gather/gather",
                 "ell_edge_map.2": f"{it}/ell_edge_map/pallas_call"}
    op_s = {"fusion.7": 3.0, "ell_edge_map.2": 0.5, "copy.3": 0.25}
    assert scopes.scope_seconds(op_s, m, "edge_map.gather") == 3.0
    assert scopes.scope_seconds(op_s, m, "ell.w8") == 3.5
    assert scopes.scope_seconds(op_s, m, "pagerank.iter") == 3.5
    # a scope is a whole path component, never a prefix of one
    assert scopes.scope_seconds(op_s, m, "edge_map") == 0
    assert scopes.scope_seconds(op_s, {}, "edge_map.gather") == 0


LOOP_HLO = """\
%relayout.body (p: (u32[], f32[64], f32[8,8])) -> (u32[], f32[64], f32[8,8]) {
  %p = (u32[], f32[64], f32[8,8]) parameter(0)
  %dynamic-update-slice.9 = f32[8,8]{1,0} dynamic-update-slice(%p)
  ROOT %tuple.2 = (u32[], f32[64], f32[8,8]) tuple(%dynamic-update-slice.9)
}

ENTRY %main.1 (x: f32[8]) -> f32[8] {
  %fusion.7 = f32[64]{0} fusion(f32[8]{0} %x), kind=kLoop, calls=%f.7, metadata={op_name="jit(pagerank)/while/body/pagerank.iter/ell.w8/edge_map.gather/gather"}
  %broadcast.1 = f32[8,8]{1,0} broadcast(f32[] %c)
  %tuple.1 = (u32[], f32[64], f32[8,8]) tuple(%fusion.7, %broadcast.1)
  %while.3 = (u32[], f32[64], f32[8,8]) while(%tuple.1), condition=%relayout.cond, body=%relayout.body
  %get-tuple-element.4 = f32[8,8]{1,0} get-tuple-element(%while.3), index=2, metadata={op_name="jit(pagerank)/while/body/pagerank.iter/ell.w8/edge_map.gather/gather"}
  %ell_edge_map.5 = f32[1,8]{1,0} custom-call(%get-tuple-element.4), custom_call_target="tpu_custom_call", metadata={op_name="jit(pagerank)/while/body/pagerank.iter/ell.w8/ell_edge_map/pallas_call"}
  ROOT %copy.6 = f32[8]{0} copy(%x)
}
"""


def test_compiler_made_loops_take_their_readers_scope():
    """A relayout loop the compiler made with no metadata counts under the
    scope of what reads its result; so do the buffers feeding it and the
    steps inside it.  An instruction that nothing named reads stays out."""
    m = scopes.scope_map(LOOP_HLO)
    gather = ("jit(pagerank)/while/body/pagerank.iter/ell.w8/"
              "edge_map.gather/gather")
    for name in ("while.3", "tuple.1", "broadcast.1",
                 "dynamic-update-slice.9"):
        assert m[name] == gather, name
    assert "copy.6" not in m
    op_s = {"fusion.7": 2.0, "broadcast.1": 0.25,
            "dynamic-update-slice.9": 0.5, "ell_edge_map.5": 0.125,
            "copy.6": 1.0}
    assert scopes.scope_seconds(op_s, m, "edge_map.gather") == 2.75
    assert scopes.scope_seconds(op_s, m, "pagerank.iter") == 2.875


def test_span_seconds_sums_complete_events_by_name():
    events = [{"ph": "X", "name": "csr.sort", "dur": 1.5e6},
              {"ph": "X", "name": "csr.sort", "dur": 0.5e6},
              {"ph": "X", "name": "reorder.map", "dur": 250.0},
              {"ph": "i", "name": "csr.sort"}]
    assert scopes.span_seconds(events) == {"csr.sort": 2.0,
                                           "reorder.map": 0.00025}


def recorded():
    run = json.loads(SCOPED.with_suffix(".run.json").read_text())
    text = gzip.decompress(
        SCOPED.with_suffix(".hlo.txt.gz").read_bytes()).decode()
    data = gzip.decompress(SCOPED.with_suffix(".xplane.pb.gz").read_bytes())
    prof = jax.profiler.ProfileData.from_serialized_xspace(data)
    return run, text, scopes.op_seconds(prof), devtrace.summarize(prof)


def test_recorded_chip_solve_by_scope():
    run, text, op_s, summary = recorded()
    m = scopes.scope_map(text)
    # the numbers read from the recording when it was made
    assert summary.busy_s == pytest.approx(0.014092237, rel=1e-9)
    assert len(op_s) == 118 > len(summary.device_ops)
    assert sum(op_s.values()) == pytest.approx(summary.busy_s, rel=1e-6)
    assert set(op_s) <= set(m)  # every operation that ran has a path
    gather_s = scopes.scope_seconds(op_s, m, "edge_map.gather")
    assert 100 * gather_s / summary.busy_s == pytest.approx(
        97.96145920622823, rel=1e-9)
    # the program's scopes hold the whole solve but the entry's fills
    iter_s = scopes.scope_seconds(op_s, m, "pagerank.iter")
    assert iter_s >= 0.99 * summary.busy_s
    assert (gather_s + summary.pallas_s) <= summary.busy_s
    kernels = [n for n in op_s if n.startswith("ell_edge_map.")]
    assert kernels and all(m[n].endswith("/ell_edge_map/pallas_call")
                           for n in kernels)
    # the layout gauge is what the device gathered: the f32 results of the
    # gather fusions, one per ELL width group, sum to ``layout.ell_slots``
    slots, groups = 0, set()
    for name, path in m.items():
        shape = re.search(rf"%{re.escape(name)} = f32\[([\d,]+)\]\S* "
                          r"fusion\(", text)
        if shape and path.endswith("/edge_map.gather/gather") \
                and name in op_s:
            slots += math.prod(int(d) for d in shape.group(1).split(","))
            groups.add(re.search(r"/ell\.w(\d+)/", path).group(1))
    assert slots == run["counters"]["layout.ell_slots"] == 216576
    assert len(groups) >= 4
    # millions of slots gathered per device second of the gather
    rate = slots * run["iterations"][0] / gather_s / 1e6
    assert rate == pytest.approx(141.19445900643979, rel=1e-9)


def test_recorded_set_up_spans_nest_in_the_harness_phases():
    run, *_ = recorded()
    spans, phases = run["spans"], run["phases"]
    assert spans["csr.sort"] <= phases["csr_build"] + spans["reorder.relabel"]
    assert spans["reorder.map"] + spans["reorder.relabel"] \
        <= phases["reorder"]
    assert spans["layout.ell_pack"] + spans["layout.graph_arrays"] \
        <= phases["layout"]


def read_slots_per_arc():
    return harness.load_reader("ell_slots_per_arc")(None)


def test_ell_slots_per_arc_reads_the_layout_gauges():
    registry = reset_registry()
    assert read_slots_per_arc() is None  # no ``ell`` layout built
    registry.gauge("layout.ell_slots").set(3000)
    registry.gauge("layout.arcs").set(2000)
    assert read_slots_per_arc() == 1.5
    reset_registry()


def test_traced_run_reports_slots_per_arc(monkeypatch):
    """A whole ``--trace 1`` run of the harness on the CPU at scale 9 (the
    profiler stubbed: the CPU has no TPU plane) reports the layout's
    slots per arc from the program's own gauges."""
    reset_registry()

    def traced(app):
        out = jax.block_until_ready(app.solve(0))
        return 0.5, out, devtrace.TraceSummary(
            window_s=1.0, busy_s=0.5, pallas_s=0.01, devices=1,
            device_ops=[], idle_gaps=[])

    monkeypatch.setattr(harness, "traced", traced)
    chip = NS(platform="tpu", device_kind="TPU v5 lite",
              memory_stats=lambda: {})
    cell = harness.load_cell("kron-s22.pr")
    cell.config = dict(cell.config, scale=9, edge_factor=8)
    args = harness.parse(["--workload", "kron-s22.pr", "--seed",
                          str((1 << 33) + 5), "--seconds", "0",
                          "--trace", "1"])
    res = harness.run(args, time.perf_counter(), cell, [chip])
    assert res["correct"] is True
    value = res["metrics"]["ell_slots_per_arc"]["value"]
    assert 1.0 <= value < 4.0
    assert value == read_slots_per_arc()
    reset_registry()
