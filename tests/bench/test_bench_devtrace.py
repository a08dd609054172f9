"""The reduction from a profiler trace to busy, idle, Pallas share and the
breakdown: on hand-made traces with known answers, and on a small trace
recorded on a TPU v5e (one PageRank solve at scale 12 through the harness)."""
import sys
from pathlib import Path
from types import SimpleNamespace as NS

import pytest

ROOT = Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from bench import devtrace  # noqa: E402

RECORDED = Path(__file__).with_name("data") / "pr-s12.xplane.pb.gz"
MS = 1_000_000


def ev(name, start_ms, dur_ms, **stats):
    return NS(name=name, start_ns=start_ms * MS, duration_ns=dur_ms * MS,
              stats=list(stats.items()))


def profile(device_lines, host_events):
    planes = [NS(name="/host:CPU",
                 lines=[NS(name="python", events=host_events)])]
    for i, events in enumerate(device_lines):
        planes.append(NS(name=f"/device:TPU:{i}",
                         lines=[NS(name="XLA Modules", events=[]),
                                NS(name="XLA Ops", events=events)]))
    return NS(planes=planes)


HOST = [ev("bench.window", 10, 100), ev("bench.dispatch", 10, 20),
        ev("bench.wait", 30, 80), ev("unrelated", 0, 200)]


def test_busy_idle_and_gaps_on_one_device():
    ops = [ev("fusion.1", 5, 10),  # half before the window: 5 ms count
           ev("gather.2", 40, 20),
           ev("gather.2", 50, 20),  # overlaps: union is 40..70
           ev('%k.3 = f32[8]{0} custom-call(f32[8]{0} %x), '
              'custom_call_target="tpu_custom_call"', 80, 10),
           ev("late", 120, 5)]  # after the window
    s = devtrace.summarize(profile([ops], HOST))
    assert s.window_s == pytest.approx(0.100)
    assert s.busy_s == pytest.approx(0.045)
    assert s.idle_share == pytest.approx(0.55)
    assert s.pallas_s == pytest.approx(0.010)
    assert s.pallas_share == pytest.approx(0.010 / 0.045)
    # gaps 15..40 (mid 27.5: dispatch), 70..80 and 90..110 (wait)
    assert [n for n, _ in s.idle_gaps] == ["bench.dispatch", "bench.wait",
                                           "bench.wait"]
    assert [g for _, g in s.idle_gaps] == pytest.approx([0.025, 0.020,
                                                         0.010])
    assert s.device_ops[0] == ("gather.2", pytest.approx(0.040))
    assert dict(s.device_ops)["fusion.1"] == pytest.approx(0.005)
    assert "late" not in dict(s.device_ops)


def test_busy_is_averaged_over_devices():
    a = [ev("x", 10, 100)]
    b = [ev("x", 10, 50)]
    s = devtrace.summarize(profile([a, b], HOST))
    assert s.devices == 2
    assert s.busy_s == pytest.approx(0.075)
    assert s.pallas_share == 0


def test_no_window_or_no_device_is_an_error():
    with pytest.raises(ValueError, match="bench.window"):
        devtrace.summarize(profile([[ev("x", 0, 1)]], HOST[1:]))
    with pytest.raises(ValueError, match="device plane"):
        devtrace.summarize(profile([], HOST))


def test_breakdown_keeps_ten_entries():
    ops = [ev(f"op.{i}", 10 + 5 * i, 1) for i in range(15)]
    s = devtrace.summarize(profile([ops], HOST))
    assert len(s.device_ops) == 10 and len(s.idle_gaps) == 10


def test_op_names_from_hlo_text():
    assert devtrace.op_name(
        "%while.4 = (f32[4096]{0:T(1024)}, u32[]{:S(2)}) while((f32[4096]"
        "{0:T(1024)}) %tuple.1)") == ("while.4", "while")
    assert devtrace.op_name(
        "%body.54 = f32[1,8]{1,0:T(1,128)S(1)} custom-call(f32[1,1152,8] "
        "%reshape.455), custom_call_target=\"tpu_custom_call\"") == \
        ("body.54", "custom-call")
    assert devtrace.op_name("jit_pagerank(42)") == ("jit_pagerank(42)", "")


def test_control_flow_spans_do_not_count_as_busy():
    loop = ev("%while.1 = (f32[8]{0}) while((f32[8]{0}) %t)", 10, 100)
    body = [ev("%fusion.2 = f32[8]{0} fusion(f32[8]{0} %p)", 20, 10),
            ev("%body.3 = f32[8]{0} custom-call(f32[8]{0} %q), "
               'custom_call_target="tpu_custom_call"', 40, 10),
            ev("%custom-call.4 = f32[8]{0} custom-call(f32[8]{0} %q), "
               'custom_call_target="ConcatBitcast"', 60, 10)]
    s = devtrace.summarize(profile([[loop] + body], HOST))
    assert s.busy_s == pytest.approx(0.030)
    assert s.pallas_s == pytest.approx(0.010)  # Mosaic kernels only
    assert s.device_ops[0][0] == "fusion.2 (fusion) f32[8]"


def test_recorded_chip_trace():
    s = devtrace.summarize_file(RECORDED)
    assert s.devices == 1
    # the numbers this reduction read from the trace when it was recorded
    assert s.window_s == pytest.approx(0.017427949, rel=1e-9)
    assert s.busy_s == pytest.approx(0.011759972, rel=1e-9)
    assert s.pallas_s == pytest.approx(2.1596e-05, rel=1e-9)
    assert 0 < s.pallas_share < 0.01
    assert s.device_ops[0] == ("fusion.89 (fusion) f32[32768]",
                               pytest.approx(0.002806269, rel=1e-9))
    assert s.idle_gaps[0] == ("bench.dispatch",
                              pytest.approx(0.004516487, rel=1e-9))
    assert len(s.device_ops) == len(s.idle_gaps) == 10
    assert s.busy_s <= s.window_s
