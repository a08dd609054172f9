"""The per-layer metric readers on a hand-made traced run."""
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from bench import devtrace, harness, roofline  # noqa: E402


def traced_run(app, pallas_s=0.5):
    summary = devtrace.TraceSummary(window_s=10.0, busy_s=8.0,
                                    pallas_s=pallas_s, devices=1,
                                    device_ops=[], idle_gaps=[])
    return harness.Run(app=app, device_kind="TPU v5 lite",
                       phases={"csr_build": 1.5, "layout": 2.5,
                               "reorder_program": 3.5},
                       iterations=[20], trace=summary,
                       traced_bytes=roofline.pagerank_bytes(100, 1600, 20))


def read(metric, run):
    return harness.load_reader(metric)(run)


def test_pagerank_readers():
    run = traced_run("pagerank")
    assert read("pr_iters", run) == 20
    assert read("device_idle.pr", run) == pytest.approx(20.0)
    assert read("pallas_share.pr", run) == pytest.approx(6.25)
    assert read("edge_map_roofline.pr", run) == pytest.approx(
        100 * 20 * (4 * 1600 + 16 * 100) / 8.0 / 819e9)
    assert read("csr_build_s", run) == 1.5
    assert read("layout_s", run) == 2.5
    assert read("reorder_s", run) == 3.5


@pytest.mark.parametrize("metric", ["pr_iters"])
def test_readers_of_another_app_find_nothing(metric):
    assert read(metric, traced_run("bfs")) is None


@pytest.mark.parametrize("app", ["pagerank", "bfs"])
@pytest.mark.parametrize("family", ["device_idle", "pallas_share",
                                    "edge_map_roofline"])
def test_a_family_reader_serves_each_app(family, app):
    """``<family>.<app>`` has no file of its own: the family's reader reads
    the run of any app, and BENCHMARK.json names the metric's cells."""
    run = traced_run(app)
    assert read(f"{family}.x", run) == read(family, run) is not None


def test_no_pallas_kernel_no_share():
    assert read("pallas_share.pr", traced_run("pagerank", 0.0)) is None
