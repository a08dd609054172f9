"""Compulsory bytes, the table of peaks, and the roofline share."""
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from bench import roofline  # noqa: E402


def test_pagerank_bytes_are_int32_csr_per_iteration():
    # 4 B per edge (source index), 16 B per vertex (offset, rank, degree,
    # new rank), once per iteration
    assert roofline.pagerank_bytes(10, 100, 1) == 4 * 100 + 16 * 10
    assert roofline.pagerank_bytes(1 << 22, 16 << 22, 25) == \
        25 * (4 * (16 << 22) + 16 * (1 << 22))


def test_bfs_bytes_count_reached_edges_once():
    assert roofline.bfs_bytes(10, 0) == 160
    assert roofline.bfs_bytes(10, 7) == 4 * 7 + 160


@pytest.mark.parametrize("kind", ["cpu", "TPU v4", "TPU v5", ""])
def test_unknown_device_kind_is_refused(kind):
    with pytest.raises(KeyError, match="no peaks"):
        roofline.peaks(kind)
    with pytest.raises(KeyError):
        roofline.hbm_share(1, 1.0, kind)


def test_v5e_peak_and_share():
    p = roofline.peaks("TPU v5 lite")
    assert p["hbm_bytes_per_s"] == 819e9
    assert "TPU v5e" in p["source"]
    # 819 GB in one second is the whole roofline
    assert roofline.hbm_share(819e9, 1.0, "TPU v5 lite") == pytest.approx(100)
    assert roofline.hbm_share(819e9, 4.0, "TPU v5 lite") == pytest.approx(25)
