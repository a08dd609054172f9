"""Compulsory bytes of the graph kernels and the table of chip peaks.

The bytes are those of an int32 CSR, counted from the graph and the
answer, never from the layout the program chose: a change of tiles, padding
or where the gather runs leaves them as they are.

- PageRank, per iteration: every edge's source index (4 B) and, per
  vertex, its offset, its rank, its out-degree and its new rank (16 B).
- BFS, per traversal: each out-edge of a reached vertex once (4 B), and per
  vertex its offset, its level read and written, and its frontier word
  (16 B).

A share of the roofline is bytes / device-busy seconds / peak bytes per
second.  The peaks are keyed by ``device_kind``; a device that is not in
``peaks.json`` is an error, not a default.
"""
from __future__ import annotations

import json
from pathlib import Path

__all__ = ["peaks", "pagerank_bytes", "bfs_bytes", "hbm_share"]

PEAKS_FILE = Path(__file__).with_name("peaks.json")


def peaks(device_kind: str) -> dict:
    table = json.loads(PEAKS_FILE.read_text())
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"{PEAKS_FILE.name}; known: {sorted(table)}")
    return table[device_kind]


def pagerank_bytes(num_vertices: int, num_edges: int, iterations: int) -> int:
    return iterations * (4 * num_edges + 16 * num_vertices)


def bfs_bytes(num_vertices: int, reached_edges: int) -> int:
    return 4 * reached_edges + 16 * num_vertices


def hbm_share(nbytes: int, busy_s: float, device_kind: str) -> float:
    """Per cent of the chip's HBM roofline that ``nbytes`` in ``busy_s``
    device-busy seconds reach."""
    return 100.0 * nbytes / busy_s / peaks(device_kind)["hbm_bytes_per_s"]
