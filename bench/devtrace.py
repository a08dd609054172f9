"""Reduction of a JAX profiler trace (``.xplane.pb``) to device metrics.

What is read:

- device planes (``/device:TPU:<n>``), line ``XLA Ops``: one event per HLO
  operation that ran on the chip, named by its HLO text
  (``%name = <type> <opcode>(...)``).  Control flow (``while``,
  ``conditional``, ``call``) appears there too, as an event spanning the
  operations it runs; only the other, leaf operations count;
- host planes, any line: the harness's own ``jax.profiler.TraceAnnotation``
  spans, whose names start with ``bench.``.

``bench.window`` bounds the measured interval.  Busy time is the union of
the leaf-operation intervals inside it, averaged over the device planes;
idle is the rest.  Each idle gap is named after the innermost ``bench.``
span open on the host at the gap's midpoint, so a gap reads as what the
host was doing.  Pallas kernels are the ``custom-call`` operations with the
target ``tpu_custom_call`` (Mosaic); every other operation is XLA's.
"""
from __future__ import annotations

import dataclasses
import gzip
import re
from collections import defaultdict
from typing import Dict, List, Optional, Tuple

__all__ = ["TraceSummary", "op_name", "summarize", "summarize_file"]

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
WINDOW_SPAN = "bench.window"
SPAN_PREFIX = "bench."
CONTROL_FLOW = frozenset({"while", "conditional", "call"})
PALLAS_TARGET = 'custom_call_target="tpu_custom_call"'
OPCODE = re.compile(r"\s*([\w-]+)\(")


@dataclasses.dataclass
class TraceSummary:
    window_s: float
    busy_s: float  # averaged over the device planes
    pallas_s: float  # device time in Pallas kernels, averaged likewise
    devices: int
    device_ops: List[Tuple[str, float]]  # most time first, all devices
    idle_gaps: List[Tuple[str, float]]  # longest first

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s

    @property
    def pallas_share(self) -> Optional[float]:
        return self.pallas_s / self.busy_s if self.busy_s > 0 else None


def op_name(text: str) -> Tuple[str, str]:
    """(name, opcode) of an HLO instruction's text; an event name that is
    not HLO text is its own name, with no opcode."""
    if not text.startswith("%") or " = " not in text:
        return text, ""
    name, rest = text[1:].split(" = ", 1)
    end = rest.find(" ")  # a plain type is one token
    if rest.startswith("("):  # a tuple type: skip its balanced parentheses
        depth = 0
        for end, ch in enumerate(rest):
            depth += (ch == "(") - (ch == ")")
            if depth == 0:
                break
        end += 1
    m = OPCODE.match(rest, max(end, 0))
    return name, (m.group(1) if m else "")


def _label(text: str, name: str, opcode: str) -> str:
    """``name (opcode) shape`` for the breakdown: the result's shape
    without its layout tells which ELL group an operation served."""
    if not opcode:
        return name
    shape = text.split(" = ", 1)[1].split(" ", 1)[0].split("{", 1)[0]
    return f"{name} ({opcode}) {shape}" if shape[:1] != "(" else \
        f"{name} ({opcode})"


def _union(intervals: List[Tuple[int, int]]) -> List[Tuple[int, int]]:
    out: List[Tuple[int, int]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def _clip(iv: List[Tuple[int, int]], lo: int, hi: int):
    return [(max(s, lo), min(e, hi)) for s, e in iv if e > lo and s < hi]


def summarize(profile, top: int = 10) -> TraceSummary:
    """Reduce a ``jax.profiler.ProfileData`` to a :class:`TraceSummary`."""
    spans = []  # (start, end, name) of the harness's host annotations
    dev_lines = []
    for plane in profile.planes:
        if DEVICE_PLANE.match(plane.name):
            dev_lines += [list(line.events) for line in plane.lines
                          if line.name == OPS_LINE]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(SPAN_PREFIX):
                        s = int(ev.start_ns)
                        spans.append((s, s + int(ev.duration_ns), ev.name))
    windows = [(s, e) for s, e, n in spans if n == WINDOW_SPAN]
    if len(windows) != 1:
        raise ValueError(f"expected one {WINDOW_SPAN} span, found "
                         f"{len(windows)}")
    if not dev_lines:
        raise ValueError("the trace holds no TPU device plane with an "
                         f"'{OPS_LINE}' line")
    lo, hi = windows[0]
    busy = pallas = 0
    per_op: Dict[str, int] = defaultdict(int)
    gaps: List[Tuple[str, float]] = []
    inner = sorted((s, e, n) for s, e, n in spans if n != WINDOW_SPAN)
    for events in dev_lines:
        iv, pallas_iv = [], []
        for ev in events:
            s = int(ev.start_ns)
            e = s + int(ev.duration_ns)
            if e <= lo or s >= hi:
                continue
            name, opcode = op_name(ev.name)
            if opcode in CONTROL_FLOW:
                continue
            iv.append((s, e))
            if opcode == "custom-call" and PALLAS_TARGET in ev.name:
                pallas_iv.append((s, e))
            label = _label(ev.name, name, opcode)
            per_op[label] += min(e, hi) - max(s, lo)
        merged = _clip(_union(iv), lo, hi)
        busy += sum(e - s for s, e in merged)
        pallas += sum(e - s for s, e in _clip(_union(pallas_iv), lo, hi))
        edges = [lo] + [t for se in merged for t in se] + [hi]
        for g0, g1 in zip(edges[::2], edges[1::2]):
            if g1 > g0:
                mid = (g0 + g1) // 2
                open_ = [n for s, e, n in inner if s <= mid < e]
                gaps.append((open_[-1] if open_ else "no bench span",
                             (g1 - g0) * 1e-9))
    n = len(dev_lines)
    ops = sorted(((k, v * 1e-9) for k, v in per_op.items()),
                 key=lambda kv: -kv[1])
    gaps.sort(key=lambda kv: -kv[1])
    return TraceSummary(window_s=(hi - lo) * 1e-9, busy_s=busy * 1e-9 / n,
                        pallas_s=pallas * 1e-9 / n, devices=n,
                        device_ops=ops[:top], idle_gaps=gaps[:top])


def summarize_file(path, top: int = 10) -> TraceSummary:
    """:func:`summarize` of an ``.xplane.pb`` file, gzipped or not."""
    from jax.profiler import ProfileData

    data = open(path, "rb").read()
    if str(path).endswith(".gz"):
        data = gzip.decompress(data)
    return summarize(ProfileData.from_serialized_xspace(data), top=top)
