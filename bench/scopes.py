"""Device time by the program's own names: HLO instruction -> named scope.

The program names its device work with ``jax.named_scope`` (``pagerank.iter``,
``ell.w<W>``, ``edge_map.gather``, ``edge_map.combine``), and each compiled
instruction carries that path in its ``op_name`` metadata.  A profiler trace
names each device operation by its HLO instruction.  Joining the two sums
device time by scope, however XLA numbers and shapes its fusions:

- :func:`op_seconds` reduces a ``jax.profiler.ProfileData`` to device
  seconds per HLO instruction name inside the ``bench.window`` span,
  averaged over the device planes, every operation kept;
- :func:`scope_map` reads instruction name -> path from a compiled
  program's text (``Compiled.as_text()``);
- :func:`scope_seconds` sums the operations under one scope;
- :func:`span_seconds` sums a ``repro.obs.trace`` tracer's host spans by
  name.

The harness does not call these yet: a ``--trace 1`` run would have to
enable the tracer before set-up and keep the compiled text and the trace's
per-operation times (``PERF.md``, Open questions).
"""
from __future__ import annotations

import re
from collections import defaultdict
from typing import Dict, List

from . import devtrace

__all__ = ["op_seconds", "scope_map", "scope_seconds", "span_seconds"]

HLO_COMPUTATION = re.compile(r"^(?:ENTRY\s+)?%([\w.-]+) .*\{\s*$")
HLO_INSTRUCTION = re.compile(r"^\s+(?:ROOT\s+)?%([\w.-]+) = (.*)$")
HLO_REF = re.compile(r"%([\w.-]+)")
OP_NAME_META = re.compile(r'metadata=\{[^}]*?op_name="([^"]*)"')


def op_seconds(profile) -> Dict[str, float]:
    """Device seconds per HLO instruction name of the leaf operations inside
    the trace's one ``bench.window`` span, averaged over the device
    planes (``devtrace`` reads the same events for busy time)."""
    windows = [(int(ev.start_ns), int(ev.start_ns) + int(ev.duration_ns))
               for plane in profile.planes
               if plane.name.startswith("/host:")
               for line in plane.lines for ev in line.events
               if ev.name == devtrace.WINDOW_SPAN]
    if len(windows) != 1:
        raise ValueError(f"expected one {devtrace.WINDOW_SPAN} span, found "
                         f"{len(windows)}")
    lo, hi = windows[0]
    lines = [line for plane in profile.planes
             if devtrace.DEVICE_PLANE.match(plane.name)
             for line in plane.lines if line.name == devtrace.OPS_LINE]
    if not lines:
        raise ValueError("the trace holds no TPU device plane with an "
                         f"'{devtrace.OPS_LINE}' line")
    per_name: Dict[str, int] = defaultdict(int)
    for line in lines:
        for ev in line.events:
            s = int(ev.start_ns)
            e = s + int(ev.duration_ns)
            name, opcode = devtrace.op_name(ev.name)
            if e > lo and s < hi and opcode not in devtrace.CONTROL_FLOW:
                per_name[name] += min(e, hi) - max(s, lo)
    return {k: v * 1e-9 / len(lines) for k, v in per_name.items()}


def scope_map(hlo_text: str) -> Dict[str, str]:
    """HLO instruction name -> its ``op_name`` metadata (the named-scope
    path it was traced under, ending in the primitive).

    The compiler makes some instructions with no metadata: a loop that
    relays a large operand out (a ``while`` over ``dynamic-update-slice``
    steps), the tuple and buffers that feed it, async copies.  Such an
    instruction takes the path of the first instruction that reads its
    result and has one, through any chain of unnamed ones; failing that,
    an instruction inside a computation takes the path of the instruction
    that calls the computation.  So the loop XLA made to relay out the
    gather's result counts under the gather's scope."""
    comps: Dict[str, List[str]] = defaultdict(list)  # members, in order
    refs: Dict[str, List[str]] = {}
    path: Dict[str, str] = {}
    comp = ""
    for line in hlo_text.splitlines():
        head = HLO_COMPUTATION.match(line)
        if head:
            comp = head.group(1)
            continue
        m = HLO_INSTRUCTION.match(line)
        if not m:
            continue
        name, rest = m.groups()
        comps[comp].append(name)
        meta = OP_NAME_META.search(rest)
        if meta:
            path[name] = meta.group(1)
        refs[name] = HLO_REF.findall(rest.split(", metadata=")[0])
    users: Dict[str, List[str]] = defaultdict(list)
    callers: Dict[str, List[str]] = defaultdict(list)
    for name, rs in refs.items():
        for r in rs:
            (callers if r in comps else users)[r].append(name)

    def from_users() -> bool:
        grew = False
        for name in refs:
            if name not in path:
                hit = next((path[u] for u in users[name] if u in path), None)
                if hit is not None:
                    path[name] = hit
                    grew = True
        return grew

    while True:
        while from_users():
            pass
        grew = False
        for c, members in comps.items():
            hit = next((path[x] for x in callers[c] if x in path), None)
            for name in members if hit is not None else ():
                if name not in path:
                    path[name] = hit
                    grew = True
        if not grew:
            return path


def scope_seconds(op_s: Dict[str, float], scopes: Dict[str, str],
                  scope: str) -> float:
    """Device seconds of the operations traced under the named scope
    ``scope``: one whose path has ``scope`` as a component."""
    return sum(sec for name, sec in op_s.items()
               if scope in scopes.get(name, "").split("/"))


def span_seconds(events) -> Dict[str, float]:
    """Host seconds of a tracer's finished spans (Chrome ``X`` events,
    microseconds), summed by name."""
    out: Dict[str, float] = defaultdict(float)
    for ev in events:
        if ev["ph"] == "X":
            out[ev["name"]] += ev["dur"] * 1e-6
    return dict(out)
