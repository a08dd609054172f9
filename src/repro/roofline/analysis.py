"""Roofline analysis from compiled dry-run artifacts (DESIGN.md §7).

Three terms per (arch × shape × mesh), all in seconds:

    compute    = HLO_FLOPs_per_device / peak_FLOPs        (197 TFLOP/s bf16)
    memory     = HLO_bytes_per_device / HBM_bw            (819 GB/s)
    collective = collective_bytes_per_device / link_bw    (~50 GB/s/link ICI)

``cost_analysis()`` of the SPMD-partitioned executable reports PER-DEVICE
flops/bytes.  Collective bytes are parsed from the optimized HLO text:
every all-gather / all-reduce / reduce-scatter / all-to-all /
collective-permute contributes its payload bytes, multiplied by the trip
count of any enclosing while loop (trip counts recovered from the loop
condition's comparison constant).
"""
from __future__ import annotations

import dataclasses
import math
import os
import re
from typing import Dict, List, Optional, Tuple

__all__ = ["HW", "HW_PROFILES", "DEVICE_KIND_PROFILES",
           "parse_collective_bytes", "roofline_terms", "model_flops"]


@dataclasses.dataclass(frozen=True)
class HW:
    """A hardware roofline profile.

    Defaults are TPU v5e, but the profile is selectable: ``HW.profile()``
    resolves the ``REPRO_HW_PROFILE`` env var, else the profile of the
    device JAX runs on (``DEVICE_KIND_PROFILES``; an unknown kind raises),
    and ``repro.tune.cost`` routes every candidate price through it — under
    ``"cpu-interpret"`` (the Pallas interpreter on host CPU) the FLOP peak
    is infinite, so rankings degrade gracefully to modeled HBM bytes
    instead of comparing against a 197-TFLOP peak no interpreter will see.

    ``dispatch_overhead`` is the fixed cost of one Pallas grid step.  On
    real hardware grid steps are pipelined and it is ~0; the interpreter
    executes each grid cell as a Python-level call, so there it DOMINATES
    small-graph wall clock (tens of µs per step — calibrated against the
    measured sweep's audit trail) and tile-geometry rankings that ignore
    it are wrong in exactly the way a pure byte model is wrong.
    """

    peak_flops: float = 197e12  # bf16 / chip (TPU v5e)
    hbm_bw: float = 819e9  # bytes/s
    link_bw: float = 50e9  # bytes/s per ICI link
    dispatch_overhead: float = 0.0  # s per kernel grid step
    name: str = "v5e"

    @classmethod
    def profile(cls, name: Optional[str] = None) -> "HW":
        """Look up a named profile; ``None`` reads ``REPRO_HW_PROFILE``,
        else picks by ``jax.devices()[0].device_kind``.  Unknown names and
        device kinds raise with the known list: CPU callers name
        ``"cpu-interpret"`` themselves."""
        if name is None:
            name = os.environ.get("REPRO_HW_PROFILE")
        if name is None:
            import jax

            kind = jax.devices()[0].device_kind
            try:
                name = DEVICE_KIND_PROFILES[kind]
            except KeyError:
                raise ValueError(
                    f"no hardware profile for device kind {kind!r}; known "
                    f"kinds: {', '.join(sorted(DEVICE_KIND_PROFILES))} "
                    "(name a profile, e.g. 'cpu-interpret')") from None
        try:
            return HW_PROFILES[name]
        except KeyError:
            raise ValueError(
                f"unknown hardware profile {name!r}; known profiles: "
                f"{', '.join(sorted(HW_PROFILES))}") from None


#: name -> profile.  ``cpu-interpret`` models the interpret-mode sweeps the
#: benchmarks run on CI hosts: ~host-DRAM bandwidth, no meaningful FLOP or
#: interconnect peak (both infinite), and a per-grid-step dispatch cost —
#: the Python-level interpreter loop — that dominates small-graph wall
#: clock (~50 µs/step, calibrated on the registry sweeps' audit trails),
#: so tile geometry ranks by bytes + dispatch instead of bytes alone.
HW_PROFILES: Dict[str, HW] = {
    "v5e": HW(),
    "cpu-interpret": HW(peak_flops=math.inf, hbm_bw=20e9, link_bw=math.inf,
                        dispatch_overhead=5e-5, name="cpu-interpret"),
}

#: ``device_kind`` as JAX reports it -> profile name (peaks: Google Cloud
#: documentation, "TPU v5e").
DEVICE_KIND_PROFILES: Dict[str, str] = {"TPU v5 lite": "v5e"}


_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "bf16": 2, "f16": 2, "f8e4m3fn": 1, "f8e5m2": 1,
    "s64": 8, "u64": 8, "s32": 4, "u32": 4, "s16": 2, "u16": 2,
    "s8": 1, "u8": 1, "pred": 1, "c64": 8, "c128": 16,
}

_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")
_COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
                "collective-permute")


def _shape_bytes(shape_str: str) -> int:
    """Total bytes of all array shapes in a (possibly tuple) HLO type string."""
    total = 0
    for dt, dims in _SHAPE_RE.findall(shape_str):
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        if dims:
            for d in dims.split(","):
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def _split_computations(hlo: str) -> Dict[str, str]:
    """computation name -> body text (coarse brace matching on HLO text)."""
    comps: Dict[str, str] = {}
    # computations start at column 0 like: `%name (args) -> type {` or
    # `ENTRY %name ...{`; bodies are indented lines until a lone `}`.
    cur_name, cur_lines = None, []
    for line in hlo.splitlines():
        m = re.match(r"(?:ENTRY\s+)?%?([\w\.\-]+)\s*\(.*\)\s*->\s*\S.*\{\s*$", line)
        if m and not line.startswith(" "):
            cur_name = m.group(1)
            cur_lines = []
            continue
        if cur_name is not None:
            if line.startswith("}"):
                comps[cur_name] = "\n".join(cur_lines)
                cur_name = None
            else:
                cur_lines.append(line)
    return comps


def _while_multipliers(hlo: str, comps: Dict[str, str]) -> Dict[str, int]:
    """computation name -> product of enclosing while trip counts."""
    # find while ops: `... = <type> while(...), condition=%c, body=%b`
    body_cond: List[Tuple[str, str, str]] = []  # (parent, body, cond)
    for parent, text in comps.items():
        for m in re.finditer(r"while\([^)]*\)[^\n]*condition=%?([\w\.\-]+)"
                             r"[^\n]*body=%?([\w\.\-]+)", text):
            body_cond.append((parent, m.group(2), m.group(1)))
        for m in re.finditer(r"while\([^)]*\)[^\n]*body=%?([\w\.\-]+)"
                             r"[^\n]*condition=%?([\w\.\-]+)", text):
            body_cond.append((parent, m.group(1), m.group(2)))

    def trip_count(cond_name: str) -> int:
        text = comps.get(cond_name, "")
        consts = [int(c) for c in re.findall(r"constant\((\d+)\)", text)]
        consts = [c for c in consts if 1 < c < 10_000_000]
        return max(consts) if consts else 1

    mult: Dict[str, int] = {name: 1 for name in comps}

    # propagate: body computations run trip_count times (× parent multiplier).
    # iterate to fixpoint over the (shallow) nesting.
    for _ in range(8):
        changed = False
        for parent, body, cond in body_cond:
            m_new = mult.get(parent, 1) * trip_count(cond)
            if mult.get(body, 1) != m_new:
                mult[body] = m_new
                changed = True
        if not changed:
            break
    # calls / fusions inherit parent multiplier
    for _ in range(8):
        changed = False
        for parent, text in comps.items():
            for m in re.finditer(r"(?:calls=|to_apply=)%?([\w\.\-]+)", text):
                callee = m.group(1)
                if callee in mult and mult[callee] < mult.get(parent, 1):
                    mult[callee] = mult[parent]
                    changed = True
        if not changed:
            break
    return mult


def parse_collective_bytes(hlo: str) -> Dict[str, float]:
    """Per-device collective payload bytes by kind, while-loop adjusted."""
    comps = _split_computations(hlo)
    if not comps:  # fallback: treat whole text as one computation
        comps = {"main": hlo}
    mult = _while_multipliers(hlo, comps)
    out: Dict[str, float] = {k: 0.0 for k in _COLLECTIVES}
    op_re = re.compile(
        r"=\s*(?P<shape>[^=]*?)\s*(?P<kind>" + "|".join(_COLLECTIVES) +
        r")(?P<suffix>-start|-done)?\("
    )
    for name, text in comps.items():
        m = mult.get(name, 1)
        for line in text.splitlines():
            om = op_re.search(line)
            if not om:
                continue
            if om.group("suffix") == "-done":
                continue  # payload counted at -start
            # RESULT type covers all-gather output growth; reduce ops are
            # payload-sized either way.
            out[om.group("kind")] += _shape_bytes(om.group("shape")) * m
    out["total"] = sum(out[k] for k in _COLLECTIVES)
    return out


_DEF_RE = re.compile(r"^\s*(?:ROOT\s+)?(%[\w\.\-]+)\s*=\s*((?:\([^)]*\))|(?:\S+))")
# ops that move no HBM bytes of their own (layout/book-keeping only)
_ZERO_COST_RE = re.compile(
    r"=\s*\S+\s+(bitcast|tuple|get-tuple-element|parameter|constant|"
    r"partition-id|replica-id|after-all|reshape)\(")
_SIG_PARAM_RE = re.compile(r"(%[\w\.\-]+):\s*(\S+?)(?:[,)]|$)")
# operand may be `%name` (older HLO text) or `f32[64,128]{1,0} %name`
# (newer XLA prints operand types inline in call sites)
_DOT_CALL_RE = re.compile(
    r"\bdot\(\s*(?:(?P<type>[a-z0-9]+\[[\d,]*\](?:\{[\d,]*\})?)\s+)?"
    r"(?P<name>%[\w\.\-]+)")
_LC_RE = re.compile(r"lhs_contracting_dims=\{([\d,]*)\}")


def parse_hlo_costs(hlo: str) -> Dict[str, float]:
    """Trip-count-aware FLOPs and HBM-traffic estimates from optimized HLO.

    XLA's ``cost_analysis()`` counts every while-loop body ONCE — for a
    layer-scanned model that under-counts by ~n_layers.  We re-derive:

      * flops: 2 * |result| * |contracted dims| for every dot, times the
        enclosing while trip count (matmuls dominate all our cells).  The lhs
        operand's shape is resolved through a per-computation symbol table
        (defining lines + computation signature parameters);
      * bytes: post-fusion HLO buffers are materialized tensors, so per-op
        result bytes approximate HBM writes; traffic ≈ 2x result bytes
        (one write + one read), trip-count adjusted.
    """
    comps_hdrs = _split_computations_with_headers(hlo)
    if not comps_hdrs:
        comps_hdrs = {"main": ("", hlo)}
    comps = {k: v[1] for k, v in comps_hdrs.items()}
    mult = _while_multipliers(hlo, comps)
    # fusion/reduce bodies live in registers — their internal results are NOT
    # HBM traffic; only the fusion op's own result (counted at the call site)
    # is materialized.
    interior = set()
    for text in comps.values():
        for line in text.splitlines():
            if "fusion(" in line or "reduce(" in line or "reduce-window(" in line:
                for mm in re.finditer(r"(?:calls=|to_apply=)%?([\w\.\-]+)", line):
                    interior.add(mm.group(1))
    flops = 0.0
    bytes_hbm = 0.0
    for name, (header, text) in comps_hdrs.items():
        m = mult.get(name, 1)
        skip_bytes = name in interior
        # symbol table: %name -> type string
        sym: Dict[str, str] = {}
        for pm in _SIG_PARAM_RE.finditer(header):
            sym[pm.group(1)] = pm.group(2)
        for line in text.splitlines():
            dm = _DEF_RE.match(line)
            if not dm:
                continue
            sym[dm.group(1)] = dm.group(2)
            if not skip_bytes and not _ZERO_COST_RE.search(line):
                bytes_hbm += _shape_bytes(dm.group(2)) * m * 2.0
            if "dot(" not in line:
                continue
            lc = _LC_RE.search(line)
            call = _DOT_CALL_RE.search(line)
            if not (lc and call):
                continue
            out_dims = _SHAPE_RE.findall(dm.group(2))
            if not out_dims:
                continue
            out_n = 1
            if out_dims[0][1]:
                for d in out_dims[0][1].split(","):
                    out_n *= int(d)
            lhs_type = call.group("type") or sym.get(call.group("name"), "")
            lhs_dims_m = _SHAPE_RE.findall(lhs_type)
            k = 1
            if lhs_dims_m and lc.group(1):
                dims = ([int(d) for d in lhs_dims_m[0][1].split(",")]
                        if lhs_dims_m[0][1] else [])
                for i in (int(i) for i in lc.group(1).split(",") if i != ""):
                    if i < len(dims):
                        k *= dims[i]
            flops += 2.0 * out_n * k * m
    return {"flops": flops, "bytes": bytes_hbm}


def _split_computations_with_headers(hlo: str) -> Dict[str, Tuple[str, str]]:
    """computation name -> (header line, body text)."""
    comps: Dict[str, Tuple[str, str]] = {}
    cur_name, cur_header, cur_lines = None, "", []
    for line in hlo.splitlines():
        m = re.match(r"(?:ENTRY\s+)?%?([\w\.\-]+)\s*\(.*\)\s*->\s*\S.*\{\s*$", line)
        if m and not line.startswith(" "):
            cur_name = m.group(1)
            cur_header = line
            cur_lines = []
            continue
        if cur_name is not None:
            if line.startswith("}"):
                comps[cur_name] = (cur_header, "\n".join(cur_lines))
                cur_name = None
            else:
                cur_lines.append(line)
    return comps


def model_flops(n_active_params: float, tokens: float, kind: str) -> float:
    """6·N·D for a train step; 2·N·D for forward-only (prefill/decode)."""
    return (6.0 if kind == "train" else 2.0) * n_active_params * tokens


def roofline_terms(
    flops_per_device: float,
    bytes_per_device: float,
    collective_bytes_per_device: float,
    hw: HW = HW(),
) -> Dict[str, float]:
    c = flops_per_device / hw.peak_flops
    m = bytes_per_device / hw.hbm_bw
    n = collective_bytes_per_device / hw.link_bw
    dominant = max(("compute", c), ("memory", m), ("collective", n),
                   key=lambda kv: kv[1])[0]
    return {
        "compute_s": c,
        "memory_s": m,
        "collective_s": n,
        "dominant": dominant,
        "bound_s": max(c, m, n),
    }
