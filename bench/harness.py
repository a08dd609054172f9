"""The benchmark's harness, steered by ``BENCHMARK.json`` and data files.

A cell (``workloads`` entry) names a configuration, whose file gives the
graph, its ordering and the program's backend, and a traffic mix
``bench/traffic/<traffic>.json``, which names the solve (``apps.APPS``), its
parameters and the end-to-end metric its solves report.  Per-layer metrics
are read by ``bench/metrics/<name>.py``, or where there is no such file by
the reader of the metric's family, ``bench/metrics/<name up to its first
dot>.py``; each is a ``read(run)`` that returns a number or ``None`` when
it finds nothing to read.

One run: set-up (the configuration's graph generated on the device and
relabelled by the seed, the program's CSR build, DBG reorder and layout,
and ahead-of-time compilation of the cell's solve, from the persistent
cache after a checkout's first run), then whole solves back to
back until the first solve boundary after ``--seconds``; with ``--trace 1``
one profiled solve instead.  Then device memory is read, the program's
arrays are freed, and every answer of the run is judged against the numpy
reference in the generated graph's own vertex ids.

Standard error carries the device, phase times, per-solve times and, last,
each compared number beside its limit.  The last line of standard output is
the result, printed only when the run reached its end.
"""
from __future__ import annotations

import argparse
import contextlib
import dataclasses
import importlib.util
import json
import shutil
import statistics
import sys
import tempfile
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
BENCH = ROOT / "bench"
#: solves of a run whose answers are judged: all of them, or a seeded
#: sample of this many where the window holds more
JUDGED = 8


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


# ---------------------------------------------------------------------------
# the cell, from BENCHMARK.json and the files it names
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: list
    per_layer: list


def _applies(metric: dict, cell: str, e2e_names=None) -> bool:
    """An end-to-end metric applies where it lists the cell or lists none;
    a per-layer metric where it lists the cell or, listing none, wherever
    the metric it moves is reported."""
    if "workloads" in metric:
        return cell in metric["workloads"]
    return e2e_names is None or metric["moves"] in e2e_names


def load_cell(name: str, spec: dict | None = None) -> Cell:
    """The cell ``name`` of ``spec``, by default ``BENCHMARK.json``."""
    if spec is None:
        spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise SystemExit(f"unknown workload {name!r}; known: "
                         f"{', '.join(sorted(cells))}")
    w = cells[name]
    cfg_entry = {c["name"]: c for c in spec["configs"]}[w["config"]]
    config = json.loads((ROOT / cfg_entry["file"]).read_text())
    traffic = json.loads((BENCH / "traffic" / f"{w['traffic']}.json")
                         .read_text())
    e2e = [m for m in spec["end_to_end"] if _applies(m, name)]
    names = {m["name"] for m in e2e}
    per_layer = [m for m in spec["per_layer"] if _applies(m, name, names)]
    return Cell(name, int(w["chips"]), config, traffic, e2e, per_layer)


def load_reader(metric: str):
    path = BENCH / "metrics" / f"{metric}.py"
    if not path.exists():
        path = BENCH / "metrics" / f"{metric.split('.')[0]}.py"
    spec = importlib.util.spec_from_file_location(
        f"bench_metric_{metric.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


@dataclasses.dataclass
class Run:
    """What a ``--trace 1`` run hands the per-layer metric readers."""

    app: str
    device_kind: str
    phases: dict  # set-up phase -> seconds, on the host clock
    iterations: list  # the solver's own count, of the traced solve's answers
    trace: object  # devtrace.TraceSummary
    traced_bytes: int  # compulsory bytes of the traced solve


def require_devices(chips: int):
    """The cell's chips, or exit: a run on the CPU or on too few chips is
    not a run of this benchmark."""
    import jax

    devs = jax.devices()
    log(f"[device] platform={devs[0].platform} kind={devs[0].device_kind} "
        f"count={len(devs)}")
    if devs[0].platform != "tpu":
        raise SystemExit(f"bench: JAX finds no TPU (platform "
                         f"{devs[0].platform!r}); no result")
    if len(devs) < chips:
        raise SystemExit(f"bench: the cell needs {chips} chips, JAX finds "
                         f"{len(devs)}; no result")
    return devs[:chips]


class Phases:
    """Host seconds of each set-up phase, logged as they end."""

    def __init__(self):
        self.seconds: dict = {}

    @contextlib.contextmanager
    def __call__(self, name: str):
        t = time.perf_counter()
        yield
        self.seconds[name] = time.perf_counter() - t
        log(f"[setup] {name}: {self.seconds[name]:.4f} s")


def set_up(cell: Cell, seed: int, phase: Phases):
    """Generates the cell's graph and hands it to the program: CSR build,
    reorder, layout and the solve compiled ahead of time.  Returns the app
    and the generated edge list (src, dst) for the reference."""
    import jax
    import numpy as np

    from repro.apps import to_arrays
    from repro.core import reorder
    from repro.graph import csr

    from . import apps, graphgen

    cfg = cell.config
    v = 1 << cfg["scale"]
    with phase("generate"):
        src, dst, perm = graphgen.generate(cfg, seed)
    log(f"[setup] graph: V={v} arcs={src.shape[0]}")
    with phase("csr_build"):
        g0 = csr.from_edges(src, dst, v, name=cell.name)
    with phase("reorder"):
        g, res = reorder.reorder_graph(g0, cfg["ordering"])
    del g0
    phase.seconds["reorder_program"] = float(res.seconds)
    with phase("layout"):
        ga = jax.block_until_ready(to_arrays(g, backend=cfg["backend"]))
    del g
    app = apps.APPS[cell.traffic["app"]](
        cell.traffic, cfg, ga, np.asarray(res.mapping), perm,
        np.bincount(src, minlength=v))
    with phase("compile"):
        app.compile()
    return app, src, dst


def window(app, seconds: float, seed: int):
    """Whole solves back to back until the first solve boundary after
    ``seconds``.  Returns (window seconds, seconds of each solve, judged
    answers): a seeded uniform sample of at most ``JUDGED`` (solve index,
    answer) pairs, kept by reservoir sampling so the check stays short."""
    import jax
    import numpy as np

    rng = np.random.default_rng(seed)
    outs, solve_s = [], []
    t0 = time.perf_counter()
    while True:
        t = time.perf_counter()
        out = jax.block_until_ready(app.solve(len(solve_s)))
        solve_s.append(time.perf_counter() - t)
        i = len(solve_s) - 1
        j = i if i < JUDGED else int(rng.integers(0, i + 1))
        if j < len(outs):
            outs[j] = (i, out)
        elif j < JUDGED:
            outs.append((i, out))
        del out
        if time.perf_counter() - t0 >= seconds:
            return time.perf_counter() - t0, solve_s, sorted(outs)


def traced(app):
    """One solve under the profiler, annotated for the trace reduction.
    Returns (seconds of the solve, its answer, the trace's summary)."""
    import jax

    from . import devtrace

    tmp = Path(tempfile.mkdtemp(prefix="bench-trace-"))
    try:
        jax.profiler.start_trace(str(tmp))
        with jax.profiler.TraceAnnotation(devtrace.WINDOW_SPAN):
            t = time.perf_counter()
            with jax.profiler.TraceAnnotation("bench.dispatch"):
                out = app.solve(0)
            with jax.profiler.TraceAnnotation("bench.wait"):
                out = jax.block_until_ready(out)
            seconds = time.perf_counter() - t
        jax.profiler.stop_trace()
        summary = devtrace.summarize_file(next(tmp.rglob("*.xplane.pb")))
        return seconds, out, summary
    finally:
        shutil.rmtree(tmp, ignore_errors=True)


def run(args, t_start: float, cell: Cell, devices) -> dict:
    """One run of ``cell`` on ``devices``; returns the result line."""
    import jax
    import numpy as np

    from . import reference

    phase = Phases()
    app, src, dst = set_up(cell, args.seed, phase)
    setup_s = time.perf_counter() - t_start
    log(f"[setup] total (process start to first solve): {setup_s:.4f} s")
    summary = None
    if args.trace:
        s, out, summary = traced(app)
        solve_s, outs = [s], [(0, out)]
    else:
        window_s, solve_s, outs = window(app, args.seconds, args.seed)
    for i, s in list(enumerate(solve_s))[:JUDGED]:
        log(f"[solve] {i}: {s:.4f} s")
    log(f"[solve] {len(solve_s)} solves; median "
        f"{statistics.median(solve_s):.4f} s, max {max(solve_s):.4f} s")
    peak = max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devices)
    iterations = [app.iterations(o) for _, o in outs]
    log(f"[solve] iterations of the judged solves: {iterations}")
    outs = [(i, jax.tree_util.tree_map(np.asarray, o)) for i, o in outs]
    del app.ga, app.exe  # the program's arrays leave the device
    ref = reference.EdgeList(src, dst, 1 << cell.config["scale"])
    del src, dst
    t = time.perf_counter()
    checks, failed = app.judge(outs, ref)
    log(f"[check] judged {len(outs)} answers in "
        f"{time.perf_counter() - t:.4f} s")

    kind = devices[0].device_kind
    device = {"platform": devices[0].platform, "kind": kind,
              "count": len(devices), "memory_peak_bytes": peak}
    metrics = {}
    if args.trace:
        record = Run(app=cell.traffic["app"], device_kind=kind,
                     phases=phase.seconds, iterations=iterations,
                     trace=summary,
                     traced_bytes=app.compulsory_bytes(outs[0][1], ref))
        device["busy_s"] = summary.busy_s
        device["window_s"] = summary.window_s
        for m in cell.per_layer:
            value = load_reader(m["name"])(record)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        values = {"setup_s": setup_s,
                  cell.traffic["metric"]:
                      window_s / (len(solve_s) * app.answers)}
        metrics = {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
                   for m in cell.end_to_end if m["name"] in values}
    result = {"correct": failed == 0,
              "attempted": len(solve_s) * app.answers,
              "failed": failed, "metrics": metrics, "device": device}
    if summary is not None:
        result["breakdown"] = {
            "device_ops": [list(o) for o in summary.device_ops],
            "idle_gaps": [list(g) for g in summary.idle_gaps]}
    result["checks"] = {k: {"value": val, "limit": lim}
                        for k, (val, lim) in checks.items()}
    for k, (val, lim) in checks.items():
        log(f"[check] {k}: {val} (limit {lim})")
    return result


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def main(t_start: float, argv=None) -> int:
    """``t_start``: the process's start on ``time.perf_counter``."""
    args = parse(argv)
    cell = load_cell(args.workload)
    devices = require_devices(cell.chips)
    result = run(args, t_start, cell, devices)
    print(json.dumps(result), flush=True)
    return 0
