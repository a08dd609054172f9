"""Building blocks of the per-layer metric readers in ``metrics/``.

Each returns a ``read(run)``, or is one, over a ``harness.Run`` of a
``--trace 1`` run; it returns ``None`` where the run holds nothing for it
to read (another app, no Pallas kernel on the path), and the harness then
leaves the metric out.  The trace readers serve whatever app the run
solved: ``BENCHMARK.json`` names each metric's cells.
"""
from __future__ import annotations

from . import roofline


def phase(name: str):
    """Host seconds of one set-up phase, as the harness timed it."""
    def read(run):
        return run.phases.get(name)
    return read


def iterations(app: str):
    """The solver's own iteration count of the traced solve."""
    def read(run):
        if run.app != app or not run.iterations:
            return None
        return float(run.iterations[0])
    return read


def hbm_roofline(run):
    """Compulsory bytes of the traced solve over its device-busy time over
    the chip's peak HBM bandwidth, in per cent."""
    if not run.traced_bytes:
        return None
    return roofline.hbm_share(run.traced_bytes, run.trace.busy_s,
                              run.device_kind)


def pallas_share(run):
    """Per cent of the traced solve's device-busy time in Pallas kernels."""
    if not run.trace.pallas_s:
        return None
    return 100.0 * run.trace.pallas_share


def device_idle(run):
    """Per cent of the traced window in which no operation ran on the
    device."""
    return 100.0 * run.trace.idle_share
