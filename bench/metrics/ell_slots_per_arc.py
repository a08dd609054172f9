"""Padded ELL slots the layout gathers per pass, per arc of the graph: the
program's gauges ``layout.ell_slots`` and ``layout.arcs``, which
``apps.to_arrays(backend="ell")`` sets in the process's metric registry
(``repro.obs.metrics.get_registry()``) during set-up.  ``None`` where the
program set no such gauge (another backend, or a program without them)."""
from repro.obs.metrics import get_registry


def read(run):
    gauges = get_registry().snapshot()
    slots, arcs = gauges.get("layout.ell_slots"), gauges.get("layout.arcs")
    return slots / arcs if slots and arcs else None
