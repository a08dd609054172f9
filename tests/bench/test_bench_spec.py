"""``BENCHMARK.json`` is well formed, and every name in it resolves to the
data file or metric reader that the harness looks it up by."""
import json
import re
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from bench import harness  # noqa: E402

SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
CELLS = [w["name"] for w in SPEC["workloads"]]


def test_top_level_keys_and_paths():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert SPEC["command"] == ["python3", "bench/run.py"]
    for p in SPEC["paths"]:
        assert (ROOT / p).is_dir() and not p.startswith("/") and ".." not in p
    assert 1 <= SPEC["run_seconds"] <= 51
    # a full check with 24 cells fits its time
    runs = 2 + 14 * 24
    assert runs * (SPEC["run_seconds"] + 60) + 24 * 180 + 1200 <= 43200


def test_names_units_and_keys():
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        names = [e["name"] for e in SPEC[group]]
        assert len(set(names)) == len(names)
        assert all(NAME.match(n) for n in names)
    for m in SPEC["end_to_end"] + SPEC["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
    for m in SPEC["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert m["source"] in ("host_clock", "device_trace")
        cap = 0.25
        assert 0.01 <= m["bound"] <= cap
    for m in SPEC["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
    for c in SPEC["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
    for w in SPEC["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["chips"] in (1, 4) and len(w["why"]) <= 200


@pytest.mark.parametrize("cell", CELLS)
def test_cell_resolves_and_reports_enough(cell):
    c = harness.load_cell(cell)
    e2e = {m["name"] for m in c.end_to_end}
    assert "setup_s" in e2e and len(e2e) >= 2
    assert c.traffic["metric"] in e2e
    assert c.per_layer
    for m in c.per_layer:
        assert m["moves"] in e2e
        assert callable(harness.load_reader(m["name"]))
    assert c.config["scale"] and c.config["ordering"] == "dbg"


def test_configs_state_their_cuts():
    for c in SPEC["configs"]:
        cfg = json.loads((ROOT / c["file"]).read_text())
        assert cfg["name"] == c["name"]
        assert sorted(cfg["reduced"]) == sorted(c["reduced"])
        for key in c["reduced"]:
            assert cfg["published"][key] != cfg[key]
        assert cfg["assumed"]


def test_every_reader_file_is_named_in_the_spec():
    """Each reader file serves a metric of the spec, by its name or as the
    reader of its family (the name up to its first dot)."""
    files = {p.name[:-3] for p in (ROOT / "bench" / "metrics").glob("*.py")}
    names = {m["name"] for m in SPEC["per_layer"]}
    assert files == {n if n in files else n.split(".")[0] for n in names}
