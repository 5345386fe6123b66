"""BENCHMARK.json keeps to its contract, and every cell finds its files by
name."""
from __future__ import annotations

import json
import re
import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH / "tests"))

import run  # noqa: E402
import tiny  # noqa: E402

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
BENCHMARK = json.loads((ROOT / "BENCHMARK.json").read_text())
CELLS = [w["name"] for w in BENCHMARK["workloads"]]


def test_top_level_keys_and_sizes():
    assert set(BENCHMARK) == {"command", "paths", "run_seconds", "configs",
                              "workloads", "end_to_end", "per_layer"}
    assert len(json.dumps(BENCHMARK)) <= 64 * 1024
    assert BENCHMARK["paths"] == ["bench"]
    assert BENCHMARK["command"] == ["python3", "bench/run.py"]
    rs = BENCHMARK["run_seconds"]
    assert isinstance(rs, int) and 1 <= rs <= 51
    # a full check with 24 cells fits the driver's time budget
    assert (2 + 14 * 24) * (rs + 60) + 24 * 180 + 1200 <= 43200


def test_names_units_and_keys():
    names = set()
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        seen = [e["name"] for e in BENCHMARK[group]]
        assert len(seen) == len(set(seen)), group
        names |= set(seen)
        for e in BENCHMARK[group]:
            assert NAME.match(e["name"]), e["name"]
    for c in BENCHMARK["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert len(c["reduced"]) <= 16
    for w in BENCHMARK["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["chips"] in (1, 4)
        assert 1 <= len(w["why"]) <= 200 and "\n" not in w["why"]
    for m in BENCHMARK["end_to_end"] + BENCHMARK["per_layer"]:
        assert UNIT.match(m["unit"]), m["unit"]
        assert m["better"] in ("lower", "higher")
        assert m["source"] in SOURCES
    for m in BENCHMARK["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert set(m) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
        assert 0.01 <= m["bound"] <= 0.25
    for m in BENCHMARK["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}


def test_four_chip_cells_at_most_half():
    four = [w for w in BENCHMARK["workloads"] if w["chips"] == 4]
    assert len(four) <= max(1, len(CELLS) // 2)


@pytest.mark.parametrize("cell", CELLS + ["mnist-serve-poisson"])
def test_cell_resolves_by_name(cell):
    """Every cell, and the serving cell that waits in a test fixture, finds
    its config, mix, driver, limits and readers by name."""
    c = tiny.resolve(cell)
    assert (BENCH / "drivers" / f"{c.traffic['driver']}.py").is_file()
    names = [m["name"] for m in c.end_to_end]
    assert "setup_s" in names and len(names) >= 2
    assert c.per_layer, "every cell reports a per-layer metric"
    for m in c.per_layer:
        assert m["moves"] in names, (m["name"], m["moves"])
        assert (BENCH / "metrics" / f"{m['name']}.py").is_file()
        reader = run.load_module(BENCH / "metrics" / f"{m['name']}.py")
        assert callable(reader.read)
    assert c.limits, "a cell's comparison has limits"


def test_every_config_is_used_and_its_file_exists():
    used = {w["config"] for w in BENCHMARK["workloads"]}
    for c in BENCHMARK["configs"]:
        assert c["name"] in used
        assert (ROOT / c["file"]).is_file()
        cfg = json.loads((ROOT / c["file"]).read_text())
        assert cfg["reduced"] == c["reduced"]
