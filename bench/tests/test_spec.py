"""BENCHMARK.json resolves: every cell to its configuration and traffic
files, every per-layer metric to its reader, by name."""
from __future__ import annotations

import json
import re

import pytest

from bench import kinds, run

SPEC = json.loads((run.ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


INTERFACE = ("job", "build", "fetch", "compare", "LIMITS", "control")
SERVING = ("server", "request")


@pytest.mark.parametrize("cell", [w["name"] for w in SPEC["workloads"]])
def test_every_cell_resolves(cell):
    c, config, traffic, metrics = run.load_cell(cell)
    kind = kinds.find(config["kind"])
    for name in INTERFACE:
        assert hasattr(kind, name), (config["kind"], name)
    assert all(limit == 0 for limit in kind.LIMITS.values())
    assert traffic["kind"] in ("dense", "snn", "serve")
    names = [m["name"] for m in metrics["end_to_end"]]
    assert "setup_s" in names and len(names) >= 2
    assert metrics["per_layer"]
    for m in metrics["per_layer"]:
        assert callable(run.reader(m["name"]))
        assert m["moves"] in names


def test_names_units_and_files():
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        for entry in SPEC[group]:
            assert NAME.match(entry["name"]), entry["name"]
    for m in SPEC["end_to_end"] + SPEC["per_layer"]:
        assert re.match(r"^[A-Za-z0-9_/%.-]{1,16}$", m["unit"]), m
    for c in SPEC["configs"]:
        assert (run.ROOT / c["file"]).is_file()
        assert sorted(c["reduced"]) == sorted(json.loads(
            (run.ROOT / c["file"]).read_text())["reduced"])
    assert all(m["bound"] <= 0.25 for m in SPEC["end_to_end"])


@pytest.mark.parametrize("cell", [
    w["name"] for w in SPEC["workloads"]
    if run.load_cell(w["name"])[2]["kind"] == "serve"])
def test_serve_cells_are_entered_on_a_serving_kind(cell):
    c, config, traffic, metrics = run.load_cell(cell)
    kind = kinds.find(config["kind"])
    for name in SERVING:
        assert hasattr(kind, name), (config["kind"], name)
    assert traffic["bucket"] % c["chips"] == 0
    rates = [m for m in metrics["end_to_end"] if m["unit"] == "cycles/s"]
    assert len(rates) == 1


def test_an_unknown_kind_is_refused():
    with pytest.raises(SystemExit, match="bench/kinds/"):
        kinds.find("no_such_kind")


def test_serve_traffic_on_a_kind_without_a_serving_builder_is_refused():
    from bench import cells

    _, config, traffic, _ = run.load_cell("snn-fc1200-serve-4chip")
    dense = kinds.find("dense_platform")
    with pytest.raises(SystemExit, match="dense_platform.py"):
        cells.ServeCell(config, traffic, 1, [None], kind=dense)


def test_an_unknown_cell_is_refused():
    with pytest.raises(SystemExit, match="no workload"):
        run.load_cell("no-such-cell")
