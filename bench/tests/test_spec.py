"""BENCHMARK.json resolves: every cell to its configuration and traffic
files, every per-layer metric to its reader, by name."""
from __future__ import annotations

import json
import re

import pytest

from bench import run

SPEC = json.loads((run.ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


@pytest.mark.parametrize("cell", [w["name"] for w in SPEC["workloads"]])
def test_every_cell_resolves(cell):
    c, config, traffic, metrics = run.load_cell(cell)
    assert config["kind"] in ("dense_platform", "snn_network")
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


def test_the_serving_cell_resolves_beside_the_spec():
    from bench.tests.helpers import spec_with_serve

    c, config, traffic, metrics = run.load_cell("snn-fc1200-serve-4chip",
                                                spec_with_serve())
    assert traffic["kind"] == "serve" and c["chips"] == 4
    assert traffic["bucket"] % c["chips"] == 0
    for m in metrics["per_layer"]:
        assert callable(run.reader(m["name"]))


def test_an_unknown_cell_is_refused():
    with pytest.raises(SystemExit, match="no workload"):
        run.load_cell("no-such-cell")
