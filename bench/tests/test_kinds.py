"""A configuration kind is a file: one found outside ``bench/kinds/`` runs
a job through the harness's driver and its check, so a later kind needs
an added file and no edit."""
from __future__ import annotations

from pathlib import Path

from bench import cells, kinds
from bench.tests.helpers import tiny_cell


def test_a_kind_file_in_another_directory_runs_a_job(tmp_path):
    before = sorted(p.name for p in kinds.KINDS.iterdir())
    (tmp_path / "copied_network.py").write_text(
        (kinds.KINDS / "snn_network.py").read_text())
    kind = kinds.find("copied_network", tmp_path)
    assert Path(kind.__file__).parent == tmp_path.resolve()
    assert kinds.find("copied_network", tmp_path) is kind
    _, config, traffic, _ = tiny_cell("snn-fc1200-infer")
    config = {**config, "kind": "copied_network"}
    drv = cells.SimCell(config, {**traffic, "pool": 1}, 2**33 + 1, kind=kind)
    done = [drv.run_one(0)]
    (correct, checks), failed = drv.verify(done)
    assert correct and failed == 0, checks
    assert list(checks) == list(kind.LIMITS)
    assert sorted(p.name for p in kinds.KINDS.iterdir()) == before
