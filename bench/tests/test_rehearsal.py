"""Every cell end to end on the CPU at a tiny size, through the harness
past its look for a chip; the command itself refuses the CPU."""
from __future__ import annotations

import json
import os
import subprocess
import sys

import pytest

from bench.run import ROOT
from bench.tests.helpers import rehearse


@pytest.mark.parametrize("cell", ["dense-cim-imagenet-conv1",
                                  "dense-riscv-googlenet-conv2",
                                  "snn-fc1200-infer"])
def test_one_chip_cells_run_and_check(cell):
    res = rehearse(cell)
    assert res["correct"], res["checks"]
    assert res["attempted"] >= 1 and res["failed"] == 0
    assert set(res["metrics"]) == {"sim_cycles_per_s", "setup_s"}
    assert list(res)[-1] == "checks"


def test_serve_cell_runs_on_four_devices():
    """The cell as ``BENCHMARK.json`` enters it, cut to its tiny size.  In a
    subprocess: the virtual devices must exist before jax starts."""
    code = ("import json, sys; sys.path[:0] = ['.', 'src'];"
            "from bench.tests.helpers import rehearse;"
            "print(json.dumps(rehearse('snn-fc1200-serve-4chip', "
            "seconds=1.0)))")
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "XLA_FLAGS": "--xla_force_host_platform_device_count=4"}
    out = subprocess.run([sys.executable, "-c", code], cwd=ROOT, env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    res = json.loads(out.stdout.strip().splitlines()[-1])
    assert res["correct"], res["checks"]
    assert res["device"]["count"] == 4
    assert res["attempted"] >= 8 and res["failed"] == 0
    assert set(res["metrics"]) == {"sim_cycles_per_s.serve", "setup_s"}
    assert res["metrics"]["sim_cycles_per_s.serve"]["value"] > 0


@pytest.mark.parametrize("cell", ["snn-fc1200-infer",
                                  "dense-riscv-googlenet-conv2"])
def test_traced_run_on_the_cpu_reads_no_device_metric(cell):
    """The CPU has no device plane; a dense cell traces a slice only."""
    res = rehearse(cell, trace=True, seconds=1.0)
    assert res["correct"]
    assert res["metrics"] == {}
    assert "busy_s" in res["device"] and "breakdown" in res


def test_the_command_refuses_a_cpu():
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    out = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "snn-fc1200-infer",
         "--seed", "1", "--seconds", "1"], cwd=ROOT, env=env,
        capture_output=True, text=True, timeout=300)
    assert out.returncode != 0
    assert out.stdout == ""
    assert "needs a TPU" in out.stderr
