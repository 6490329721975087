"""Design-space exploration of VP segmentation strategies — the workflow the
paper's VP exists to enable (§IV-C), including the automatic segmentation it
lists as future work.

For one workload, compares uniform / load-oriented / auto partitions on
simulated cycles AND host simulation time, sequential vs parallel.

  PYTHONPATH=src python examples/vp_segmentation_explore.py
"""
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))  # for benchmarks.*

import numpy as np

from benchmarks.common import build_workload, timed_run, verify
from repro.compile_cache import enable_compile_cache
from repro.core import segmentation as sg
from repro.core.controller import Controller
from repro.vp import workloads as wl

enable_compile_cache()
layer = wl.TABLE_III[2].scaled(8)  # ImageNet-conv1
print(f"workload: {layer.name} ({layer.h}x{layer.w}x{layer.p}), mode: cim offload\n")
print(f"{'strategy':16s}{'segments':>9s}{'sq ms':>10s}{'pll ms':>10s}{'speedup':>9s}{'cycles':>12s}{'ok':>4s}")

for strategy in ("uniform", "load_oriented"):
    cfg, states, pending, job = build_workload(layer, strategy, "cim", 10_000)
    t_sq, cyc, ctl = timed_run(cfg, states, pending, "sequential", 10_000)
    t_pll, _, ctl_p = timed_run(cfg, states, pending, "vmap", 10_000)
    ok = verify(ctl_p, job, layer)
    print(f"{strategy:16s}{cfg.n_segments:9d}{t_sq*1e3:10.1f}{t_pll*1e3:10.1f}"
          f"{t_sq/t_pll:8.2f}x{cyc:12,}{'Y' if ok else 'N':>4s}")

# automatic segmentation (paper future work): balance measured module costs
costs = {"cpu0": 3.0, "cpu1": 8.0, "dram": 2.0, "cim0": 4.0, "cim1": 4.0, "cim2": 4.0, "cim3": 4.0}
descs = sg.auto_segmentation(costs, n_segments=4)
print(f"\nauto_segmentation({costs}) ->")
for i, d in enumerate(descs):
    print(f"  segment {i}: cpu={d.cpu} dram={d.dram} cims={d.n_cims} mgr={d.cim_mgr}")
