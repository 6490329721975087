"""Quickstart: the paper in ninety seconds.

Builds the uniform-segmentation VP (2 segments × {RISC-V CPU, 2 CIM-Units},
shared DRAM), runs a GoogleNet conv layer's VMM both on the RISC-V core and
offloaded to the memristor crossbars, and compares conventional sequential
SystemC-style execution against the time-decoupled parallel backend.

  PYTHONPATH=src python examples/quickstart.py
"""
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))  # for benchmarks.*

import numpy as np

from benchmarks.common import build_workload, timed_run, verify
from repro.compile_cache import enable_compile_cache
from repro.vp import workloads as wl

enable_compile_cache()

layer = wl.TABLE_III[2].scaled(3)  # ImageNet-conv1, reduced for CPU (÷3 keeps compute ≫ sync overhead)
print(f"workload: {layer.name}  O[{layer.h},{layer.p}] = A[{layer.h},{layer.w}] @ B[{layer.w},{layer.p}]\n")

print("1) RISC-V + shared DRAM (the von Neumann path)")
cfg, states, pending, job = build_workload(layer, "uniform", "riscv", 10_000)
host, cycles, ctl = timed_run(cfg, states, pending, "vmap", 10_000)
print(f"   simulated cycles: {cycles:,}   result correct: {verify(ctl, job, layer)}")
riscv_cycles = cycles

print("2) offloaded to CIM-Units (computing-in-memory)")
cfg, states, pending, job = build_workload(layer, "uniform", "cim", 10_000)
host_sq, cycles, ctl = timed_run(cfg, states, pending, "sequential", 10_000)
print(f"   simulated cycles: {cycles:,}   ({riscv_cycles / cycles:.1f}x fewer than RISC-V)")
print(f"   result correct: {verify(ctl, job, layer)}")

print("3) parallel simulation speedup (the paper's contribution)")
host_pll, _, ctl = timed_run(cfg, states, pending, "vmap", 10_000)
print(f"   sequential host time: {host_sq*1e3:7.1f} ms  (one segment after another)")
print(f"   parallel   host time: {host_pll*1e3:7.1f} ms  (segments stepped together)")
print(f"   => simulation speedup: {host_sq / host_pll:.2f}x  (paper: up to 2.3x uniform)")
print("\ntransaction histogram (Fig. 1a tracing):", np.asarray(ctl.stats()["txn_histogram"]))
