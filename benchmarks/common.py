"""Shared benchmark machinery: workload construction per segmentation
strategy + timed sq/pll comparison (the paper's measurement, §V)."""
from __future__ import annotations

import os
import time

import numpy as np

from repro.core import segmentation as sg
from repro.core.controller import Controller
from repro.vp import workloads as wl

FULL = os.environ.get("REPRO_FULL_BENCH", "0") == "1"
SCALE = 1 if FULL else 8  # Table III dims divided by SCALE unless FULL


def build_workload(layer: wl.Layer, strategy: str, mode: str, channel_latency: int,
                   use_kernel: bool = False):
    """Returns (cfg, states, pending, job); ``use_kernel`` routes the
    crossbar math through the Pallas kernel."""
    if strategy == "uniform":
        descs = sg.uniform(2, 2)
        mgrs, ids = [0, 1], {0: (0, 1), 1: (2, 3)}
    elif strategy == "load_oriented":
        descs = sg.load_oriented()
        mgrs, ids = [1], {1: (0, 2)}
    else:
        raise ValueError(strategy)
    if mode == "cim":
        job = wl.cim_workload(layer, mgr_segments=mgrs, cim_ids_per_mgr=ids,
                              ordinals=sg.mailbox_ordinals(descs))
        kw = dict(programs=job["programs"], dram_words=job["dram"],
                  crossbars=job["crossbars"], scratch_init=job["scratch"])
    elif mode == "riscv":
        job = wl.riscv_workload(layer)
        kw = dict(programs=job["programs"], dram_words=job["dram"])
    elif mode == "mixed":
        # paper-style combined load: CPU0 computes a slice on RISC-V + DRAM
        # while CPU1 offloads the rest to CIM units (load-oriented: CPU1
        # drives the CIM segments; uniform: both CPUs loaded).
        cim_job = wl.cim_workload(layer, mgr_segments=mgrs[-1:], cim_ids_per_mgr=ids,
                                  ordinals=sg.mailbox_ordinals(descs))
        r_layer = wl.Layer(layer.network, layer.layer, layer.h, layer.w, max(layer.p // 2, 1))
        r_job = wl.riscv_workload(r_layer)
        job = dict(cim_job)
        job["programs"] = {**cim_job["programs"], 0: r_job["programs"][0]}
        kw = dict(programs=job["programs"], dram_words=job["dram"],
                  crossbars=job["crossbars"], scratch_init=job["scratch"])
    else:
        raise ValueError(mode)
    cfg, states, pending = sg.build(descs, channel_latency=channel_latency,
                                    use_kernel=use_kernel, **kw)
    return cfg, states, pending, job


def timed_run(cfg, states, pending, backend: str, quantum: int, max_rounds=2000,
              fused=None):
    """Warm-compile, then run to completion; returns (host_s, sim_cycles, ctl).

    ``fused`` is forwarded to ``Controller.run`` (None = backend default:
    the device-resident megaloop on vmap/shard_map, the per-round host loop
    on sequential/threads).  Rounds/sec is ``ctl.rounds_run / host_s``.
    """
    warm = Controller(cfg, states, pending, backend=backend, quantum=quantum)
    warm.run(max_rounds=2, check_every=2, fused=fused)  # compile round + megastep
    warm.block_until_ready()
    ctl = Controller(cfg, states, pending, backend=backend, quantum=quantum)
    t0 = time.perf_counter()
    rounds, _ = ctl.run(max_rounds=max_rounds, check_every=2, fused=fused)
    host = time.perf_counter() - t0
    return host, int(np.max(ctl.sim_time())), ctl


def verify(ctl, job, layer) -> bool:
    st = ctl.result_states()
    o = np.asarray(
        st["dram"]["data"][0][job["o_word"] : job["o_word"] + layer.h * layer.p]
    ).reshape(layer.h, layer.p)
    return bool(np.array_equal(o, job["expected"]))
