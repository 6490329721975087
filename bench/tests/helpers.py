"""Tiny versions of the cells, for CPU runs of the whole harness path."""
from __future__ import annotations

import copy
import time

# per cell: overrides of its configuration and traffic that keep every
# shape of the real cell (multi-crossbar layers, several segments, a
# bucket over several devices) at a size a test can hold; a dense layer's
# rounds and cycles at that size were read from the program once
TINY_SNN = {"layer_sizes": [300, 270, 10], "n_segments": 2,
            "in_cap": 4096, "out_cap": 1024}
TINY = {
    "dense-cim-imagenet-conv1": (
        {"quantum": 256},
        {"layer": {"network": "t", "layer": "t", "h": 8, "w": 8, "p": 4},
         "expect_rounds": 6, "expect_cycles": 40809}),
    "dense-riscv-googlenet-conv2": (
        {"quantum": 256},
        {"layer": {"network": "t", "layer": "t", "h": 4, "w": 4, "p": 2},
         "expect_rounds": 2, "expect_cycles": 20000}),
    "snn-fc1200-infer": (TINY_SNN, {"t_steps": 6}),
    "snn-fc1200-serve-4chip": (
        TINY_SNN, {"t_steps_choices": [4, 5, 6], "pool": 6, "clients": 8,
                   "bucket": 8}),
}


def tiny_cell(name: str, chips: int | None = None):
    """(cell, config, traffic, metrics) of ``name`` cut to its tiny size."""
    from bench import run

    cell, config, traffic, metrics = run.load_cell(name)
    over_c, over_t = TINY[name]
    config, traffic = copy.deepcopy(config), copy.deepcopy(traffic)
    config.update(over_c)
    traffic.update(over_t)
    if chips is not None:
        cell = {**cell, "chips": chips}
    return cell, config, traffic, metrics


def rehearse(name: str, *, seed: int = 2**31 + 7, seconds: float = 0.5,
             chips: int | None = None, trace: bool = False) -> dict:
    """One run of the tiny cell through ``run_cell`` on the CPU: the whole
    harness past its look for a chip."""
    import jax

    from bench import kernels, run

    cell, config, traffic, metrics = tiny_cell(name, chips)
    return run.run_cell(cell, config, traffic, metrics, seed=seed,
                        seconds=seconds, trace=trace,
                        devices=jax.devices()[:cell["chips"]],
                        peak=kernels.peaks("TPU v5 lite"),
                        t_start=time.perf_counter())
