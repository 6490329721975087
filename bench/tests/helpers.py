"""Tiny versions of the cells, for CPU runs of the whole harness path."""
from __future__ import annotations

import copy
import json
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


# the serving cell and its metrics, kept out of BENCHMARK.json until it has
# run on four chips (PERF.md, Open questions)
SERVE = {
    "workloads": [{"name": "snn-fc1200-serve-4chip",
                   "config": "snn-fc784-1200-1200-10",
                   "traffic": "snn-serve-closed32", "chips": 4}],
    "end_to_end": [
        {"name": n, "unit": u, "workloads": ["snn-fc1200-serve-4chip"]}
        for n, u in (("requests_per_s", "req/s"), ("request_p95_ms", "ms"))],
    "per_layer": [
        {"name": n, "unit": u, "workloads": ["snn-fc1200-serve-4chip"]}
        for n, u in (("device_idle_pct.serve", "%"),
                     ("device_ms_per_round.serve", "ms"),
                     ("lif_step_roofline.serve", "%"))],
}


def spec_with_serve() -> dict:
    """BENCHMARK.json with the serving cell added."""
    from bench import run

    spec = json.loads((run.ROOT / "BENCHMARK.json").read_text())
    return {k: spec[k] + SERVE.get(k, []) if isinstance(spec[k], list)
            else spec[k] for k in spec}


def tiny_cell(name: str, chips: int | None = None):
    """(cell, config, traffic, metrics) of ``name`` cut to its tiny size."""
    from bench import run

    cell, config, traffic, metrics = run.load_cell(name, spec_with_serve())
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
