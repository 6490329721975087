"""The lower-precision control: the plain reference computed in bfloat16,
put in the program's place and judged by the benchmark's own comparison.
A comparison that lets it through could not tell a simulation computed in
a lower precision from an exact one.

    python bench/control.py --workload <cell> --seeds <n> [<n> ...]

runs the control at the cell's own size (on the default device, which is
the chip where there is one) and prints, per seed, each number the cell
compares with its limit; ``correct`` must come out false on every seed.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parents[1]


def dense_numbers(config, traffic, seed: int) -> dict:
    from bench import check, jobs, reference

    job = jobs.dense_job(config, traffic, seed, 0)
    got = reference.dense_output(job.a, job.b, "bf16")
    return {"output_words_wrong": check.dense_wrong(job, got)}


def snn_numbers(config, t_steps: int, rate: float, seed: int) -> dict:
    """The bf16 oracle's membranes and spike totals after the chain's
    horizon, laid out one unit per layer, against the exact oracle's."""
    from bench import check, jobs, reference

    job = jobs.snn_job(config, t_steps, rate, seed, 0)
    ticks = reference.feedforward_horizon(t_steps, len(job.weights))
    ref = check.snn_reference(job, ticks)
    v, c = check.snn_reference(job, ticks, "bf16")
    width = max(len(x[0]) for x in v)
    shape = (len(v), 1, width)
    vu, cu = np.zeros(shape, np.int64), np.zeros(shape, np.int64)
    for l, (vl, cl) in enumerate(zip(v, c)):
        vu[l, 0, :vl.shape[1]] = vl[ticks]
        cu[l, 0, :cl.shape[1]] = cl[ticks]
    groups = [(l, 0, x.shape[1], l, 0) for l, x in enumerate(v)]
    tu = np.full((len(v), 1), ticks)
    spikes, mem = check.snn_wrong(groups, vu, cu, tu, ref)
    return {"spike_totals_wrong": spikes, "membranes_wrong": mem}


def numbers(config, traffic, seed: int) -> dict:
    """Each number the control reads in ``traffic``'s cell."""
    if traffic["kind"] == "dense":
        return dense_numbers(config, traffic, seed)
    t = (traffic["t_steps"] if traffic["kind"] == "snn"
         else max(traffic["t_steps_choices"]))
    return snn_numbers(config, t, traffic["rate"], seed)


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    for p in (ROOT / "src", ROOT):
        sys.path.insert(0, str(p))
    import jax

    from bench import run

    _, config, traffic, _ = run.load_cell(args.workload)
    dev = jax.devices()[0]
    for seed in args.seeds:
        vals = numbers(config, traffic, seed)
        print(json.dumps({"workload": args.workload, "seed": seed,
                          "device": dev.device_kind,
                          "correct": not any(vals.values()),
                          "numbers": vals}), flush=True)


if __name__ == "__main__":
    main()
