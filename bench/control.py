"""The lower-precision control: the plain reference computed in bfloat16,
put in the program's place and judged by the benchmark's own comparison.
A comparison that lets it through could not tell a simulation computed in
a lower precision from an exact one.

    python bench/control.py --workload <cell> --seeds <n> [<n> ...]

runs the control at the cell's own size (on the default device, which is
the chip where there is one) and prints, per seed, each number the cell
compares with its limit; ``correct`` must come out false on every seed.
Each configuration kind computes its own control (``control`` in
``bench/kinds/<kind>.py``).
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    for p in (ROOT / "src", ROOT):
        sys.path.insert(0, str(p))
    import jax

    from bench import kinds, run

    _, config, traffic, _ = run.load_cell(args.workload)
    kind = kinds.find(config["kind"])
    dev = jax.devices()[0]
    for seed in args.seeds:
        vals = kind.control(config, traffic, seed)
        print(json.dumps({"workload": args.workload, "seed": seed,
                          "device": dev.device_kind,
                          "correct": not any(vals.values()),
                          "numbers": vals}), flush=True)


if __name__ == "__main__":
    main()
