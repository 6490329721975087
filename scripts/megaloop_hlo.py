"""Print the optimized HLO of a benchmark cell's ``vmap`` megaloop, as the
program dispatched it on this machine's device, without what does not
change the program: the module's name, metadata, the source tables, and
each Pallas kernel's debug labels (JAX names a kernel's custom call after
the name stack, and its serialized Mosaic payload carries source files and
lines).

    python scripts/megaloop_hlo.py --workload snn-fc1200-infer \\
        [--root <checkout>] > change.txt

The script runs one job of the cell (``bench/cells.py``) and prints what
``repro.core.controller.dispatched_megaloop_hlo()`` gives.  ``--root``
runs another checkout's program (``src/``) and benchmark (``bench/``), so
two commits compare with ``diff``: an empty diff means the same fused
program (every fusion, layout and schedule).  Both checkouts need
``dispatched_megaloop_hlo``, which compiles under a module name of each
checkout's own, so a compile cache the two share cannot hand one side the
other's executable.  The program depends on shapes only, which the
cell's configuration and traffic files fix, so the job's seed is a
constant.
"""
from __future__ import annotations

import argparse
import re
import sys
from pathlib import Path

SECTIONS = ("FileNames", "FunctionNames", "FileLocations", "StackFrames")
MODULE = re.compile(r"^HloModule [^\s,]+")
KERNEL = re.compile(r'%([\w.-]+) = [^\n]*custom_call_target="tpu_custom_call"')
PAYLOAD = re.compile(r'(custom_call_target="tpu_custom_call"[^\n]*?), '
                     r'backend_config=\{"flag_configs".*?"custom_call_config"'
                     r':\{"body":"[^"]*"')


def normalize(text: str) -> str:
    out, skip = [], False
    for line in text.splitlines():
        if line.split(" ")[0] in SECTIONS:
            skip = True
            continue
        if skip:
            skip = bool(line.strip())
            continue
        out.append(re.sub(r", metadata=\{[^}]*\}", "", line))
    text = MODULE.sub("HloModule jit_mega", "\n".join(out))
    for k, name in enumerate(KERNEL.findall(text)):
        text = re.sub("%" + re.escape(name) + r"(?![\w.-])", f"%kernel.{k}",
                      text)
    return PAYLOAD.sub(r"\1, backend_config={payload", text)


SEED = 7


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--root", default=str(Path(__file__).resolve().parents[1]))
    args = ap.parse_args()
    root = Path(args.root).resolve()
    sys.path[:0] = [str(root / "src"), str(root)]
    from bench import cells, run
    from repro.core.controller import dispatched_megaloop_hlo

    _, config, traffic, _ = run.load_cell(args.workload)
    cells.SimCell(config, traffic, SEED).warm()
    print(normalize(dispatched_megaloop_hlo()[0]))


if __name__ == "__main__":
    main()
