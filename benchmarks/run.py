"""Benchmark driver — one section per paper table/figure.

Prints ``name,us_per_call,derived`` CSV lines (see benchmarks/README.md
for the per-section line formats).  ``--json`` additionally captures every
emitted line into a JSON report.  Set REPRO_FULL_BENCH=1 (or pass
``--full``) for the unscaled Table III dimensions — the default divides
h/w/p by 8 so the whole suite finishes in minutes on a 1-core container;
speedup *ratios* are scale-stable, see EXPERIMENTS.md.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
from pathlib import Path

# runnable as ``python benchmarks/run.py`` from anywhere: put the repo root
# (the ``benchmarks`` namespace package) and src/ (``repro``) on the path
_ROOT = Path(__file__).resolve().parents[1]
for p in (str(_ROOT), str(_ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)


def provenance() -> dict:
    """Where this report came from: jax/backend/device/CPU-count/git-SHA.

    Embedded in every ``--json`` report so baselines are comparable across
    machines — ``--check --baseline OLD.json`` warns (never fails) when two
    reports were measured on different stacks.
    """
    import subprocess

    import jax

    try:
        sha = subprocess.run(
            ["git", "rev-parse", "HEAD"], cwd=_ROOT, text=True,
            capture_output=True, timeout=10).stdout.strip() or "unknown"
    except OSError:
        sha = "unknown"
    dev = jax.devices()[0]
    return {
        "jax": jax.__version__,
        "backend": jax.default_backend(),
        "device_kind": dev.device_kind,
        "device_count": jax.device_count(),
        "cpu_count": os.cpu_count(),
        "git_sha": sha,
    }


def provenance_warnings(ours: dict, baseline: dict) -> list:
    """Human-readable mismatch lines between two provenance dicts."""
    warns = []
    for key in sorted(set(ours) | set(baseline)):
        a, b = baseline.get(key), ours.get(key)
        if a != b:
            warns.append(f"provenance mismatch: {key}: "
                         f"baseline {a!r} vs this run {b!r}")
    return warns


def sections():
    from benchmarks import (
        bench_feature_matrix,
        bench_quantum_sweep,
        bench_roofline,
        bench_segmentation,
        bench_snn,
        bench_vmm_workloads,
    )

    return [
        ("feature_matrix", "Table I  — simulator feature matrix",
         bench_feature_matrix.main),
        ("vmm_workloads", "Table III / §V-B — VMM workloads (riscv vs cim)",
         bench_vmm_workloads.main),
        ("segmentation", "Fig. 4c/4d — segmentation speedups (sq vs pll)",
         bench_segmentation.main),
        ("snn", "SNN — spiking inference, spikes/sec per segmentation "
         "(feed-forward + recurrent/lateral) + wide-layer naive vs "
         "traffic-aware placement", bench_snn.main),
        ("quantum_sweep", "§V-C — quantum-size sweep", bench_quantum_sweep.main),
        ("roofline", "§Roofline — dry-run derived terms (40 cells)",
         bench_roofline.main),
    ]


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(
        prog="benchmarks/run.py",
        description="Run the paper-reproduction benchmark suite "
                    "(CSV lines on stdout; optional JSON report).")
    ap.add_argument("--only", metavar="KEY", default=None,
                    help="run a single section by key (see --list)")
    ap.add_argument("--list", action="store_true",
                    help="list section keys and titles, then exit")
    ap.add_argument("--json", metavar="PATH", default=None,
                    help="also write {section: [emitted lines]} plus timings "
                         "to PATH as JSON")
    ap.add_argument("--full", action="store_true",
                    help="unscaled Table III dimensions "
                         "(equivalent to REPRO_FULL_BENCH=1; much slower)")
    ap.add_argument("--check", action="store_true",
                    help="exit 1 if any emitted line carries a failed "
                         "verification flag (ok=False / correct=False / "
                         "supported=False) — CI smoke: perf runs cannot "
                         "silently break correctness")
    ap.add_argument("--baseline", metavar="PATH", default=None,
                    help="a previous --json report to compare provenance "
                         "against; with --check, mismatches (jax version, "
                         "backend, device kind, CPU count, git SHA) print "
                         "warnings — numbers from different stacks are not "
                         "comparable, but this never fails the run")
    args = ap.parse_args(argv)
    if args.full:
        os.environ["REPRO_FULL_BENCH"] = "1"  # before benchmarks.common import
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()

    secs = sections()
    if args.list:
        for key, title, _ in secs:
            print(f"{key:16s} {title}")
        return
    if args.only is not None:
        secs = [s for s in secs if s[0] == args.only]
        if not secs:
            sys.exit(f"unknown section {args.only!r}; try --list")

    report = {}
    t0 = time.time()
    print("name,us_per_call,derived")
    for key, title, fn in secs:
        print(f"# === {title} ===", flush=True)
        lines = []

        def out(line):
            print(line)
            lines.append(str(line))

        t1 = time.time()
        fn(out=out)
        report[key] = {"title": title, "lines": lines,
                       "seconds": round(time.time() - t1, 3)}
    total = time.time() - t0
    print(f"# total bench time: {total:.1f}s")
    prov = provenance()
    if args.json:
        with open(args.json, "w") as f:
            json.dump({"sections": report, "total_seconds": round(total, 3),
                       "full": bool(os.environ.get("REPRO_FULL_BENCH") == "1"),
                       "provenance": prov},
                      f, indent=2)
        print(f"# json report -> {args.json}")
    if args.baseline and args.check:
        with open(args.baseline) as f:
            base_prov = json.load(f).get("provenance", {})
        for w in provenance_warnings(prov, base_prov):
            print(f"# WARNING: {w}", file=sys.stderr)
    if args.check:
        bad = [line for sec in report.values() for line in sec["lines"]
               if any(flag in line for flag in
                      ("ok=False", "correct=False", "supported=False"))]
        if bad:
            print(f"# VERIFICATION FAILED on {len(bad)} line(s):", file=sys.stderr)
            for line in bad:
                print(f"#   {line}", file=sys.stderr)
            sys.exit(1)
        print(f"# verification flags clean across "
              f"{sum(len(s['lines']) for s in report.values())} lines")


if __name__ == "__main__":
    main()
