"""Fleet-scale SNN serving through one batched megaloop.

A fleet of independent inference requests (same compiled topology,
per-request rasters, weights, and channel caps) is submitted to
``SnnServer`` and served in padded buckets: each bucket runs as ONE
jitted job-axis megaloop dispatch (docs/serving.md), with per-job
termination flags judging every request against its OWN caps.

The script serves the same fleet at two bucket sizes, verifies every
result against the pure-jnp oracle counts carried by the request
builder, spot-checks that heterogeneous caps really shared one bucket,
and writes a requests/sec + p99-latency artifact, schema-validated
before exit so CI can trust its shape:

  PYTHONPATH=src python examples/snn_serve.py --json serve_bench.json

p99 here is *serving* latency — wall time from ``submit`` to the
request's bucket completing — so it rises with bucket size while
throughput climbs: the batching trade, visible in one artifact.
"""
import argparse
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1] / "src"))

import numpy as np

from repro.compile_cache import enable_compile_cache
from repro.serve.snn_serve import SnnServer, _normalize
from repro.snn import workloads as wl

SIZES = (16, 12, 8)
T_STEPS = 8
QUANTUM = 32
N_REQUESTS = 8
BUCKETS = (2, 8)

# the artifact contract: (key, required type) per row — checked by
# validate_artifact so downstream dashboards can rely on the shape
ROW_SCHEMA = (("bucket", int), ("req_per_s", float), ("p99_ms", float),
              ("served", int), ("dispatches", int), ("all_ok", bool))


def validate_artifact(obj):
    assert isinstance(obj.get("job"), str) and isinstance(obj.get("seed"), int)
    assert isinstance(obj.get("n_requests"), int) and obj["n_requests"] > 0
    assert isinstance(obj.get("check_every"), int)
    rows = obj.get("rows")
    assert isinstance(rows, list) and rows, "rows must be a non-empty list"
    for row in rows:
        for key, typ in ROW_SCHEMA:
            assert isinstance(row.get(key), typ), (key, row.get(key))
        assert row["bucket"] >= 1 and row["req_per_s"] > 0
        assert row["p99_ms"] > 0 and row["served"] == obj["n_requests"]
        assert row["all_ok"], "a served request failed verification"
    assert [r["bucket"] for r in rows] == sorted(r["bucket"] for r in rows), \
        "rows must be bucket-ordered"


def main(argv=None):
    ap = argparse.ArgumentParser(
        description="Serve an SNN request fleet through the batched "
                    "megaloop; write a requests/sec + p99 artifact.")
    ap.add_argument("--json", metavar="PATH", default="serve_bench.json",
                    help="serving-metrics artifact output path")
    ap.add_argument("--requests", type=int, default=N_REQUESTS,
                    help="fleet size")
    ap.add_argument("--seed", type=int, default=11, help="fleet PRNG seed")
    args = ap.parse_args(argv)
    enable_compile_cache()

    # heterogeneous caps on purpose: half the fleet gets roomier channels,
    # yet _normalize folds caps out of the bucket key, so ONE bucket serves
    # both halves (each judged against its own caps by the vmapped flags)
    fleet = (wl.serve_fleet(args.requests // 2, SIZES, seed=args.seed,
                            t_steps_choices=(T_STEPS,), in_cap=192,
                            out_cap=64)
             + wl.serve_fleet(args.requests - args.requests // 2, SIZES,
                              seed=args.seed + 1,
                              t_steps_choices=(T_STEPS,), in_cap=320,
                              out_cap=128))
    assert len({_normalize(r.cfg) for r in fleet}) == 1, \
        "mixed caps should share one bucket key"
    print(f"fleet: {len(fleet)} requests, {SIZES} @ t={T_STEPS}, "
          "mixed in_cap 192/320 -> one bucket key")

    rows = []
    for bucket in BUCKETS:
        def serve():
            srv = SnnServer(quantum=QUANTUM, check_every=4, max_rounds=400,
                            bucket_size=bucket)
            for r in fleet:
                srv.submit(r)
            t0 = time.perf_counter()
            res = srv.flush()
            return time.perf_counter() - t0, res, srv
        serve()  # warm: compile the width-`bucket` batched megaloop
        elapsed, results, srv = serve()

        all_ok = True
        for ticket, req in enumerate(fleet):
            r = results[ticket]
            assert r.ok, f"request {ticket} failed: {r.error}"
            np.testing.assert_array_equal(r.output_counts(),
                                          req.expected_counts)
            all_ok &= r.ok
        p99 = float(np.percentile([r.latency_s for r in results.values()],
                                  99)) * 1e3
        rps = len(fleet) / elapsed
        rows.append({"bucket": bucket, "req_per_s": rps, "p99_ms": p99,
                     "served": srv.served, "dispatches": srv.dispatches,
                     "all_ok": bool(all_ok)})
        print(f"bucket={bucket}: {rps:.1f} req/s, p99 {p99:.0f} ms, "
              f"{srv.dispatches} dispatches, all {srv.served} requests "
              "oracle-exact")

    artifact = {
        "job": "x".join(str(s) for s in SIZES) + f"@t{T_STEPS}",
        "seed": args.seed,
        "n_requests": len(fleet),
        "check_every": 4,
        "quantum": QUANTUM,
        "rows": rows,
    }
    validate_artifact(artifact)
    with open(args.json, "w") as f:
        json.dump(artifact, f, indent=2)
    print(f"serving metrics -> {args.json} (schema-valid)")


if __name__ == "__main__":
    main()
