"""Device-resident telemetry on the hybrid co-simulation, end to end.

The paper's headline scenario — dense VMM offload on CPU0's units while
CPU1 injects a spike raster over MMIO into LIF layers — runs on the fused
vmap megaloop with trace rings enabled (``Controller(obs=TraceConfig())``).
Every dispatch drains its ring batch through the ``on_telemetry`` callback
(streamed here as NDJSON, the live-dashboard format), and at the end the
full event log is exported as a Chrome-trace/Perfetto JSON timeline:
quantum slices per segment, LIF tick instants per CIM unit, inbox
occupancy counters, and cross-segment spike flow arrows.

Tracing must be *invisible* to the simulation, so the script also runs the
same job untraced and asserts the final states are bit-identical — plus
the usual oracle checks on both the dense output matrix and the
CPU-published spike counts.

  PYTHONPATH=src python examples/snn_telemetry.py --json trace.json --ndjson trace.ndjson

Load the JSON at https://ui.perfetto.dev (or chrome://tracing); see
docs/observability.md for the event schema and track layout.
"""
import argparse
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1] / "src"))

import jax
import numpy as np

from repro import snn
from repro.compile_cache import enable_compile_cache
from repro.core.controller import Controller
from repro.obs import TraceConfig, export

SIZES = (16, 12, 8)
T_STEPS = 6
QUANTUM = 400


def main(argv=None):
    ap = argparse.ArgumentParser(
        description="Hybrid co-simulation with device-resident telemetry: "
                    "stream NDJSON per dispatch, export a Perfetto timeline.")
    ap.add_argument("--json", metavar="PATH", default="telemetry_trace.json",
                    help="Chrome-trace/Perfetto JSON output path")
    ap.add_argument("--ndjson", metavar="PATH", default=None,
                    help="also stream drained batches here as NDJSON "
                         "(one flat object per trace event)")
    args = ap.parse_args(argv)
    enable_compile_cache()

    job = snn.hybrid_job(SIZES, t_steps=T_STEPS, rate=0.5, seed=2)
    cfg, states, pending, meta = snn.build_hybrid(job, "packed",
                                                  channel_latency=2000)

    # untraced reference: tracing is compiled out entirely with obs=None
    ref = Controller(cfg, states, pending, backend="vmap", quantum=QUANTUM)
    ref.run(max_rounds=800, check_every=2, fused=True)

    ndjson_fh = open(args.ndjson, "w") if args.ndjson else None
    on_telemetry = export.ndjson_callback(ndjson_fh) if ndjson_fh else None
    ctl = Controller(cfg, states, pending, backend="vmap", quantum=QUANTUM,
                     obs=TraceConfig())
    ctl.run(max_rounds=800, check_every=2, fused=True,
            on_telemetry=on_telemetry)
    if ndjson_fh:
        ndjson_fh.close()

    # bit-identity: telemetry must not perturb the simulation
    traced_st = dict(ctl.result_states())
    traced_st.pop("trace", None)
    assert ctl.rounds_run == ref.rounds_run
    for a, b in zip(jax.tree.leaves(traced_st),
                    jax.tree.leaves(ref.result_states())):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    # oracle checks: both halves of the co-simulation are exact
    o, counts = snn.hybrid_results(ctl.result_states(), meta)
    np.testing.assert_array_equal(o, job.dense_expected)
    np.testing.assert_array_equal(counts, job.snn.expected_counts)

    events = ctl.trace_events()
    obj = export.write_chrome_trace(args.json, events,
                                    tick_period=cfg.snn_tick_period,
                                    title="hybrid co-simulation")
    kinds = {str(k): int(n) for k, n in zip(
        *np.unique([export.tr.KIND_NAMES[int(k)] for k in events["kind"]],
                   return_counts=True))}
    print(f"rounds: {ctl.rounds_run} (bit-identical to untraced run)")
    print(f"dispatch host syncs: {ctl.dispatch_syncs} "
          f"for {ctl.dispatches} fused dispatch(es)")
    print(f"trace events: {len(events)} ({kinds}), lost: {ctl.trace_lost}")
    print(f"perfetto timeline -> {args.json} "
          f"({len(obj['traceEvents'])} trace events, schema-valid)")
    if args.ndjson:
        print(f"ndjson stream -> {args.ndjson}")
    print("dense O matrix and CPU-published spike counts match their oracles")


if __name__ == "__main__":
    main()
