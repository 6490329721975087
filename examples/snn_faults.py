"""Fault injection & graceful degradation on an SNN inference job.

Three passes over the same rate-coded network (see docs/faults.md):

1. **Seeded faults, traced** — the job runs fault-free (``faults=None``,
   the subsystem compiled out) and again with all three fault families
   live (stuck crossbar cells, dead/drifted neurons, seeded AER spike
   drop/duplication) plus trace rings, so every transport injection lands
   in the event log as a ``fault_injected`` event.  The fault-free run is
   asserted oracle-exact; the faulted run is asserted *deterministic*
   (bit-identical fused vs per-round dispatch).

2. **Graceful degradation** — the same faulted network is rebuilt with an
   undersized outbox and ``on_overflow="drop"``: where the default policy
   aborts with a watermark RuntimeError, the drop policy completes with
   the overflow converted into counted, traced spike loss.

3. **Degradation sweep** — ``snn.degradation_sweep`` drives one fault axis
   (transport / crossbar / neuron) through a rate grid and writes the
   accuracy-vs-fault-rate curve as a JSON artifact, schema-validated
   before the script exits so CI can trust its shape.

  PYTHONPATH=src python examples/snn_faults.py --json faults_sweep.json

"""
import argparse
import json
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1] / "src"))

import jax
import numpy as np

from repro import snn
from repro.compile_cache import enable_compile_cache
from repro.core.controller import Controller
from repro.faults import FaultConfig, fidelity
from repro.obs import TraceConfig

SIZES = (32, 24, 10)
T_STEPS = 8
QUANTUM = 32

FAULTS = FaultConfig(seed=7, p_stuck0=0.05, p_dead=0.05,
                     p_thresh_drift=0.1, p_spike_drop=0.1, p_spike_dup=0.05)

# the sweep artifact contract: (key, required type) per row — checked by
# validate_artifact so downstream dashboards can rely on the shape
ROW_SCHEMA = (("rate", float), ("fidelity", float),
              ("total_spikes", int), ("rounds", int), ("counts", list))


def validate_artifact(obj):
    assert isinstance(obj.get("job"), str) and isinstance(obj.get("seed"), int)
    assert obj.get("fault_kind") in ("transport", "crossbar", "neuron")
    rows = obj.get("sweep")
    assert isinstance(rows, list) and rows, "sweep must be a non-empty list"
    for row in rows:
        for key, typ in ROW_SCHEMA:
            assert isinstance(row.get(key), typ), (key, row.get(key))
        assert 0.0 <= row["rate"] <= 1.0 and 0.0 <= row["fidelity"] <= 1.0
        assert all(isinstance(c, int) for c in row["counts"])
    rates = [r["rate"] for r in rows]
    assert rates == sorted(rates), "rows must be rate-ordered"
    assert rows[0]["rate"] == 0.0 and rows[0]["fidelity"] == 1.0, \
        "rate 0 must be oracle-exact (faults compiled out)"


def run(cfg, states, pending, fused=True, obs=None):
    ctl = Controller(cfg, states, pending, backend="vmap", quantum=QUANTUM,
                     obs=obs)
    ctl.run(max_rounds=400, check_every=2, fused=fused)
    return ctl


def main(argv=None):
    ap = argparse.ArgumentParser(
        description="Seeded fault injection, graceful overflow degradation, "
                    "and an accuracy-vs-fault-rate sweep artifact.")
    ap.add_argument("--json", metavar="PATH", default="faults_sweep.json",
                    help="degradation-sweep artifact output path")
    ap.add_argument("--kind", default="transport",
                    choices=("transport", "crossbar", "neuron"),
                    help="which fault axis the sweep drives")
    ap.add_argument("--rates", default="0,0.2,0.5,1.0",
                    help="comma-separated fault rates for the sweep")
    ap.add_argument("--seed", type=int, default=7, help="fault PRNG seed")
    args = ap.parse_args(argv)
    enable_compile_cache()

    job = snn.snn_inference_job(SIZES, t_steps=T_STEPS, rate=0.5, seed=2)
    descs = snn.segmentation_for(snn.n_units_for(job.layers), "uniform",
                                 n_segments=2)

    # -- 1. fault-free vs faulted, traced ---------------------------------
    cfg, states, pending, meta = snn.build_snn(job.layers, descs, job.raster)
    base = run(cfg, states, pending)
    counts = snn.output_spike_counts(base.result_states(), meta)
    np.testing.assert_array_equal(counts, job.expected_counts)
    print(f"fault-free: {int(np.asarray(counts).sum())} output spikes, "
          "oracle-exact")

    fcfg, fstates, fpending, fmeta = snn.build_snn(
        job.layers, descs, job.raster, faults=FAULTS)
    faulted = run(fcfg, fstates, fpending, obs=TraceConfig())
    per_round = run(fcfg, fstates, fpending, fused=False)
    traced_st = dict(faulted.result_states())
    traced_st.pop("trace", None)
    for a, b in zip(jax.tree.leaves(traced_st),
                    jax.tree.leaves(per_round.result_states())):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    st = faulted.result_states()["stats"]
    events = faulted.trace_events()
    from repro.obs import trace as tr
    n_fault_ev = int((np.asarray(events["kind"]) == tr.EV_FAULT).sum())
    fcounts = snn.output_spike_counts(faulted.result_states(), fmeta)
    print(f"faulted:    {int(np.asarray(fcounts).sum())} output spikes "
          f"(dropped={int(np.asarray(st['spikes_dropped']).sum())}, "
          f"duped={int(np.asarray(st['spikes_duped']).sum())}), "
          f"{n_fault_ev} fault_injected trace events, "
          "bit-identical fused vs per-round")

    # -- 2. graceful degradation under an undersized outbox ---------------
    try:
        run(*snn.build_snn(job.layers, descs, job.raster, out_cap=8)[:3])
        raise AssertionError("undersized outbox should have aborted")
    except RuntimeError as e:
        print(f"raise policy: {str(e).splitlines()[0][:72]}…")
    dcfg, dstates, dpending, dmeta = snn.build_snn(
        job.layers, descs, job.raster, out_cap=8,
        faults=FaultConfig(on_overflow="drop"))
    degraded = run(dcfg, dstates, dpending)
    lost = int(np.asarray(
        degraded.result_states()["stats"]["outbox_lost"]).sum())
    dc = snn.output_spike_counts(degraded.result_states(), dmeta)
    print(f"drop policy:  run completes, {lost} spikes lost to overflow, "
          f"fidelity {fidelity(dc, job.expected_counts):.3f}")

    # -- 3. degradation sweep artifact ------------------------------------
    rates = [float(r) for r in args.rates.split(",")]
    sweep = snn.degradation_sweep(job, rates, fault_kind=args.kind,
                                  seed=args.seed)
    artifact = {
        "job": "x".join(str(s) for s in SIZES) + f"@t{T_STEPS}",
        "fault_kind": args.kind,
        "seed": args.seed,
        "sweep": [{"rate": r["rate"], "fidelity": r["fidelity"],
                   "total_spikes": r["total_spikes"], "rounds": r["rounds"],
                   "counts": [int(c) for c in r["counts"]]} for r in sweep],
    }
    validate_artifact(artifact)
    with open(args.json, "w") as f:
        json.dump(artifact, f, indent=2)
    curve = " ".join(f"{r['rate']:g}:{r['fidelity']:.3f}"
                     for r in artifact["sweep"])
    print(f"degradation sweep ({args.kind}) -> {args.json} "
          f"(schema-valid): {curve}")


if __name__ == "__main__":
    main()
