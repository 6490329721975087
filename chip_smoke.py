#!/usr/bin/env python3
"""Smoke run of the VP's main path on a TPU, through its normal entry points.

    python chip_smoke.py             # one chip: every phase below
    python chip_smoke.py --chips 4   # the multi-chip paths only

One chip runs, in this one process:

* dense, at the paper's dimensions: Table III ImageNet-conv1 (224x224x11,
  uniform segmentation, quantum 10 000) offloaded to CIM units on the
  ``vmap`` megaloop, on ``sequential`` and with the Pallas crossbar kernel,
  then in RISC-V mode; every output is checked against the layer's oracle
  and the simulated cycles must agree across backends and kernel paths
  (RISC-V mode at these dimensions is 688 rounds, the longest phase);
* SNN: the feed-forward and the wide network of ``benchmarks/bench_snn.py``
  on 4 uniform segments, with and without the Pallas LIF kernel, against
  the jnp oracle;
* serving: one ``SnnServer`` bucket of 8 requests against their oracles;
* kernels: ``lif_step`` and ``crossbar_vmm`` against their jnp references,
  bit for bit, over the full input range.

``--chips 4`` runs only the ``shard_map`` segment backend (one segment per
chip) and the serving fan-out (one job shard per chip), each against its
one-chip result, bit for bit.

Each phase prints one line of bring-up observations (host wall seconds of
one run; they are not benchmark numbers).  The last line is a JSON object
naming the device, printed only when every check passed.  On any platform
but TPU the script exits non-zero before running anything.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

import jax
import numpy as np

ROOT = Path(__file__).resolve().parent

SNN_QUANTUM = 32
SERVE_REQUESTS = 8
SERVE_CHECK_EVERY = 4


def fail(msg: str):
    sys.exit(f"chip_smoke: FAILED: {msg}")


def check(ok, what: str):
    if not ok:
        fail(what)


class CompileClock:
    """Seconds JAX spends tracing, lowering and compiling (its own
    monitoring events; a persistent-cache hit counts as its load time)."""

    EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
              "/jax/core/compile/jaxpr_to_mlir_module_duration",
              "/jax/core/compile/backend_compile_duration")

    def __init__(self):
        self.total = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event, duration, **_):
        if event in self.EVENTS:
            self.total += duration


def observe(phase, workload, *, rounds="-", cycles="-", correct, compile_s,
            run_s="-"):
    fmt = lambda x: x if isinstance(x, str) else f"{x:.3f}"
    print(f"observed phase={phase} workload={workload} rounds={rounds} "
          f"sim_cycles={cycles} correct={correct} compile_s={fmt(compile_s)} "
          f"run_s={fmt(run_s)}", flush=True)


def run_controller(clock, cfg, states, pending, backend, quantum, max_rounds,
                   mesh=None):
    """Warm-up (compiles) then one timed run of a fresh controller;
    returns (ctl, compile_s, run_s)."""
    from repro.core.controller import Controller

    c0 = clock.total
    warm = Controller(cfg, states, pending, backend=backend, quantum=quantum,
                      mesh=mesh)
    warm.run(max_rounds=2, check_every=2)
    warm.block_until_ready()
    ctl = Controller(cfg, states, pending, backend=backend, quantum=quantum,
                     mesh=mesh)
    t0 = time.perf_counter()
    ctl.run(max_rounds=max_rounds, check_every=2)
    run_s = time.perf_counter() - t0
    return ctl, clock.total - c0, run_s


def megaloop_has_kernel(ctl) -> bool:
    """Whether the controller's compiled megaloop holds a Mosaic kernel."""
    args = (ctl.states, ctl.pending, np.int32(0), np.int32(1), np.int32(1))
    return "tpu_custom_call" in ctl._vmap_mega.lower(*args).compile().as_text()


# -- one chip ---------------------------------------------------------------

def dense_phases(clock, layer, quantum=10_000, latency=10_000):
    from benchmarks.common import build_workload, verify

    name = lambda l: f"{l.network}-{l.layer}({l.h}x{l.w}x{l.p})"
    cycles = {}
    for phase, backend, use_kernel in (("dense/cim/vmap", "vmap", False),
                                       ("dense/cim/sequential", "sequential",
                                        False),
                                       ("dense/cim/vmap+kernel", "vmap", True)):
        cfg, states, pending, job = build_workload(
            layer, "uniform", "cim", latency, use_kernel=use_kernel)
        ctl, comp, run_s = run_controller(clock, cfg, states, pending, backend,
                                          quantum, max_rounds=2000)
        ok = verify(ctl, job, layer)
        cycles[phase] = int(np.max(ctl.sim_time()))
        observe(phase, name(layer), rounds=ctl.rounds_run,
                cycles=cycles[phase], correct=ok, compile_s=comp, run_s=run_s)
        check(ok, f"{phase}: output differs from the layer oracle")
        if use_kernel:
            check(megaloop_has_kernel(ctl),
                  f"{phase}: compiled megaloop holds no tpu_custom_call")
    check(len(set(cycles.values())) == 1,
          f"dense simulated cycles differ across backends/kernels: {cycles}")

    cfg, states, pending, job = build_workload(layer, "uniform", "riscv",
                                               latency)
    ctl, comp, run_s = run_controller(clock, cfg, states, pending, "vmap",
                                      quantum, max_rounds=2000)
    ok = verify(ctl, job, layer)
    observe("dense/riscv/vmap", name(layer), rounds=ctl.rounds_run,
            cycles=int(np.max(ctl.sim_time())), correct=ok, compile_s=comp,
            run_s=run_s)
    check(ok, "dense/riscv/vmap: output differs from the layer oracle")


def snn_networks():
    """(name, job, descs) for the feed-forward and the wide network of
    benchmarks/bench_snn.py, each on 4 uniform segments (the wide one
    placed the way its naive run is)."""
    from benchmarks import bench_snn as b
    from repro import snn

    ff = snn.snn_inference_job(b.SIZES, t_steps=b.T_STEPS, rate=0.5, seed=2)
    wide = snn.snn_inference_job(b.WIDE_SIZES, t_steps=b.WIDE_T_STEPS,
                                 rate=0.4, seed=4)
    net = lambda sizes: "snn" + "x".join(map(str, sizes))
    return [
        (net(b.SIZES), ff,
         snn.segmentation_for(len(ff.layers), "uniform", n_segments=4)),
        (net(b.WIDE_SIZES), wide,
         snn.segmentation_for(wide.layers, "uniform", n_segments=4)),
    ]


def snn_phases(clock, networks):
    from repro import snn

    for name, job, descs in networks:
        totals = {}
        for use_kernel in (False, True):
            phase = "snn/vmap" + ("+kernel" if use_kernel else "")
            cfg, states, pending, meta = snn.build_snn(
                job.layers, descs, job.raster, use_kernel=use_kernel)
            ctl, comp, run_s = run_controller(clock, cfg, states, pending,
                                              "vmap", SNN_QUANTUM,
                                              max_rounds=400)
            st = ctl.result_states()
            counts = snn.output_spike_counts(st, meta)
            ok = bool(np.array_equal(counts, job.expected_counts))
            totals[use_kernel] = snn.total_spikes(st)
            observe(phase, name, rounds=ctl.rounds_run,
                    cycles=int(np.max(ctl.sim_time())), correct=ok,
                    compile_s=comp, run_s=run_s)
            check(ok, f"{phase} {name}: output counts differ from the oracle")
            if use_kernel:
                check(megaloop_has_kernel(ctl),
                      f"{phase} {name}: compiled megaloop holds no "
                      "tpu_custom_call")
        check(totals[False] == totals[True],
              f"{name}: spike totals differ across kernel paths: {totals}")


def serve_fleet():
    from benchmarks import bench_snn as b
    from repro.snn import workloads as wl

    return wl.serve_fleet(SERVE_REQUESTS, b.MEGA_SIZES, seed=6,
                          t_steps_choices=(b.SERVE_T_STEPS,), rate=0.2,
                          **b.SERVE_CAPS)


def serve(reqs, mesh=None):
    """One flush of ``reqs`` through an SnnServer; returns (results by
    ticket, seconds)."""
    from repro.serve.snn_serve import SnnServer

    srv = SnnServer(quantum=SNN_QUANTUM, check_every=SERVE_CHECK_EVERY,
                    max_rounds=400, bucket_size=SERVE_REQUESTS, mesh=mesh)
    for r in reqs:
        srv.submit(r)
    t0 = time.perf_counter()
    res = srv.flush()
    return res, time.perf_counter() - t0


def serve_phase(clock, reqs):
    c0 = clock.total
    serve(reqs)
    comp = clock.total - c0
    res, run_s = serve(reqs)
    ok = len(res) == len(reqs) and all(
        r.ok and np.array_equal(r.output_counts(), req.expected_counts)
        for r, req in zip((res[k] for k in sorted(res)), reqs))
    observe("serve/bucket8", f"fleet{len(reqs)}",
            rounds=max(r.rounds for r in res.values()), correct=ok,
            compile_s=comp, run_s=run_s)
    check(ok, "serve: a request failed or differs from its oracle counts")


def kernel_inputs(seed=0, u=8, r=256, c=256):
    """Inputs spanning each kernel's full range, extremes included."""
    from repro.kernels.lif_step.ref import SPIKE_SAT

    rng = np.random.default_rng(seed)
    i32 = lambda lo, hi, shape: rng.integers(lo, hi, shape).astype(np.int32)
    w = rng.integers(-128, 128, (u, r, c)).astype(np.int8)
    s = i32(-SPIKE_SAT, SPIKE_SAT + 1, (u, c))
    # the accumulator's extremes: |W·s| = 256·128·511 < 2^24
    w[0], s[0] = 127, SPIKE_SAT
    w[1], s[1] = -128, SPIKE_SAT
    return dict(
        w=w, s=s, v=i32(0, 1 << 20, (u, r)), refrac=i32(0, 3, (u, r)),
        thresh=i32(1, 1 << 22, (u,)), leak=i32(0, 64, (u,)),
        rp=i32(0, 4, (u,)), extra=i32(-(1 << 20), 1 << 20, (u, r)),
        f_and=rng.integers(-128, 128, (u, r, c)).astype(np.int8),
        f_xor=np.where(rng.random((u, r, c)) < 0.05,
                       rng.integers(-128, 128, (u, r, c)), 0).astype(np.int8),
        dead=rng.random((u, r)) < 0.05, dth=i32(-8, 9, (u, r)),
        x=i32(-(1 << 12), 1 << 12, (u, c)),  # past the DAC clamp
    )


def kernel_phase(clock):
    from repro.kernels.crossbar_vmm import ops as xb_ops, ref as xb_ref
    from repro.kernels.lif_step import ops as lif_ops, ref as lif_ref

    d = kernel_inputs()
    u, r, c = d["w"].shape
    lif_args = (d["w"], d["s"], d["v"], d["refrac"], d["thresh"], d["leak"],
                d["rp"])
    cases = {
        "lif_step/plain": lambda m: m.lif_step_units(*lif_args),
        "lif_step/extra": lambda m: m.lif_step_units(*lif_args, d["extra"]),
        "lif_step/faults": lambda m: m.lif_step_units(
            *lif_args, d["extra"], d["f_and"], d["f_xor"], d["dead"],
            d["dth"]),
        "crossbar_vmm/plain": lambda m: m.crossbar_vmm_batch(d["w"], d["x"]),
        "crossbar_vmm/faults": lambda m: m.crossbar_vmm_batch(
            d["w"], d["x"], 8, 8, d["f_and"], d["f_xor"]),
    }
    for case, call in cases.items():
        ops, ref = ((lif_ops, lif_ref) if case.startswith("lif")
                    else (xb_ops, xb_ref))
        c0 = clock.total
        t0 = time.perf_counter()
        got = jax.device_get(call(ops))
        run_s = time.perf_counter() - t0
        want = jax.device_get(call(ref))
        ok = all(np.array_equal(a, b) for a, b in
                 zip(jax.tree.leaves(got), jax.tree.leaves(want)))
        observe(f"kernel/{case}", f"U{u}xR{r}xC{c}", correct=ok,
                compile_s=clock.total - c0, run_s=run_s)
        check(ok, f"kernel/{case}: differs from its jnp reference")


# -- four chips -------------------------------------------------------------

def same_tree(a, b) -> bool:
    la, lb = jax.tree.leaves(a), jax.tree.leaves(b)
    return len(la) == len(lb) and all(
        np.array_equal(np.asarray(x), np.asarray(y)) for x, y in zip(la, lb))


def four_chip_phases(clock, n):
    from repro import snn
    from repro.launch.mesh import make_mesh, make_serve_mesh

    name, job, descs = snn_networks()[0]
    cfg, states, pending, meta = snn.build_snn(job.layers, descs, job.raster)
    check(cfg.n_segments == n, f"{name} has {cfg.n_segments} segments, "
          f"not one per chip ({n})")
    one, _, _ = run_controller(clock, cfg, states, pending, "vmap",
                               SNN_QUANTUM, max_rounds=400)
    ctl, comp, run_s = run_controller(
        clock, cfg, states, pending, "shard_map", SNN_QUANTUM, max_rounds=400,
        mesh=make_mesh((n,), ("segment",)))
    ok = (ctl.rounds_run == one.rounds_run
          and same_tree(ctl.result_states(), one.result_states())
          and np.array_equal(snn.output_spike_counts(ctl.result_states(), meta),
                             job.expected_counts))
    observe(f"snn/shard_map{n}", name, rounds=ctl.rounds_run,
            cycles=int(np.max(ctl.sim_time())), correct=ok, compile_s=comp,
            run_s=run_s)
    check(ok, f"snn/shard_map{n}: differs from the one-chip vmap run")

    reqs = serve_fleet()
    solo, _ = serve(reqs)
    c0 = clock.total
    fan, _ = serve(reqs, mesh=make_serve_mesh(n))
    comp = clock.total - c0
    fan, run_s = serve(reqs, mesh=make_serve_mesh(n))
    ok = sorted(fan) == sorted(solo) and all(
        fan[k].ok and solo[k].ok and fan[k].rounds == solo[k].rounds
        and same_tree(fan[k].states, solo[k].states) for k in solo)
    observe(f"serve/fanout{n}", f"fleet{len(reqs)}",
            rounds=max(r.rounds for r in fan.values()), correct=ok,
            compile_s=comp, run_s=run_s)
    check(ok, f"serve/fanout{n}: differs from the one-chip server")


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the multi-chip paths, each against "
                         "its one-chip result")
    args = ap.parse_args(argv)

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        fail(f"needs a TPU; JAX found platform {dev.platform!r} "
             f"({dev.device_kind})")
    n = len(jax.devices())
    check(n >= args.chips, f"--chips {args.chips} needs {args.chips} "
          f"devices, JAX found {n}")
    check((ROOT / "src" / "repro").is_dir(),
          f"no repro package beside {Path(__file__).name}; run it from a "
          "checkout of the repository")
    for p in (ROOT / "src", ROOT):
        sys.path.insert(0, str(p))
    from repro.compile_cache import enable_compile_cache
    from repro.vp import workloads as wl

    cache = enable_compile_cache()
    clock = CompileClock()
    print(f"# {dev.device_kind} x{n}; compile cache {cache}; lines below are "
          "bring-up observations of one run each, not benchmark numbers",
          flush=True)
    if args.chips == 4:
        four_chip_phases(clock, 4)
    else:
        kernel_phase(clock)
        snn_phases(clock, snn_networks())
        serve_phase(clock, serve_fleet())
        dense_phases(clock, wl.TABLE_III[2])
    print(f"observed total_compile_s={clock.total:.3f}", flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))


if __name__ == "__main__":
    main()
