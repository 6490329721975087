"""Run one cell of the benchmark once, on the chips of this machine.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell is an entry of ``workloads`` in ``BENCHMARK.json``; its
configuration and traffic mix are the files ``bench/configs/<config>.json``
and ``bench/traffic/<traffic>.json``, and each per-layer metric is read by
``bench/metrics/<metric>.py``.  A configuration's ``kind`` names the module
``bench/kinds/<kind>.py`` that makes its jobs from the seed, builds and
places them, fetches and compares their results with the plain references,
and computes its lower-precision control.  Nothing here names a cell, a
configuration or a kind.

To add a configuration, add files and entries and edit none: the
configuration's file, a traffic file for each of its cells, their entries
in ``BENCHMARK.json``, and, where no kind in ``bench/kinds/`` builds it, one
new kind module with the interface ``job``, ``build``, ``fetch``,
``compare``, ``LIMITS`` and ``control`` (and ``server`` and ``request``
where it is served); ``bench/kinds/snn_network.py`` is an example.

Set-up (``setup_s``, from process start to the window) builds a pool of
jobs from the seed, places them and warms their shapes from the persistent
compile cache.  The window then runs for ``--seconds``.  With ``--trace 1``
the profiler records the window and the per-layer metrics come from its
trace; with ``--trace 0`` the end-to-end metrics are printed.  After the
window every result is compared with the plain references (``check.py``),
and the numbers compared are printed beside their limits, last on standard
error and last in the result line.  The last line of standard output is
one JSON object.  Off a TPU, with fewer chips than the cell needs, or on a
device missing from ``peaks.json``, it exits non-zero and prints no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import threading  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
BENCH = ROOT / "bench"
TRACE_DIR = BENCH / "out" / "trace"   # git-ignored; removed after reading


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def load_cell(name: str, spec: dict | None = None):
    """(cell, config, traffic, metrics) of workload ``name``: the entries
    and files that ``BENCHMARK.json`` and its name lead to."""
    spec = spec or json.loads((ROOT / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise SystemExit(f"no workload {name!r} in BENCHMARK.json "
                         f"(known: {sorted(cells)})")
    cell = cells[name]
    config = json.loads((BENCH / "configs" / f"{cell['config']}.json")
                        .read_text())
    traffic = json.loads((BENCH / "traffic" / f"{cell['traffic']}.json")
                         .read_text())

    def applies(m):
        return name in m.get("workloads", [name])

    metrics = {
        "end_to_end": [m for m in spec["end_to_end"] if applies(m)],
        "per_layer": [m for m in spec["per_layer"] if applies(m)],
    }
    return cell, config, traffic, metrics


def reader(metric: str):
    """The ``read(ctx)`` function of ``bench/metrics/<metric>.py``."""
    path = BENCH / "metrics" / f"{metric}.py"
    mod_spec = importlib.util.spec_from_file_location(
        f"bench_metric_{metric.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod.read


class CompileClock:
    """Seconds JAX spends tracing, lowering and compiling, from its own
    monitoring events (a persistent-cache hit counts as its load time),
    and how many backend compiles happened."""

    EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
              "/jax/core/compile/jaxpr_to_mlir_module_duration",
              "/jax/core/compile/backend_compile_duration")

    def __init__(self):
        import jax

        self.total = 0.0
        self.compiles = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event, duration, **_):
        if event in self.EVENTS:
            self.total += duration
            self.compiles += event == self.EVENTS[2]


def device_check(chips: int):
    """The devices the cell runs on and their peaks; exits on anything but
    a TPU with at least ``chips`` chips of a kind the peaks table knows."""
    import jax

    from bench import kernels

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise SystemExit(f"needs a TPU; JAX found platform "
                         f"{devs[0].platform!r} ({devs[0].device_kind})")
    if len(devs) < chips:
        raise SystemExit(f"the cell needs {chips} chips, JAX found "
                         f"{len(devs)}")
    try:
        peak = kernels.peaks(devs[0].device_kind)
    except KeyError as e:
        raise SystemExit(str(e)) from None
    return devs[:chips], peak


def _trace_slice(seconds: float) -> None:
    import jax

    from bench import trace_reduce

    with jax.profiler.TraceAnnotation(trace_reduce.SLICE_SPAN):
        time.sleep(seconds)
    jax.profiler.stop_trace()


def run_cell(cell: dict, config: dict, traffic: dict, metrics: dict, *,
             seed: int, seconds: float, trace: bool, devices, peak: dict,
             t_start: float, clock: CompileClock | None = None) -> dict:
    """Set up, warm, measure and check one run of ``cell``; returns the
    result object.  ``devices`` are the chips the cell uses."""
    import jax

    from bench import cells, kinds, trace_reduce

    kind = kinds.find(config["kind"])
    serve = traffic["kind"] == "serve"
    if serve:
        driver = cells.ServeCell(config, traffic, seed, devices, kind=kind)
    else:
        driver = cells.SimCell(config, traffic, seed, kind=kind)
    driver.warm()
    setup_s = time.perf_counter() - t_start
    compiled_setup = (clock.total, clock.compiles) if clock else (0.0, 0)

    # a traffic file may bound the traced slice of the window: where every
    # round runs a long scan, the trace of a whole window outgrows the
    # profiler's buffer.  The slice is the span bench.traced, closed by a
    # timer while the window runs on
    slice_s = traffic.get("trace_seconds")
    stopper = threading.Thread(target=_trace_slice, args=(slice_s,))
    if trace:
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        jax.profiler.start_trace(str(TRACE_DIR))
        if slice_s:
            stopper.start()
    with jax.profiler.TraceAnnotation(trace_reduce.WINDOW_SPAN):
        done, window_s = driver.window(seconds)
    if trace:
        if slice_s:
            stopper.join()
        else:
            jax.profiler.stop_trace()
    if clock:
        log(f"setup: {setup_s:.3f} s, of it compiling {compiled_setup[0]:.3f}"
            f" s ({compiled_setup[1]} backend compiles); compiles inside "
            f"the window: {clock.compiles - compiled_setup[1]}")
    memory_peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                      for d in devices)

    dev = devices[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices()), "memory_peak_bytes": memory_peak}
    out = {"metrics": {}, "device": device}
    if trace:
        tr = trace_reduce.load(TRACE_DIR, len(devices))
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        ctx = trace_reduce.Context(
            trace=tr, rounds=None if slice_s else driver.rounds(done),
            peak=peak,
            kernel_units=driver.lanes_per_chip
            * driver.platform.n_segments * driver.platform.n_cim_slots)
        for m in metrics["per_layer"]:
            value = reader(m["name"])(ctx)
            if value is not None:
                out["metrics"][m["name"]] = {"value": value, "unit": m["unit"]}
        device["busy_s"] = tr.busy_s()
        device["window_s"] = tr.window_s()
        out["breakdown"] = {"device_ops": tr.top_ops(10),
                            "idle_gaps": tr.idle_gaps_by_span(10)}
    else:
        # a metric <quantity>.<suffix> reports the quantity under a bound
        # of its own, for cells that spread more than the others
        e2e = {"setup_s": setup_s,
               "sim_cycles_per_s": cells.sim_cycles(done) / window_s}
        for m in metrics["end_to_end"]:
            out["metrics"][m["name"]] = {
                "value": e2e[m["name"].split(".")[0]], "unit": m["unit"]}

    (correct, checks), failed = driver.verify(done)
    what = "jobs"
    if serve:
        what = (f"requests in {driver.flushes} flushes of "
                f"{', '.join(f'{t:.3f}' for t in driver.flush_s)} s")
    log(f"window: {window_s:.3f} s, {len(done)} {what}, "
        f"{driver.rounds(done)} rounds")
    errors = [d.error for d in done if d.error]
    if errors:
        log(f"{len(errors)} failed, the first with: {errors[0]}")
    for name, c in checks.items():
        log(f"check {name} = {c['value']} (limit {c['limit']})")
    return {"correct": correct, "attempted": len(done) + driver.lost,
            "failed": failed, **out, "checks": checks}


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if args.seed < 0:
        raise SystemExit("--seed must be a non-negative whole number")
    cell, config, traffic, metrics = load_cell(args.workload)
    for p in (ROOT / "src", ROOT):
        sys.path.insert(0, str(p))
    devices, peak = device_check(cell["chips"])
    import jax

    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    # cache every program, however quickly it compiles: each run is a new
    # process, and only the first run of a cell in a checkout may compile
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    clock = CompileClock()
    result = run_cell(cell, config, traffic, metrics, seed=args.seed,
                      seconds=args.seconds, trace=bool(args.trace),
                      devices=devices, peak=peak, t_start=T_START,
                      clock=clock)
    print(json.dumps(result), flush=True)


if __name__ == "__main__":
    sys.path.insert(0, str(ROOT))
    main()
