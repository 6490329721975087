"""Cross-backend conformance: the canonical bit-exactness gate.

One parametrized sweep asserts that a dense CIM offload job, a
feed-forward SNN job, a recurrent SNN job, and a hybrid job (dense VMM +
spiking layers + two live RISC-V CPUs, the SNN raster injected over MMIO)
produce *bit-identical* final states, pending boxes, and round counts
across every controller backend (sequential / threads / vmap, per-round
and megaloop dispatch; shard_map rides in a multi-device subprocess) for
each segmentation strategy and quantum — and that every cell of the sweep
reproduces the workload's oracle expectation exactly.  The older per-feature equivalence
checks (tests/test_snn.py, tests/test_snn_wide.py, tests/test_megaloop.py)
stay as focused diagnostics; this sweep is the gate new execution paths
must pass.

A seeded hypothesis property sweep rides on top when the 'test' extra is
installed (CI runs it with a fixed --hypothesis-seed).

Also here: the controller-lifecycle, CPU-free fast-path, and channel-cap
watermark hardening tests — conformance of resource handling and error
behavior across execution paths.
"""
import dataclasses
import threading

import jax
import numpy as np
import pytest

from repro import snn
from repro.core import channel as ch
from repro.core import segmentation as sg
from repro.core.controller import Controller
from repro.vp import workloads as wl

try:
    from hypothesis import given, settings, strategies as st

    HAVE_HYPOTHESIS = True
except ImportError:
    HAVE_HYPOTHESIS = False


# ---------------------------------------------------------------------------
# job builders: (cfg, states, pending) + an oracle check per workload class


DENSE_LAYER = wl.Layer("conf", "t", 8, 8, 4)
FF_JOB = snn.snn_inference_job((32, 24, 10), t_steps=8, rate=0.5, seed=2)
REC_JOB = snn.snn_recurrent_job((32, 24, 8), t_steps=8, rate=0.5, seed=2)
SKIP_JOB = snn.snn_skip_job((32, 24, 16, 10), t_steps=8, rate=0.5, seed=2)
HYBRID_JOB = snn.hybrid_job((16, 12, 8), t_steps=6, rate=0.5, seed=2)


def build_dense(strategy):
    if strategy == "uniform":
        descs = sg.uniform(2, 2)
        mgrs, ids = [0, 1], {0: (0, 1), 1: (2, 3)}
    else:
        descs = sg.load_oriented()
        mgrs, ids = [1], {1: (0, 2)}
    job = wl.cim_workload(DENSE_LAYER, mgr_segments=mgrs, cim_ids_per_mgr=ids,
                          ordinals=sg.mailbox_ordinals(descs))
    cfg, states, pending = sg.build(
        descs, programs=job["programs"], dram_words=job["dram"],
        crossbars=job["crossbars"], scratch_init=job["scratch"],
        channel_latency=2000)

    def check(ctl):
        st = ctl.result_states()
        o = np.asarray(st["dram"]["data"][0][
            job["o_word"]: job["o_word"] + DENSE_LAYER.h * DENSE_LAYER.p
        ]).reshape(DENSE_LAYER.h, DENSE_LAYER.p)
        np.testing.assert_array_equal(o, job["expected"])

    return (cfg, states, pending), check


def build_snn_job(job, strategy):
    descs = snn.segmentation_for(job.layers, strategy, n_segments=4,
                                 edges=job.edges)
    cfg, states, pending, meta = snn.build_snn(
        job.layers, descs, job.raster, edges=job.edges, n_ticks=job.n_ticks)

    def check(ctl):
        st = ctl.result_states()
        np.testing.assert_array_equal(snn.output_spike_counts(st, meta),
                                      job.expected_counts)
        assert snn.total_spikes(st) == job.expected_total

    return (cfg, states, pending), check


def build_hybrid_job(strategy):
    """Live CPUs + dense units + spike units in one platform: CPU1 injects
    the raster via CIM_REG_SPIKE, reads counts back via CIM_REG_COUNTS and
    publishes them to shared DRAM while CPU0 runs the dense offload."""
    job = HYBRID_JOB
    # the dense/SNN strategy names map onto the hybrid platform shapes
    hs = {"uniform": "packed", "load_oriented": "split"}.get(strategy, strategy)
    cfg, states, pending, meta = snn.build_hybrid(job, hs,
                                                  channel_latency=2000)

    def check(ctl):
        st = ctl.result_states()
        o, counts = snn.hybrid_results(st, meta)
        np.testing.assert_array_equal(o, job.dense_expected)
        np.testing.assert_array_equal(counts, job.snn.expected_counts)
        np.testing.assert_array_equal(snn.output_spike_counts(st, meta),
                                      job.snn.expected_counts)
        assert snn.total_spikes(st) == job.snn.expected_total

    return (cfg, states, pending), check


def build_sim(kind, strategy):
    if kind == "dense":
        return build_dense(strategy)
    if kind == "snn_ff":
        return build_snn_job(FF_JOB, strategy)
    if kind == "snn_recurrent":
        return build_snn_job(REC_JOB, strategy)
    if kind == "snn_skip":
        return build_snn_job(SKIP_JOB, strategy)
    if kind == "hybrid":
        return build_hybrid_job(strategy)
    raise ValueError(kind)


MODES = (  # every in-process execution path
    ("sequential", "sequential", None),
    ("threads", "threads", None),
    ("vmap/per-round", "vmap", False),
    ("vmap/megaloop", "vmap", True),
)


def run_mode(sim, backend, quantum, fused, check_every=2, max_rounds=400,
             obs=None):
    cfg, states, pending = sim
    ctl = Controller(cfg, states, pending, backend=backend, quantum=quantum,
                     obs=obs)
    rounds, _ = ctl.run(max_rounds=max_rounds, check_every=check_every,
                        fused=fused)
    states_out = dict(ctl.result_states())
    states_out.pop("trace", None)  # the ring is telemetry, not simulation
    out = (rounds, states_out, ctl._pending_stacked())
    return out, ctl


def assert_identical(got, ref, label):
    assert got[0] == ref[0], f"{label}: round counts {got[0]} vs {ref[0]}"
    for x, y in zip(jax.tree.leaves(got[1]), jax.tree.leaves(ref[1])):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y),
                                      err_msg=f"{label}: states differ")
    for x, y in zip(jax.tree.leaves(got[2]), jax.tree.leaves(ref[2])):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y),
                                      err_msg=f"{label}: pending differs")


# ---------------------------------------------------------------------------
# the canonical sweep


SWEEP = [
    ("dense", "uniform", 1000), ("dense", "uniform", 2000),
    ("dense", "load_oriented", 1000),
    ("snn_ff", "uniform", 16), ("snn_ff", "uniform", 64),
    ("snn_ff", "load_oriented", 32),
    ("snn_recurrent", "uniform", 16), ("snn_recurrent", "uniform", 64),
    ("snn_recurrent", "load_oriented", 32),
    # forward skip connection (layer 0 -> output, dst > src + 1): acyclic,
    # drains without a horizon, oracle-exact on every backend
    ("snn_skip", "uniform", 32), ("snn_skip", "load_oriented", 32),
    # hybrid: dense VMM + SNN + two live CPUs in one platform, raster
    # CPU-injected — ≥2 segmentations x ≥2 quanta (the PR-5 gate)
    ("hybrid", "split", 400), ("hybrid", "split", 1000),
    ("hybrid", "packed", 400), ("hybrid", "packed", 1000),
    ("hybrid", "auto", 700),
]


@pytest.mark.parametrize("kind,strategy,quantum", SWEEP)
def test_conformance_sweep(kind, strategy, quantum):
    sim, check = build_sim(kind, strategy)
    ref = None
    for label, backend, fused in MODES:
        got, ctl = run_mode(sim, backend, quantum, fused)
        check(ctl)  # every cell reproduces the oracle expectation exactly
        ctl.close()
        if ref is None:
            ref = got
        else:
            assert_identical(got, ref, f"{kind}/{strategy}/q{quantum}/{label}")


def test_conformance_shard_map(subproc):
    """The fourth backend: shard_map (one device per segment) must match
    vmap bit-for-bit on all three workload classes."""
    subproc(
        """
import jax, numpy as np
from repro import snn
from repro.launch.mesh import make_mesh
from repro.core import segmentation as sg
from repro.core.controller import Controller
from repro.vp import workloads as wl

mesh = make_mesh((2,), ("segment",))

def both(cfg, states, pending, quantum):
    res = {}
    for backend, kw in (("vmap", {}), ("shard_map", {"mesh": mesh})):
        ctl = Controller(cfg, states, pending, backend=backend,
                         quantum=quantum, **kw)
        rounds, _ = ctl.run(max_rounds=400, check_every=2)
        res[backend] = (rounds, ctl.result_states(), ctl._pending_stacked())
    assert res["vmap"][0] == res["shard_map"][0]
    for x, y in zip(jax.tree.leaves(res["vmap"][1:]),
                    jax.tree.leaves(res["shard_map"][1:])):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))

# dense
layer = wl.Layer("conf", "t", 8, 8, 4)
descs = sg.uniform(2, 2)
job = wl.cim_workload(layer, mgr_segments=[0, 1],
                      cim_ids_per_mgr={0: (0, 1), 1: (2, 3)},
                      ordinals=sg.mailbox_ordinals(descs))
cfg, states, pending = sg.build(descs, programs=job["programs"],
                                dram_words=job["dram"],
                                crossbars=job["crossbars"],
                                scratch_init=job["scratch"],
                                channel_latency=2000)
both(cfg, states, pending, 1000)

# feed-forward SNN
ff = snn.snn_inference_job((24, 16, 8), t_steps=6, rate=0.5, seed=2)
descs = snn.segmentation_for(ff.layers, "uniform", n_segments=2)
cfg, states, pending, _ = snn.build_snn(ff.layers, descs, ff.raster)
both(cfg, states, pending, 32)

# recurrent SNN
rec = snn.snn_recurrent_job((24, 16, 8), t_steps=6, rate=0.5, seed=2)
descs = snn.segmentation_for(rec.layers, "uniform", n_segments=2,
                             edges=rec.edges)
cfg, states, pending, _ = snn.build_snn(rec.layers, descs, rec.raster,
                                        edges=rec.edges, n_ticks=rec.n_ticks)
both(cfg, states, pending, 32)

# hybrid: dense + SNN + two live CPUs (packed = 2 segments = 2 devices)
hy = snn.hybrid_job((16, 12, 8), t_steps=6, rate=0.5, seed=2)
cfg, states, pending, _ = snn.build_hybrid(hy, "packed",
                                           channel_latency=2000)
both(cfg, states, pending, 400)
print("shard_map conformance OK")
""",
        n_devices=2,
    )


if HAVE_HYPOTHESIS:

    @settings(max_examples=10, deadline=None)
    @given(
        kind=st.sampled_from(["dense", "snn_ff", "snn_recurrent", "hybrid"]),
        strategy=st.sampled_from(["uniform", "load_oriented"]),
        backend_fused=st.sampled_from(
            [("threads", None), ("vmap", False), ("vmap", True)]),
        q_index=st.integers(min_value=0, max_value=2),
        check_every=st.integers(min_value=1, max_value=4),
    )
    def test_conformance_property(kind, strategy, backend_fused, q_index,
                                  check_every):
        """Random (job, segmentation, backend, quantum, check cadence):
        always bit-identical to the sequential reference at the same
        cadence, and always oracle-exact."""
        quantum = {"dense": (500, 1000, 2000),
                   "hybrid": (400, 700, 1000)}.get(kind, (16, 32, 64))[q_index]
        sim, check = build_sim(kind, strategy)
        ref, ctl = run_mode(sim, "sequential", quantum, None,
                            check_every=check_every)
        check(ctl)
        backend, fused = backend_fused
        got, ctl = run_mode(sim, backend, quantum, fused,
                            check_every=check_every)
        check(ctl)
        ctl.close()
        assert_identical(got, ref, f"{kind}/{strategy}/q{quantum}/{backend}")


# ---------------------------------------------------------------------------
# telemetry conformance: tracing must be invisible to the simulation


OBS_SWEEP = [  # one representative cell per workload class
    ("dense", "uniform", 1000),
    ("snn_ff", "uniform", 32),
    ("snn_recurrent", "uniform", 32),
    ("hybrid", "packed", 400),
]


@pytest.mark.parametrize("kind,strategy,quantum", OBS_SWEEP)
def test_tracing_is_bit_invisible(kind, strategy, quantum):
    """obs=TraceConfig() must not change results, rounds_run, sim_time, or
    pending boxes on any in-process backend × dispatch mode — and the
    traced run still reproduces its oracle exactly."""
    from repro.obs import TraceConfig

    sim, check = build_sim(kind, strategy)
    for label, backend, fused in MODES:
        plain, pctl = run_mode(sim, backend, quantum, fused)
        pctl.close()
        traced, tctl = run_mode(sim, backend, quantum, fused,
                                obs=TraceConfig())
        check(tctl)
        assert len(tctl.trace_events()), f"{label}: traced run saw no events"
        tctl.close()
        assert_identical(traced, plain,
                         f"{kind}/{strategy}/q{quantum}/{label}/traced")


def test_one_host_sync_per_fused_dispatch_with_telemetry(monkeypatch):
    """The megaloop contract with telemetry ON: each fused dispatch performs
    exactly one host fetch (the (rounds, done, over, ring) tuple) — draining
    the trace rings must not add device syncs."""
    import repro.core.controller as ctl_mod
    from repro.obs import TraceConfig

    real, calls = ctl_mod._HOST_FETCH, []

    def counting_fetch(tree):
        calls.append(1)
        return real(tree)

    monkeypatch.setattr(ctl_mod, "_HOST_FETCH", counting_fetch)
    sim, check = build_sim("snn_ff", "uniform")
    ctl = Controller(*sim, backend="vmap", quantum=32, obs=TraceConfig())
    ctl.run(max_rounds=400, check_every=2, fused=True,
            rounds_per_dispatch=64)
    check(ctl)
    assert ctl.dispatches >= 1
    assert len(calls) == ctl.dispatches == ctl.dispatch_syncs, \
        "fused dispatches must stay one-host-sync each with tracing on"


def test_stats_shim_matches_across_backends():
    """stats() (the back-compat shim over obs/metrics.py) returns the same
    dict on every backend — the coarse counters are part of the conformance
    surface, not just the raw states."""
    sim, _ = build_sim("snn_ff", "uniform")
    ref = None
    for label, backend, fused in MODES:
        _, ctl = run_mode(sim, backend, 32, fused)
        st = ctl.stats()
        ctl.close()
        assert set(st) == {"instructions", "messages", "txn_histogram",
                           "cache", "dram", "cim_ops", "snn"}
        if ref is None:
            ref = st
        else:
            for x, y in zip(jax.tree.leaves(st), jax.tree.leaves(ref)):
                np.testing.assert_array_equal(
                    np.asarray(x), np.asarray(y),
                    err_msg=f"{label}: stats() differs")


def test_conformance_shard_map_traced(subproc):
    """Telemetry on the fourth backend: a traced shard_map run must match a
    traced vmap run bit-for-bit (states minus the ring AND the drained
    event stream), and both must match the untraced reference."""
    subproc(
        """
import jax, numpy as np
from repro import snn
from repro.launch.mesh import make_mesh
from repro.core.controller import Controller
from repro.obs import TraceConfig
from repro.obs import trace as tr

mesh = make_mesh((2,), ("segment",))
ff = snn.snn_inference_job((24, 16, 8), t_steps=6, rate=0.5, seed=2)
descs = snn.segmentation_for(ff.layers, "uniform", n_segments=2)
cfg, states, pending, _ = snn.build_snn(ff.layers, descs, ff.raster)

res = {}
for name, backend, kw, obs in (
        ("vmap", "vmap", {}, None),
        ("vmap+obs", "vmap", {}, TraceConfig()),
        ("shard+obs", "shard_map", {"mesh": mesh}, TraceConfig())):
    ctl = Controller(cfg, states, pending, backend=backend, quantum=32,
                     obs=obs, **kw)
    rounds, _ = ctl.run(max_rounds=400, check_every=2)
    st = dict(ctl.result_states()); st.pop("trace", None)
    ev = np.sort(ctl.trace_events(), order=list(tr.FIELDS))
    res[name] = (rounds, st, ctl._pending_stacked(), ev)

for name in ("vmap+obs", "shard+obs"):
    assert res[name][0] == res["vmap"][0], name
    for x, y in zip(jax.tree.leaves(res[name][1:3]),
                    jax.tree.leaves(res["vmap"][1:3])):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y),
                                      err_msg=name)
np.testing.assert_array_equal(res["shard+obs"][3], res["vmap+obs"][3])
assert len(res["shard+obs"][3]) > 0
print("traced shard_map conformance OK")
""",
        n_devices=2,
    )


# ---------------------------------------------------------------------------
# threads backend lifecycle


def test_threads_lifecycle_close_is_idempotent_and_leakless():
    sim, check = build_sim("snn_ff", "uniform")
    before = {t for t in threading.enumerate()}
    ctl = Controller(*sim, backend="threads", quantum=32)
    ctl.run(max_rounds=300, check_every=2)
    check(ctl)
    assert any(t.name.startswith("vp-seg") for t in threading.enumerate()), \
        "the persistent pool must exist while the controller is open"
    ctl.close()
    ctl.close()  # idempotent
    leaked = [t for t in threading.enumerate()
              if t not in before and t.name.startswith("vp-seg")]
    assert not leaked, f"threads backend leaked workers: {leaked}"
    # results stay readable after close; running again does not
    check(ctl)
    with pytest.raises(RuntimeError, match="closed"):
        ctl.run(max_rounds=1)
    with pytest.raises(RuntimeError, match="closed"):
        ctl.round()


def test_close_applies_to_every_backend():
    sim, _ = build_sim("snn_ff", "uniform")
    ctl = Controller(*sim, backend="vmap", quantum=32)
    ctl.close()
    with pytest.raises(RuntimeError, match="closed"):
        ctl.run(max_rounds=1)


# ---------------------------------------------------------------------------
# CPU-free fast path: hand-injected MMIO must fall back, bit-for-bit


def test_cpu_free_fast_path_and_mmio_fallback():
    job = snn.snn_inference_job((16, 12, 8), t_steps=6, rate=0.6, seed=5)
    descs = snn.segmentation_for(2, "uniform", n_segments=2)
    cfg, states, pending, meta = snn.build_snn(job.layers, descs, job.raster)
    assert not cfg.has_cpu, "an SNN-only build takes the CPU-free fast path"
    # clean build: the fast path is kept
    clean = Controller(cfg, states, pending, backend="vmap", quantum=32)
    assert not clean.cfg.has_cpu
    clean.run(max_rounds=300, check_every=2)
    np.testing.assert_array_equal(
        snn.output_spike_counts(clean.result_states(), meta),
        job.expected_counts)

    # hand-inject an MMIO message (scratch DMA) into the pending box: the
    # fast path would silently ignore it, so the controller must detect it
    # and fall back to the full step
    injected = dict(pending)
    for f, v in (("kind", ch.MSG_W_SCRATCH), ("addr", 7), ("data", 1234),
                 ("t_avail", 0)):
        injected[f] = injected[f].at[0, -1].set(v)
    injected["valid"] = injected["valid"].at[0, -1].set(True)

    fall = Controller(cfg, states, injected, backend="vmap", quantum=32)
    assert fall.cfg.has_cpu, "hand-injected MMIO must force the full path"
    fall.run(max_rounds=300, check_every=2)

    # explicit full-path build with the same injection: bit-for-bit equal
    full_cfg = dataclasses.replace(cfg, has_cpu=True)
    full = Controller(full_cfg, states, injected, backend="vmap", quantum=32)
    full.run(max_rounds=300, check_every=2)
    assert fall.rounds_run == full.rounds_run
    for a, b in zip(jax.tree.leaves(fall.result_states()),
                    jax.tree.leaves(full.result_states())):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # the injected scratch word actually landed (the full path ran) and the
    # spike results still match the oracle
    st = fall.result_states()
    assert int(np.asarray(st["scratch"][0, 7])) == 1234
    np.testing.assert_array_equal(snn.output_spike_counts(st, meta),
                                  job.expected_counts)


# ---------------------------------------------------------------------------
# undersized channel caps raise the watermark RuntimeError, loudly


BURST_SIZES = (8, 200, 8)  # 200-neuron middle layer -> 200-spike AER bursts


def _burst_sim(**caps):
    job = snn.snn_inference_job(BURST_SIZES, t_steps=3, rate=0.9, seed=4)
    descs = snn.segmentation_for(len(job.layers), "uniform", n_segments=2)
    return snn.build_snn(job.layers, descs, job.raster, **caps)[:3]


@pytest.mark.parametrize("fused", [False, True])
def test_undersized_out_cap_raises_actionable_error(fused):
    cfg, states, pending = _burst_sim(out_cap=64)
    ctl = Controller(cfg, states, pending, backend="vmap", quantum=32)
    with pytest.raises(RuntimeError, match=r"outbox overflow.*out_cap") as ei:
        ctl.run(max_rounds=300, check_every=2, fused=fused)
    assert "raise out_cap" in str(ei.value)
    # remediation hint: the watermark records demand, so the message names
    # the smallest out_cap that would have absorbed the burst
    assert "smallest sufficient out_cap=" in str(ei.value)
    peak = int(np.asarray(ctl.result_states()["stats"]["outbox_peak"]).max())
    assert f"smallest sufficient out_cap={peak}" in str(ei.value)


@pytest.mark.parametrize("fused", [False, True])
def test_undersized_in_cap_raises_actionable_error(fused):
    # in_cap holds the tiny raster (builder check) but not the 200-spike
    # runtime burst landing in the consumer segment's inbox
    cfg, states, pending = _burst_sim(in_cap=80)
    ctl = Controller(cfg, states, pending, backend="vmap", quantum=32)
    with pytest.raises(RuntimeError, match=r"inbox overflow.*in_cap") as ei:
        ctl.run(max_rounds=300, check_every=2, fused=fused)
    assert "raise in_cap" in str(ei.value)
    peak = int(np.asarray(ctl._pending_stacked()["max_count"]).max())
    assert f"smallest sufficient in_cap={peak}" in str(ei.value)


@pytest.mark.parametrize("fused", [False, True])
def test_undersized_store_log_raises_actionable_error(fused):
    # a RISC-V VMM writing its whole output matrix in one quantum needs
    # h*p store-log entries; store_log=2 must trip the sticky watermark
    layer = wl.Layer("conf", "t", 8, 8, 4)
    job = wl.riscv_workload(layer)
    descs = [sg.SegmentDesc(cpu=True, dram=True)]
    cfg, states, pending = sg.build(descs, programs=job["programs"],
                                    dram_words=job["dram"], store_log=2)
    ctl = Controller(cfg, states, pending, backend="vmap", quantum=20_000)
    with pytest.raises(RuntimeError, match=r"store-log overflow.*store_log") as ei:
        ctl.run(max_rounds=100, check_every=2, fused=fused)
    assert "raise store_log" in str(ei.value)
    assert "smallest sufficient store_log=" in str(ei.value)


def test_error_messages_identical_fused_and_per_round():
    msgs = {}
    for fused in (False, True):
        cfg, states, pending = _burst_sim(out_cap=64)
        ctl = Controller(cfg, states, pending, backend="vmap", quantum=32)
        with pytest.raises(RuntimeError) as ei:
            ctl.run(max_rounds=300, check_every=2, fused=fused)
        msgs[fused] = str(ei.value)
    assert msgs[False] == msgs[True]
