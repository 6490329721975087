"""Fault-injection conformance (repro.faults, docs/faults.md).

Three contracts, each a sweep cell:

  1. **Off means off** — ``faults=None`` and every-rate-zero configs build
     states with no fault arrays and produce bit-identical results to a
     pre-fault build (the ``obs=None`` compile-out pattern).
  2. **Seeded determinism** — a fixed seed yields bit-identical fault
     sites and results across every backend (sequential / threads / vmap,
     per-round and megaloop; shard_map in a multi-device subprocess),
     every quantum, and every segmentation (compared through the
     placement-independent readback, since raw states differ in layout).
  3. **Graceful degradation** — ``on_overflow="drop"`` completes where the
     default policy aborts, loses the *same* spikes fused vs per-round and
     across backends, and counts the loss (``lost_total`` /
     ``outbox_lost`` / ``faults.*`` metrics).
"""
import jax
import numpy as np
import pytest

from repro import faults as flt
from repro import snn
from repro.core.controller import Controller

JOB = snn.snn_inference_job((32, 24, 10), t_steps=8, rate=0.5, seed=2)

FAULT_CONFIGS = {
    "transport": flt.FaultConfig(seed=7, p_spike_drop=0.25, p_spike_dup=0.1),
    "crossbar": flt.FaultConfig(seed=7, p_stuck0=0.1, p_stuck1=0.05,
                                p_bitflip=0.05, p_row_fail=0.02,
                                p_col_fail=0.02),
    "neuron": flt.FaultConfig(seed=7, p_dead=0.2, p_thresh_drift=0.3),
    "all": flt.FaultConfig(seed=7, p_spike_drop=0.2, p_stuck0=0.1,
                           p_dead=0.1),
}

MODES = (
    ("sequential", "sequential", None),
    ("threads", "threads", None),
    ("vmap/per-round", "vmap", False),
    ("vmap/megaloop", "vmap", True),
)


def build(fc, n_segments=2, strategy="uniform", **kw):
    descs = snn.segmentation_for(snn.n_units_for(JOB.layers), strategy,
                                 **({"n_segments": n_segments}
                                    if strategy == "uniform" else {}))
    return snn.build_snn(JOB.layers, descs, JOB.raster, edges=JOB.edges,
                         n_ticks=JOB.n_ticks, faults=fc, **kw)


def run(sim, backend="vmap", fused=True, quantum=32, max_rounds=400):
    cfg, states, pending, meta = sim
    ctl = Controller(cfg, states, pending, backend=backend, quantum=quantum)
    rounds, _ = ctl.run(max_rounds=max_rounds, check_every=2, fused=fused)
    return rounds, ctl, meta


def readback(ctl, meta):
    """Placement-independent result signature: output spike counts + the
    all-layer spike total (raw states differ in layout across
    segmentations, so cross-segmentation cells compare through this)."""
    st = ctl.result_states()
    return (np.asarray(snn.output_spike_counts(st, meta)),
            int(snn.total_spikes(st)))


# ---------------------------------------------------------------------------
# 1. faults=None / all-rates-zero compile out bit-identically


def test_faults_none_is_bit_identical_to_baseline():
    base = build(None)
    for label, backend, fused in MODES:
        r0, c0, m0 = run(base, backend, fused)
        np.testing.assert_array_equal(readback(c0, m0)[0],
                                      JOB.expected_counts, err_msg=label)


def test_faults_none_adds_no_state():
    cfg, states, _, _ = build(None)
    assert cfg.faults is None
    assert "faults" not in states
    for k in ("f_and", "f_xor", "f_dead", "f_dth", "f_uid"):
        assert k not in states["cims"], k
    for k in ("spikes_dropped", "spikes_duped", "outbox_lost"):
        assert k not in states["stats"], k


def test_zero_rate_config_compiles_out_nothing_but_matches():
    """An all-zero FaultConfig keeps the arrays out too (every has_* gate
    is False) and reproduces the baseline bit-for-bit."""
    fc = flt.FaultConfig(seed=99)
    assert not (fc.has_xbar_faults or fc.has_neuron_faults
                or fc.has_transport_faults)
    cfg, states, pending, meta = build(fc)
    for k in ("f_and", "f_xor", "f_dead", "f_dth", "f_uid"):
        assert k not in states["cims"], k
    r, ctl, _ = run((cfg, states, pending, meta))
    rb, cb, mb = run(build(None))
    assert r == rb
    for x, y in zip(jax.tree.leaves(ctl.result_states()),
                    jax.tree.leaves(cb.result_states())):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


# ---------------------------------------------------------------------------
# 2. seeded determinism across backends x dispatch x quantum x segmentation


@pytest.mark.parametrize("family", sorted(FAULT_CONFIGS))
def test_fault_sites_identical_across_backends(family):
    fc = FAULT_CONFIGS[family]
    sim = build(fc)
    ref = None
    for label, backend, fused in MODES:
        rounds, ctl, meta = run(sim, backend, fused)
        got = (rounds, ctl.result_states(), ctl._pending_stacked())
        ctl.close()
        if ref is None:
            ref = got
            continue
        assert got[0] == ref[0], f"{family}/{label}: round counts"
        for x, y in zip(jax.tree.leaves(got[1:]), jax.tree.leaves(ref[1:])):
            np.testing.assert_array_equal(np.asarray(x), np.asarray(y),
                                          err_msg=f"{family}/{label}")


@pytest.mark.parametrize("family", ["transport", "all"])
def test_fault_results_quantum_invariant(family):
    fc = FAULT_CONFIGS[family]
    outs = [readback(*run(build(fc), quantum=q)[1:]) for q in (16, 32, 64)]
    for o in outs[1:]:
        np.testing.assert_array_equal(o[0], outs[0][0])
        assert o[1] == outs[0][1]


@pytest.mark.parametrize("family", sorted(FAULT_CONFIGS))
def test_fault_results_segmentation_invariant(family):
    """The fault PRNG keys on logical unit identity and tick coordinates,
    never placement: every segmentation sees the same faulted network."""
    fc = FAULT_CONFIGS[family]
    outs = [readback(*run(build(fc, n_segments=n, strategy=s))[1:])
            for n, s in ((2, "uniform"), (3, "uniform"),
                         (None, "load_oriented"))]
    for o in outs[1:]:
        np.testing.assert_array_equal(o[0], outs[0][0])
        assert o[1] == outs[0][1]


def test_different_seeds_differ():
    """Sanity: the seed actually matters (a constant-fault bug would pass
    every determinism cell above)."""
    a = readback(*run(build(flt.FaultConfig(seed=1, p_spike_drop=0.3)))[1:])
    b = readback(*run(build(flt.FaultConfig(seed=2, p_spike_drop=0.3)))[1:])
    assert a[1] != b[1] or (a[0] != b[0]).any()


def test_fault_counters_and_kernel_parity():
    """Transport runs count their injections; the Pallas kernel path
    (use_kernel=True) agrees with the jnp ref bit-for-bit under crossbar +
    neuron faults."""
    fc = FAULT_CONFIGS["transport"]
    _, ctl, meta = run(build(fc))
    m = ctl.metrics()
    assert int(m["faults.spikes_dropped"].sum()) > 0
    assert int(m["faults.spikes_duped"].sum()) > 0

    fcx = flt.FaultConfig(seed=7, p_stuck0=0.15, p_dead=0.1)
    ref = readback(*run(build(fcx))[1:])
    ker = readback(*run(build(fcx, use_kernel=True))[1:])
    np.testing.assert_array_equal(ref[0], ker[0])
    assert ref[1] == ker[1]


def test_faults_shard_map_conformance(subproc):
    """The fourth backend: a faulted shard_map run matches vmap
    bit-for-bit (transport + structural families)."""
    subproc(
        """
import jax, numpy as np
from repro import faults as flt, snn
from repro.launch.mesh import make_mesh
from repro.core.controller import Controller

mesh = make_mesh((2,), ("segment",))
job = snn.snn_inference_job((32, 24, 10), t_steps=8, rate=0.5, seed=2)
descs = snn.segmentation_for(snn.n_units_for(job.layers), "uniform",
                             n_segments=2)
for fc in (flt.FaultConfig(seed=7, p_spike_drop=0.25, p_spike_dup=0.1),
           flt.FaultConfig(seed=7, p_stuck0=0.1, p_dead=0.2)):
    cfg, states, pending, meta = snn.build_snn(
        job.layers, descs, job.raster, faults=fc)
    res = {}
    for backend, kw in (("vmap", {}), ("shard_map", {"mesh": mesh})):
        ctl = Controller(cfg, states, pending, backend=backend, quantum=32,
                         **kw)
        rounds, _ = ctl.run(max_rounds=400, check_every=2)
        res[backend] = (rounds, ctl.result_states(), ctl._pending_stacked())
    assert res["vmap"][0] == res["shard_map"][0]
    for x, y in zip(jax.tree.leaves(res["vmap"][1:]),
                    jax.tree.leaves(res["shard_map"][1:])):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
print("faulted shard_map conformance OK")
""",
        n_devices=2,
    )


# ---------------------------------------------------------------------------
# 3. graceful degradation: on_overflow="drop"


BURST = snn.snn_inference_job((8, 200, 8), t_steps=3, rate=0.9, seed=4)


def _burst(fc, **caps):
    descs = snn.segmentation_for(snn.n_units_for(BURST.layers), "uniform",
                                 n_segments=2)
    return snn.build_snn(BURST.layers, descs, BURST.raster, faults=fc,
                         **caps)


def _run_burst(fc, backend, fused, **caps):
    cfg, states, pending, meta = _burst(fc, **caps)
    ctl = Controller(cfg, states, pending, backend=backend, quantum=32)
    rounds, _ = ctl.run(max_rounds=400, check_every=2, fused=fused)
    st = ctl.result_states()
    return {
        "rounds": rounds,
        "counts": np.asarray(snn.output_spike_counts(st, meta)),
        "inbox_lost": int(np.asarray(
            ctl._pending_stacked()["lost_total"]).sum()),
        "outbox_lost": int(np.asarray(
            st["stats"].get("outbox_lost", 0)).sum()),
    }


DROP = flt.FaultConfig(on_overflow="drop")


@pytest.mark.parametrize("caps,lost_key", [
    (dict(out_cap=24), "outbox_lost"),
    (dict(in_cap=48, out_cap=640), "inbox_lost"),
])
def test_drop_policy_completes_and_counts_loss(caps, lost_key):
    """Where the default policy raises, drop completes — and every backend
    and dispatch mode loses the identical spikes and counts them."""
    with pytest.raises(RuntimeError, match="overflow"):
        _run_burst(None, "vmap", True, **caps)
    ref = _run_burst(DROP, "vmap", True, **caps)
    assert ref[lost_key] > 0
    for backend, fused in (("vmap", False), ("sequential", False),
                           ("threads", False)):
        got = _run_burst(DROP, backend, fused, **caps)
        assert got["rounds"] == ref["rounds"], backend
        np.testing.assert_array_equal(got["counts"], ref["counts"],
                                      err_msg=backend)
        assert got[lost_key] == ref[lost_key], backend


def test_drop_policy_fatal_flags_still_raise():
    """Only the channel watermarks soften under drop: late-MMIO and
    store-log overflow are program bugs and still abort."""
    from repro.core import segmentation as sg
    from repro.vp import workloads as wl

    layer = wl.Layer("flt", "t", 8, 8, 4)
    job = wl.riscv_workload(layer)
    descs = [sg.SegmentDesc(cpu=True, dram=True)]
    cfg, states, pending = sg.build(descs, programs=job["programs"],
                                    dram_words=job["dram"], store_log=2,
                                    faults=DROP)
    ctl = Controller(cfg, states, pending, backend="vmap", quantum=20_000)
    with pytest.raises(RuntimeError, match="store-log overflow"):
        ctl.run(max_rounds=100, check_every=2)


def test_generous_caps_under_drop_policy_lose_nothing():
    """With roomy caps the drop policy is inert: results match the
    unfaulted baseline exactly and the loss counters stay zero."""
    got = _run_burst(DROP, "vmap", True)
    base = _run_burst(None, "vmap", True)
    assert got["inbox_lost"] == got["outbox_lost"] == 0
    assert got["rounds"] == base["rounds"]
    np.testing.assert_array_equal(got["counts"], base["counts"])


# ---------------------------------------------------------------------------
# the degradation-sweep driver


def test_degradation_sweep_transport_monotone():
    rates = [0.0, 0.3, 0.7, 1.0]
    res = snn.degradation_sweep(JOB, rates, fault_kind="transport", seed=3)
    assert [r["rate"] for r in res] == rates
    fids = [r["fidelity"] for r in res]
    assert fids[0] == 1.0, "rate 0 must be oracle-exact"
    # nested CRN hashing makes the curve monotone up to a small tolerance
    assert all(fids[i] + 1e-9 >= fids[i + 1] - 0.02
               for i in range(len(fids) - 1)), fids
    assert res[-1]["total_spikes"] < res[0]["total_spikes"]


@pytest.mark.parametrize("kind", ["crossbar", "neuron"])
def test_degradation_sweep_structural(kind):
    res = snn.degradation_sweep(JOB, [0.0, 0.5], fault_kind=kind, seed=3)
    assert res[0]["fidelity"] == 1.0
    assert res[1]["fidelity"] < 1.0
