"""The reduction from a trace to the per-layer metrics, on a synthetic
trace with known answers."""
from __future__ import annotations

import types

import numpy as np
import pytest

from bench import kernels, trace_reduce

PEAK = {"int8_ops_per_s": 400e12, "hbm_bytes_per_s": 800e9}


def _ev(name, start, end):
    return types.SimpleNamespace(name=name, start_ns=start, end_ns=end)


def _plane(name, lines):
    return types.SimpleNamespace(
        name=name, lines=[types.SimpleNamespace(name=n, events=evs)
                          for n, evs in lines.items()])


def _trace():
    """Window [0, 1000) ns.  Chip 0: ops [100, 300) and [200, 400) overlap,
    a kernel at [600, 700); chip 1: one op [0, 500).  Host: the harness
    spans a fetch over [400, 600) and a dispatch over [0, 1000)."""
    planes = [
        _plane("/host:CPU", {"python": [
            _ev("bench.window", 0, 1000), _ev("bench.dispatch", 0, 1000),
            _ev("bench.fetch", 400, 600), _ev("other", 0, 5)]}),
        _plane("/device:TPU:0", {
            trace_reduce.OPS_LINE: [_ev("fusion.1", 100, 300),
                                    _ev("fusion.2", 200, 400),
                                    _ev("%vmap_jit_crossbar_vmm_tiles___.3 = (s32[4,8,128]) custom-call(%a)", 600, 700)],
            "XLA Modules": [_ev("jit_mega", 0, 1000)]}),
        _plane("/device:TPU:1", {
            trace_reduce.OPS_LINE: [_ev("fusion.1", 0, 500)]}),
        _plane("/device:TPU:2", {
            trace_reduce.OPS_LINE: [_ev("fusion.9", 0, 1000)]}),
    ]
    return trace_reduce.from_profile(planes, n_chips=2)


def run_reader(name, tr):
    from bench import run

    return run.reader(name)(trace_reduce.Context(
        trace=tr, rounds=1, peak=PEAK, kernel_units=4))


def test_union_merges_overlaps():
    iv = np.array([[5, 7], [0, 2], [1, 3], [7, 8]], float)
    np.testing.assert_array_equal(trace_reduce.union(iv), [[0, 3], [5, 8]])


def test_busy_and_idle_average_the_used_chips():
    tr = _trace()
    assert tr.window_s() == pytest.approx(1e-6)
    # chip 0 busy 300 + 100 ns, chip 1 500 ns; chip 2 is not the cell's
    assert tr.busy_s() == pytest.approx(450e-9)
    assert tr.idle_pct() == pytest.approx(55.0)


def test_idle_gaps_go_to_the_innermost_span():
    gaps = dict(_trace().idle_gaps_by_span(10))
    # chip 0 idles [0, 100) and [700, 1000) under dispatch, [400, 600)
    # under fetch
    assert gaps == pytest.approx({"bench.dispatch": 400e-9,
                                  "bench.fetch": 200e-9})


def test_top_ops_sum_over_chips():
    top = _trace().top_ops(2)
    assert [n for n, _ in top] == ["fusion.1", "fusion.2"]
    assert run_reader("lif_step_roofline.sim", _trace()) is None
    assert top[0][1] == pytest.approx(700e-9)


def test_per_round_and_roofline_readers():
    from bench import run

    ctx = trace_reduce.Context(trace=_trace(), rounds=9, peak=PEAK,
                               kernel_units=4)
    per_round = run.reader("device_ms_per_round.sim")(ctx)
    assert per_round == pytest.approx(1000 * 450e-9 / 9)
    share = run.reader("crossbar_vmm_roofline")(ctx)
    ops, nbytes = kernels.crossbar_vmm(4)
    want = 100 * max(ops / PEAK["int8_ops_per_s"],
                     nbytes / PEAK["hbm_bytes_per_s"]) / 100e-9
    assert share == pytest.approx(want)


def test_readers_read_nothing_from_an_empty_trace():
    from bench import run

    empty = trace_reduce.Trace(ops=[[]], spans=[], window=(0, 1000))
    ctx = trace_reduce.Context(trace=empty, rounds=3, peak=PEAK,
                               kernel_units=4)
    for name in ("device_idle_pct.sim", "device_ms_per_round.serve",
                 "crossbar_vmm_roofline", "lif_step_roofline.sim",
                 "lif_step_roofline.serve"):
        assert run.reader(name)(ctx) is None, name


def test_a_trace_without_the_window_span_is_refused():
    with pytest.raises(ValueError, match="bench.window"):
        trace_reduce.from_profile([_plane("/host:CPU", {"python": []})], 1)


def test_a_traced_slice_is_the_window():
    planes = [
        _plane("/host:CPU", {"python": [_ev("bench.traced", 100, 300),
                                        _ev("bench.fetch", 250, 290)]}),
        _plane("/device:TPU:0", {trace_reduce.OPS_LINE: [
            _ev("fusion.1", 0, 200), _ev("%jit_lif_step_tiles.1 = ()", 120, 140)]}),
    ]
    tr = trace_reduce.from_profile(planes, 1)
    assert tr.window == (100, 300)
    assert tr.idle_pct() == pytest.approx(50.0)
    # one gap, [200, 300): its middle falls in the fetch
    assert dict(tr.idle_gaps_by_span(5)) == pytest.approx(
        {"bench.fetch": 100e-9})
