"""Per-phase device time (``bench/phases.py``) and its readers, on a
synthetic trace of one megaloop with known answers."""
from __future__ import annotations

import numpy as np
import pytest

from bench import phases, trace_reduce

PEAK = {"int8_ops_per_s": 400e12, "hbm_bytes_per_s": 800e9}

# instruction -> (op_name, interval in ns); the megaloop's while wraps the
# round, and the slot scan's own while wraps its two steps
OPS = {
    "while.1": ("jit(mega)/while", (0, 900)),
    "fusion.1": ("jit(mega)/while/body/vmap(vp.inbox)/add", (10, 110)),
    "while.2": ("jit(mega)/while/body/vmap(vp.slot_scan)/while", (120, 520)),
    "fusion.2": ("jit(mega)/while/body/vmap(vp.slot_scan)/while/body/add",
                 (130, 330)),
    "fusion.3": ("jit(mega)/while/body/vmap(vp.slot_scan)/while/body/mul",
                 (340, 500)),
    "fusion.4": ("jit(mega)/while/body/vp.route/gather", (530, 630)),
    "fusion.5": ("jit(mega)/while/body/vp.merge/select_n", (640, 690)),
    "custom-call.6": ("jit(mega)/while/body/vmap(vp.lif_tick)/pallas_call",
                      (700, 800)),
    "fusion.7": ("jit(mega)/while/body/vmap(vp.aer_out)/scatter", (800, 850)),
    "while.8": ("jit(mega)/while/body/vmap(vp.store_replay)/while",
                (860, 880)),
    "copy.1": ("jit(mega)/while", None),
}


def _op(name):
    return "while" if name.startswith("while") else "fusion"


def _hlo(ops=OPS):
    """The module's text: the megaloop's while in the entry computation,
    every other instruction in its body."""
    line = lambda n, o: (f'  %{n} = s32[4]{{0}} {_op(n)}(%p), kind=kLoop, '
                         f'metadata={{op_name="{o}"}}, backend_config={{"a":1}}')
    body = [line(n, o) for n, (o, _) in ops.items() if n != "while.1"]
    entry = [line("while.1", ops["while.1"][0])] if "while.1" in ops else []
    return "\n".join(
        ["%body (p: s32[4]) -> s32[4] {", *body, "}",
         "ENTRY %main (p: s32[4]) -> s32[4] {", *entry, "}"])


HLO = _hlo()
# seconds of self time by phase in the window [0, 1000)
WANT = {"vp.inbox": 100e-9, "vp.slot_scan": 400e-9, "vp.route": 100e-9,
        "vp.merge": 50e-9, "vp.lif_tick": 100e-9, "vp.aer_out": 50e-9,
        "vp.store_replay": 20e-9, None: 70e-9}


def _trace(window=(0, 1000)):
    # the trace writes each operand with its shape, the module's text without
    ops = [(f"%{n} = s32[4]{{0}} {_op(n)}(s32[4]{{0}} %p), kind=kLoop", *iv)
           for n, (_, iv) in OPS.items() if iv]
    # an operation inside the megaloop's loop that is no instruction of
    # its text (the map from trace to module misses it)
    ops.append(("%fusion.99 = s32[4]{0} fusion(s32[4]{0} %p)", 885, 895))
    # a jit_copy's operation of the same name as one of the megaloop's
    # instructions, but another text: not the megaloop's
    ops.append(("%copy.1 = s32[4]{0} copy(s32[4]{0} %q)", 950, 980))
    # outside the window
    ops.append(("%fusion.1 = s32[4]{0} fusion(s32[4]{0} %p), kind=kLoop",
                1200, 1300))
    return trace_reduce.Trace(ops=[ops], spans=[], window=window)


@pytest.fixture
def program_hlo(monkeypatch):
    calls = []

    def texts():
        calls.append(1)
        return [HLO]

    monkeypatch.setattr(phases, "_hlo_texts", texts)
    return calls


def _ctx(tr=None, rounds=2):
    return trace_reduce.Context(trace=tr or _trace(), rounds=rounds,
                                peak=PEAK, kernel_units=4)


def test_trace_names_find_their_instruction():
    """As a v5e trace and the compiled text write one operation."""
    line = ('  %copy-start.583 = (s32[4,24576]{1,0:T(4,128)S(1)}, '
            's32[4,24576]{1,0:T(4,128)}, u32[]{:S(2)}) '
            'copy-start(%pending__data__.1), '
            'metadata={op_name="jit(mega)/while/body/vp.merge/copy"}')
    event = ('%copy-start.583 = (s32[4,24576]{1,0:T(4,128)S(1)}, '
             's32[4,24576]{1,0:T(4,128)}, u32[]{:S(2)}) copy-start('
             's32[4,24576]{1,0:T(4,128)} %pending__data__.1)')
    table = {n: [e] for n, e in phases.phase_table(line).items()}
    assert phases._match(event, table, {})[0] == "vp.merge"
    # another module's instruction of the same name
    other = ('%copy-start.583 = (s32[4,24576]{1,0:T(4,128)S(1)}, '
             's32[4,24576]{1,0:T(4,128)}, u32[]{:S(2)}) copy-start('
             's32[4,24576]{1,0:T(4,128)} %args_0_.1)')
    assert phases._match(other, table, {}) is None


def test_self_time_takes_out_nested_operations():
    iv = np.array([[0, 10], [2, 5], [3, 4], [6, 8]], float)
    own = phases.self_times(iv[:, 0], iv[:, 1])
    np.testing.assert_array_equal(own, [5, 2, 1, 2])
    # overlapping neighbours share their overlap once
    np.testing.assert_array_equal(
        phases.self_times([100, 200], [300, 400]), [100, 200])


def test_self_times_sum_to_the_busy_union():
    rng = np.random.default_rng(0)
    starts = np.sort(rng.uniform(0, 1e6, 2000))
    ends = starts + rng.exponential(2e3, 2000)
    union = trace_reduce.union(np.stack([starts, ends], 1))
    assert phases.self_times(starts, ends).sum() == pytest.approx(
        np.diff(union, axis=1).sum())


def test_phase_self_time_of_a_nested_while(program_hlo):
    ctx = _ctx()
    got = phases.phases(ctx)
    assert got.by_phase == pytest.approx(WANT)
    assert got.outside == pytest.approx(40e-9)
    assert got.whole == {"vp.slot_scan": 1, "vp.store_replay": 1}
    assert phases.phase_self_s(ctx, "vp.slot_scan") == pytest.approx(400e-9)
    assert phases.phase_self_s(ctx, "vp.cim_op") == 0.0
    # the program's text is compiled once per trace
    phases.phase_self_s(ctx, "vp.route")
    assert len(program_hlo) == 1


@pytest.mark.parametrize("metric, want", [
    ("inbox_apply_ms_per_round.sim", 1000 * 100e-9 / 2),
    ("route_ms_per_round.sim", 1000 * 100e-9 / 2),
    ("merge_ms_per_round.sim", 1000 * 50e-9 / 2),
    ("lif_tick_ms_per_round.sim", 1000 * 100e-9 / 2),
    ("aer_out_ms_per_round.sim", 1000 * 50e-9 / 2),
    ("slot_scan_busy_pct.sim", 100 * 400 / 930),
    ("store_replay_busy_pct.sim", 100 * 20 / 930),
    ("phase_coverage_pct.sim", 100 * 820 / 930),
])
def test_new_readers(program_hlo, metric, want):
    from bench import run

    assert run.reader(metric)(_ctx()) == pytest.approx(want)


def test_phases_and_round_time_add_up(program_hlo):
    from bench import run

    ctx = _ctx()
    got = phases.phases(ctx)
    per_round = run.reader("device_ms_per_round.sim")(ctx)
    parts = 1000 * (got.megaloop_s() + got.outside) / ctx.rounds
    assert parts == pytest.approx(per_round)


NEW = ("inbox_apply_ms_per_round.sim", "route_ms_per_round.sim",
       "merge_ms_per_round.sim", "lif_tick_ms_per_round.sim",
       "aer_out_ms_per_round.sim", "slot_scan_busy_pct.sim",
       "store_replay_busy_pct.sim", "phase_coverage_pct.sim")


@pytest.mark.parametrize("metric", NEW)
def test_readers_read_nothing_without_phase_names(monkeypatch, metric):
    """A program from before the phase names, a trace with no device
    operations, or a traced slice without rounds: nothing, no raise."""
    from bench import run

    read = run.reader(metric)
    monkeypatch.setattr(phases, "_hlo_texts", lambda: None)
    assert read(_ctx()) is None
    monkeypatch.setattr(phases, "_hlo_texts", lambda: [HLO])
    empty = trace_reduce.Trace(ops=[[]], spans=[], window=(0, 1000))
    assert read(_ctx(empty)) is None
    if metric.endswith("_ms_per_round.sim"):
        assert read(_ctx(rounds=None)) is None


def test_readers_without_the_program_module(monkeypatch):
    """Where ``dispatched_megaloop_hlo`` does not exist the import fails
    and every reading is None."""
    import repro.core.controller as ctl

    monkeypatch.delattr(ctl, "dispatched_megaloop_hlo")
    assert phases.phases(_ctx()) is None


def test_program_names_map_a_real_megaloop():
    """A tiny SNN job through the program: its dispatched megaloop's text
    gives each of the round's operations a phase."""
    from repro import snn
    from repro.core.controller import Controller

    job = snn.snn_inference_job((16, 12, 8), t_steps=4, rate=0.5, seed=3)
    descs = snn.segmentation_for(job.layers, "uniform", n_segments=2)
    cfg, states, pending, _ = snn.build_snn(job.layers, descs, job.raster)
    Controller(cfg, states, pending, quantum=32).run(max_rounds=8)
    table = phases._table()
    found = {e.phase for entries in table.values() for e in entries}
    assert {"vp.inbox", "vp.lif_tick", "vp.aer_out", "vp.route",
            "vp.merge", "vp.check"} <= found
    # an operation named as the trace names it finds its phase
    text = next(e.text for entries in table.values() for e in entries
                if e.phase == "vp.aer_out")
    tr = trace_reduce.Trace(ops=[[(text, 0, 10)]], spans=[], window=(0, 10))
    got = phases.reduce_phases(tr, table)
    assert got.by_phase == pytest.approx({"vp.aer_out": 10e-9})


def test_two_megaloops_with_one_instruction_name():
    """Each module's operation finds its own module's instruction."""
    a = '  %fusion.1 = s32[4]{0} fusion(%p), metadata={op_name="a/vp.route/x"}'
    b = '  %fusion.1 = s32[8]{0} fusion(%q), metadata={op_name="b/vp.merge/x"}'
    table = {}
    for text in (a, b):
        for n, e in phases.phase_table(text).items():
            table.setdefault(n, []).append(e)
    seen = {}
    assert phases._match("%fusion.1 = s32[8]{0} fusion(s32[8]{0} %q)",
                         table, seen)[0] == "vp.merge"
    assert phases._match("%fusion.1 = s32[4]{0} fusion(s32[4]{0} %p)",
                         table, seen)[0] == "vp.route"


def test_coverage_falls_when_operations_go_unmatched(program_hlo,
                                                     monkeypatch):
    """Where the map from trace to module fails for the round's operations,
    coverage drops instead of the phases reading faster."""
    monkeypatch.setattr(phases, "_hlo_texts",
                        lambda: [_hlo({"while.1": OPS["while.1"],
                                       "fusion.1": OPS["fusion.1"]})])
    ctx = _ctx()
    assert phases.phase_self_s(ctx, "vp.inbox") == pytest.approx(100e-9)
    assert phases.coverage_pct(ctx) == pytest.approx(100 * 100 / 930)


def test_dense_shares_need_a_whole_store_replay(program_hlo):
    """A slice that ends before a store-log replay has finished reads as
    not measured."""
    ctx = _ctx(_trace(window=(0, 870)))
    assert phases.busy_pct(ctx, "vp.slot_scan") is None
    assert phases.busy_pct(ctx, "vp.store_replay") is None
    assert phases.coverage_pct(ctx) is not None


def test_phase_of_takes_the_innermost_scope():
    assert phases.phase_of("jit(mega)/while/body/vmap(vp.inbox)/add") \
        == "vp.inbox"
    assert phases.phase_of(
        "jit(mega)/while/body/vmap(vp.inbox)/vp.telemetry/reduce_sum") \
        == "vp.telemetry"
    assert phases.phase_of("jit(mega)/while") is None
    assert phases.phase_of("jit(f)/avp.inbox/add") is None
    assert phases.phase_of("jit(f)/vp.controller.init/add") is None


def test_phase_table_reads_the_compiled_text():
    text = "\n".join([
        "ENTRY %main (p: s32[4]) -> (s32[]) {",
        '  %fusion.3 = s32[4]{0} fusion(s32[4]{0} %p), kind=kLoop, '
        'calls=%fc, metadata={op_name="jit(mega)/while/body/vmap(vp.route)'
        '/gather" source_file="x.py" source_line=3}, backend_config={"a":1}',
        "  ROOT %while.2 = (s32[]) while((s32[]) %t), condition=%c, "
        'body=%b, metadata={op_name="jit(mega)/while"}',
        "}"])
    table = phases.phase_table(text)
    assert table["fusion.3"] == (
        "vp.route", '%fusion.3 = s32[4]{0} fusion(s32[4]{0} %p), '
        'kind=kLoop, calls=%fc, backend_config={"a":1}')
    assert table["while.2"].phase is None
    assert table["while.2"].text.startswith("%while.2 = (s32[]) while(")


def test_instructions_without_op_name_inherit_a_phase():
    """A fusion XLA built without metadata takes its fused computation's
    phase; an instruction in a loop body without metadata takes the phase
    of the while that runs the body."""
    text = "\n".join([
        "%fc (p: s32[4]) -> s32[4] {",
        "  %p = s32[4]{0} parameter(0)",
        '  ROOT %scatter.1 = s32[4]{0} scatter(%p), '
        'metadata={op_name="jit(mega)/while/body/vmap(vp.inbox)/scatter"}',
        "}",
        "%body (t: (s32[])) -> (s32[]) {",
        "  %t = (s32[]) parameter(0)",
        "  ROOT %compare_reduce_fusion.1 = pred[] fusion(), kind=kLoop, "
        "calls=%fc2",
        "}",
        "ENTRY %main (a: s32[4]) -> s32[4] {",
        "  %a = s32[4]{0} parameter(0)",
        "  %fusion.7 = s32[4]{0} fusion(%a), kind=kCustom, calls=%fc",
        "  %while.3 = (s32[]) while(%t0), condition=%cond, body=%body, "
        'metadata={op_name="jit(mega)/while/body/vmap(vp.slot_scan)/while"}',
        "  ROOT %copy.2 = s32[4]{0} copy(%a), "
        'metadata={op_name="jit(mega)/while"}',
        "}"])
    table = phases.phase_table(text)
    assert table["fusion.7"].phase == "vp.inbox"
    assert table["compare_reduce_fusion.1"].phase == "vp.slot_scan"
    assert table["copy.2"].phase is None


def test_megaloop_hlo_script_prints_the_dispatched_program(monkeypatch,
                                                           capsys):
    """``scripts/megaloop_hlo.py`` runs one job of a (tiny) cell and prints
    its megaloop without metadata."""
    import importlib.util
    import sys

    from bench import run
    from bench.tests.helpers import tiny_cell

    path = run.ROOT / "scripts" / "megaloop_hlo.py"
    spec = importlib.util.spec_from_file_location("megaloop_hlo", path)
    script = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(script)
    tiny = tiny_cell("dense-riscv-googlenet-conv2")
    monkeypatch.setattr(run, "load_cell", lambda name: tiny)
    monkeypatch.setattr(sys, "argv", [str(path), "--workload",
                                      "dense-riscv-googlenet-conv2"])
    monkeypatch.setattr(sys, "path", list(sys.path))
    script.main()
    text = capsys.readouterr().out
    assert "ENTRY" in text and " while(" in text
    assert "metadata=" not in text and "vp." not in text
