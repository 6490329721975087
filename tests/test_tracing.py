"""The profiler-facing names (obs/profile.py): every phase of a megaloop
round carries its ``jax.named_scope`` into the HLO metadata of the
``vmap`` megaloop, the job megaloop and the ``shard_map`` round, and
``Controller`` marks its host work with nested ``vp.*`` spans in a
``jax.profiler`` trace."""
import re
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest

from repro import snn
from repro.core import controller as ctl_mod
from repro.core import segmentation as sg
from repro.core.controller import Controller
from repro.obs import TraceConfig
from repro.obs import profile
from repro.vp import workloads as wl

ROUND = {"vp.inbox", "vp.slot_scan", "vp.stats", "vp.limits", "vp.route",
         "vp.merge", "vp.check"}
EXPECT = {
    "dense": ROUND | {"vp.store_replay", "vp.cim_op"},
    "snn": ROUND | {"vp.lif_tick", "vp.aer_out"},
    "hybrid-obs": set(profile.PHASES) - {"vp.freeze"},
}


def build(kind):
    """(cfg, states, pending, quantum, obs) of a tiny platform."""
    if kind == "dense":
        descs = sg.uniform(2, 2)
        job = wl.cim_workload(wl.Layer("tr", "t", 8, 8, 4), mgr_segments=[0, 1],
                              cim_ids_per_mgr={0: (0, 1), 1: (2, 3)},
                              ordinals=sg.mailbox_ordinals(descs))
        cfg, states, pending = sg.build(
            descs, programs=job["programs"], dram_words=job["dram"],
            crossbars=job["crossbars"], scratch_init=job["scratch"],
            channel_latency=2000)
        return cfg, states, pending, 256, None
    if kind == "snn":
        job = snn.snn_inference_job((16, 12, 8), t_steps=4, rate=0.5, seed=3)
        descs = snn.segmentation_for(job.layers, "uniform", n_segments=2)
        cfg, states, pending, _ = snn.build_snn(job.layers, descs, job.raster)
        return cfg, states, pending, 32, None
    job = snn.hybrid_job((16, 12, 8), t_steps=4, rate=0.5, seed=2)
    cfg, states, pending, _ = snn.build_hybrid(job, "packed",
                                               channel_latency=2000)
    return cfg, states, pending, 400, TraceConfig()


PHASE = re.compile(r"(?<![\w.])vp\.[a-z_]+(?![\w.])")


def innermost(path: str):
    """The innermost ``vp.*`` scope on an op_name path, or None."""
    found = PHASE.findall(path)
    return found[-1] if found else None


def scopes(lowered) -> set:
    """The phases named on the op_name paths of a lowered module."""
    text = lowered.as_text(debug_info=True)
    return {p for loc in re.findall(r'loc\("([^"]*)"', text)
            if (p := innermost(loc))}


@pytest.fixture(scope="module", params=sorted(EXPECT))
def platform(request):
    return request.param, build(request.param)


def test_vmap_megaloop_names_every_phase(platform):
    kind, (cfg, states, pending, quantum, obs) = platform
    ctl = Controller(cfg, states, pending, backend="vmap", quantum=quantum,
                     obs=obs)
    low = ctl._vmap_mega.lower(ctl.states, ctl.pending, jnp.int32(0),
                               jnp.int32(4), jnp.int32(2))
    assert scopes(low) == EXPECT[kind]


def test_job_megaloop_names_every_phase(platform):
    kind, (cfg, states, pending, quantum, obs) = platform
    ctl = Controller(cfg, states, pending, backend="vmap", quantum=quantum,
                     obs=obs)
    one = lambda t: jax.tree.map(lambda x: x[None], t)
    cap = lambda v: jnp.full((1,), v, jnp.int32)
    low = ctl_mod.job_mega_fn(ctl.cfg, quantum, obs).lower(
        one(ctl.states), one(ctl.pending), jnp.zeros((1,), jnp.int32),
        jnp.zeros((1,), bool), jnp.zeros((1,), bool), cap(cfg.in_cap),
        cap(cfg.out_cap), cap(cfg.store_log), jnp.int32(0), jnp.int32(4),
        jnp.int32(2))
    assert scopes(low) == EXPECT[kind] | {"vp.freeze"}


def test_shard_map_round_names_every_phase(subproc):
    out = subproc(
        """
import json, re
import jax.numpy as jnp
from repro.launch.mesh import make_mesh
from repro.core.controller import Controller
from repro.obs import profile
import test_tracing as tt

mesh = make_mesh((2,), ("segment",))
found = {}
for kind in ("dense", "snn"):
    cfg, states, pending, quantum, obs = tt.build(kind)
    ctl = Controller(cfg, states, pending, backend="shard_map",
                     quantum=quantum, mesh=mesh)
    found[kind] = {
        "round": sorted(tt.scopes(ctl._shard_round.lower(ctl.states,
                                                         ctl.pending))),
        "mega": sorted(tt.scopes(ctl._shard_mega.lower(
            ctl.states, ctl.pending, jnp.int32(0), jnp.int32(4),
            jnp.int32(2))))}
print(json.dumps(found))
""".replace("import test_tracing as tt",
            f"import sys; sys.path.insert(0, {str(Path(__file__).parent)!r})"
            "\nimport test_tracing as tt"),
        n_devices=2)
    import json

    found = json.loads(out.strip().splitlines()[-1])
    for kind in ("dense", "snn"):
        assert set(found[kind]["round"]) == EXPECT[kind] - {"vp.check"}
        assert set(found[kind]["mega"]) == EXPECT[kind]


def _host_spans(trace_dir):
    files = sorted(Path(trace_dir).rglob("*.xplane.pb"))
    data = jax.profiler.ProfileData.from_file(str(files[-1]))
    return [(e.name, e.start_ns, e.end_ns, dict(e.stats))
            for plane in data.planes if plane.name.startswith("/host:")
            for line in plane.lines for e in line.events
            if e.name in profile.SPANS]


def test_controller_host_spans_nest(tmp_path):
    cfg, states, pending, quantum, _ = build("snn")
    Controller(cfg, states, pending, quantum=quantum).run(max_rounds=2)
    with jax.profiler.trace(str(tmp_path)):
        ctl = Controller(cfg, states, pending, quantum=quantum)
        # two one-round dispatches that do not finish the job: the run
        # ends in the overflow check
        ctl.run(max_rounds=2, rounds_per_dispatch=1)
    spans = [s for s in _host_spans(tmp_path)
             if s[3].get("controller") == ctl.serial]
    names = [n for n, *_ in spans]
    assert sorted(set(names)) == sorted(profile.SPANS)
    assert names.count("vp.dispatch") == names.count("vp.sync") == 2
    assert sorted(s[3]["dispatch"] for s in spans
                  if s[0] == "vp.dispatch") == [0, 1]
    for name, start, end, _ in spans:
        parent = profile.SPANS[name]
        if parent is not None:
            assert any(p == parent and ps <= start and end <= pe
                       for p, ps, pe, _ in spans), name


def test_dispatched_megaloop_hlo_names_its_phases():
    """The compiled text of the dispatched megaloop keeps each phase on the
    op_name of its instructions."""
    cfg, states, pending, quantum, _ = build("dense")
    Controller(cfg, states, pending, quantum=quantum).run(max_rounds=4)
    texts = ctl_mod.dispatched_megaloop_hlo()
    named = [{innermost(p) for p in re.findall(r'op_name="([^"]*)"', t)}
             for t in texts]
    mine = [n for n in named if "vp.cim_op" in n]
    assert mine
    assert EXPECT["dense"] <= mine[0]
    assert all("ENTRY" in t for t in texts)


def instructions(text: str) -> list:
    """A compiled module's instruction lines without their metadata."""
    return [re.sub(r", metadata=\{[^}]*\}", "", line)
            for line in text.splitlines()
            if re.match(r"\s*(ROOT )?%\S+ = ", line)]


@pytest.mark.parametrize("committed", [False, True])
def test_dispatched_megaloop_hlo_is_the_dispatched_program(committed):
    """The text is the program ``jit`` compiled for the dispatch, whether
    the state arrays were placed on a device (committed) or not."""
    cfg, states, pending, quantum, _ = build("snn")
    if committed:
        states, pending = jax.device_put((states, pending), jax.devices()[0])
    ctl = Controller(cfg, states, pending, quantum=quantum)
    want = ctl._vmap_mega.lower(ctl.states, ctl.pending, jnp.int32(0),
                                jnp.int32(4), jnp.int32(2)).compile()
    ctl.run(max_rounds=4)
    assert instructions(want.as_text()) in [
        instructions(t) for t in ctl_mod.dispatched_megaloop_hlo()]


def test_dispatched_megaloop_hlo_has_this_codes_phases(subproc, tmp_path,
                                                        monkeypatch):
    """The persistent compile cache keys a program without its metadata:
    where a checkout without phase names compiled the megaloop first, the
    dispatch runs that executable, and the text still carries the phases."""
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    out = subproc(f"""
import contextlib, json, sys
import jax
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
sys.path.insert(0, {str(Path(__file__).parent)!r})
import test_tracing as tt
from repro.core import controller as ctl_mod
from repro.obs import profile
from repro.vp import platform as pf

class Bare(contextlib.ContextDecorator):
    def __enter__(self):
        return self
    def __exit__(self, *exc):
        return False

cfg, states, pending, quantum, _ = tt.build("snn")
ctl_mod.phase = pf.phase = lambda name: Bare()
ctl_mod.Controller(cfg, states, pending, quantum=quantum).run(max_rounds=4)
ctl_mod._FN_CACHE.clear()
ctl_mod.phase = pf.phase = profile.phase
ctl = ctl_mod.Controller(cfg, states, pending, quantum=quantum)
ctl.run(max_rounds=4)
fns = next(iter(ctl_mod._FN_CACHE.values()))
args = next(iter(fns["mega_args"].values()))
ran = fns["vmap_mega"].lower(*args).compile().as_text()
own = ctl_mod.dispatched_megaloop_hlo()
print(json.dumps([ran.count("vp."), [t.count("vp.") for t in own]]))
""", n_devices=1)
    import json

    ran, own = json.loads(out.strip().splitlines()[-1])
    assert ran == 0  # the cached executable has no phase names
    assert len(own) == 1 and own[0] > 0
