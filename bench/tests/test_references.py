"""The benchmark's copies of the generators and oracles against the
program's own, at tiny sizes."""
from __future__ import annotations

import json

import numpy as np
import pytest

from bench import jobs, kinds, reference
from bench.run import BENCH

DENSE = kinds.find("dense_platform")
SNN = kinds.find("snn_network")


def _config(name):
    return json.loads((BENCH / "configs" / f"{name}.json").read_text())


@pytest.mark.parametrize("h,w,p", [(8, 8, 4), (5, 7, 3)])
def test_programs_assemble_like_the_programs_own(h, w, p):
    from repro.core import segmentation as sg
    from repro.vp import workloads as wl
    from repro.vp.assembler import assemble, vmm_riscv_program

    ours = DENSE.riscv_program(h, w, p, 4096, 8192, 12288)
    assert np.array_equal(assemble(ours),
                          assemble(vmm_riscv_program(h, w, p, 4096, 8192,
                                                     12288)))
    bases = (sg.cim_global_base(2), sg.cim_global_base(3))
    for ords in ((0, 1), (1, 0)):
        ours = DENSE.cim_pair_program(bases, h, w, 1, p, 12288, p, ords)
        theirs = wl.cim_pair_program(bases, h, w, 1, p, 12288, p,
                                     ordinals=ords)
        assert np.array_equal(assemble(ours), assemble(theirs))


def test_dense_job_wires_units_like_the_program():
    from repro.core import segmentation as sg
    from repro.vp import workloads as wl

    config = _config("paper-2x2-uniform")
    traffic = {"layer": {"h": 8, "w": 8, "p": 5}, "mode": "cim",
               "managers": [0, 1]}
    job = DENSE.job(config, traffic, seed=3, index=0)
    descs = DENSE.platform_descs(config)
    theirs = wl.cim_workload(wl.Layer("t", "t", 8, 8, 5), [0, 1],
                             {0: (0, 1), 1: (2, 3)},
                             ordinals=sg.mailbox_ordinals(descs))
    assert sorted(job.build_kw["crossbars"]) == sorted(theirs["crossbars"])
    assert sorted(job.build_kw["programs"]) == sorted(theirs["programs"])
    assert job.o_word == theirs["o_word"]
    np.testing.assert_array_equal(reference.dense_output(job.a, job.b),
                                  job.a.astype(np.int64) @ job.b)


def test_lif_oracle_matches_the_programs_oracle():
    from repro import snn

    config = {**_config("snn-fc784-1200-1200-10"),
              "layer_sizes": [40, 30, 20, 10]}
    job = SNN.job(config, {"rate": 0.3}, seed=2**33 + 1, index=2, t_steps=7)
    layers = [snn.SNNLayer(w, snn.LIFParams(thresh=t, leak=job.leak))
              for w, t in zip(job.weights, job.thresh)]
    counts, totals = snn.oracle_run(layers, job.raster)
    n = reference.feedforward_horizon(7, 3)
    v, c = reference.lif_run(job.weights, job.thresh, job.leak,
                             job.refrac_period, job.raster, n)
    np.testing.assert_array_equal(c[-1][-1], counts)
    assert [int(x[-1].sum()) for x in c] == list(totals)
    assert all(len(x) == n + 1 for x in v)


def test_serve_lengths_are_the_same_multiset_for_every_seed():
    traffic = {"t_steps_choices": [8, 12, 16], "pool": 24}
    a = jobs.serve_t_steps(traffic, 1)
    b = jobs.serve_t_steps(traffic, 2**40 + 3)
    assert sorted(a) == sorted(b) == sorted([8, 12, 16] * 8)


def test_every_flush_sends_the_same_mix_of_lengths_on_every_seed():
    traffic = {"t_steps_choices": [8, 12, 16], "pool": 12}
    mixes, sent = set(), set()
    for seed in (1, 2**40 + 3):
        ts = jobs.serve_t_steps(traffic, seed)
        for flush in range(4):
            entries = [jobs.serve_entry(ts, c, flush) for c in range(32)]
            mixes.add(tuple(sorted(ts[e] for e in entries)))
            sent.update((seed, e) for e in entries)
    assert mixes == {(8,) * 11 + (12,) * 11 + (16,) * 10}
    assert len(sent) == 2 * 12


def test_jobs_follow_the_seed():
    config = _config("snn-fc784-1200-1200-10")
    traffic = {"t_steps": 4, "rate": 0.2}
    a = SNN.job(config, traffic, 2**32 + 5, 0)
    b = SNN.job(config, traffic, 2**32 + 5, 0)
    c = SNN.job(config, traffic, 2**32 + 6, 0)
    assert np.array_equal(a.raster, b.raster)
    assert all(np.array_equal(x, y) for x, y in zip(a.weights, b.weights))
    assert not np.array_equal(a.weights[0], c.weights[0])


def test_crossbar_units_of_the_published_network():
    assert SNN.crossbar_units([784, 1200, 1200, 10]) == 50


@pytest.mark.parametrize("check_every,rate", [(2, 0.3), (4, 0.3), (2, 0.05)])
def test_snn_rounds_match_the_program(check_every, rate):
    from bench.tests.helpers import TINY_SNN

    config = {**_config("snn-fc784-1200-1200-10"), **TINY_SNN}
    job = SNN.job(config, {"t_steps": 6, "rate": rate},
                  seed=2**35 + check_every, index=1)
    cfg, states, pending, _ = SNN.build(config, {"use_kernel": False}, job)
    from repro.core.controller import Controller

    ctl = Controller(cfg, states, pending, backend="vmap",
                     quantum=config["quantum"])
    ctl.run(max_rounds=400, check_every=check_every)
    _, counts = reference.lif_run(job.weights, job.thresh, job.leak,
                                  job.refrac_period, job.raster, 40)
    want = reference.snn_rounds(job.raster, counts, check_every)
    assert ctl.rounds_run == want
    assert int(np.max(ctl.sim_time())) == want * config["channel_latency"]
