"""With the timed path broken underneath, a whole run comes out not
correct: once for each fault the cell can have."""
from __future__ import annotations

import jax.numpy as jnp
import pytest

from bench.tests.helpers import rehearse


@pytest.fixture
def fresh_programs(monkeypatch):
    """Empty the program's caches of compiled steps, so a patched step is
    traced again."""
    from repro.core import controller

    monkeypatch.setattr(controller, "_FN_CACHE", {})
    monkeypatch.setattr(controller, "_JOB_FN_CACHE", {})
    return monkeypatch


def state_unchanged(mp):
    """Every step returns its state unchanged."""
    from repro.core import controller

    mp.setattr(controller.Controller, "run",
               lambda self, *a, **k: (self.rounds_run, 0.0))

    def frozen(cfg, quantum, obs):
        def mega(states, pending, rounds, done, over, *rest):
            return states, pending, rounds, jnp.ones_like(done), over
        return mega

    mp.setattr(controller, "_job_megaloop", frozen)


def half_the_batch_left_out(mp):
    """A flush serves the first half of the queue; the rest never return."""
    from repro.serve.snn_serve import SnnServer

    flush = SnnServer.flush

    def half(self):
        self._queue = self._queue[:len(self._queue) // 2]
        return flush(self)

    mp.setattr(SnnServer, "flush", half)


def exchange_left_out(mp):
    """Routing between segments delivers nothing."""
    from repro.core import channel

    route = channel.route
    mp.setattr(channel, "route", lambda out, lat, cap: route(
        {**out, "valid": jnp.zeros_like(out["valid"])}, lat, cap))


def answer_altered(mp):
    """Each stored word of a CPU, and each membrane of a ticking unit, is
    off by one where it is produced."""
    from repro.vp import cim, riscv

    execute, tick = riscv.execute, cim.snn_tick

    def off_by_one_store(cpu, instr):
        cpu, mem = execute(cpu, instr)
        return cpu, {**mem, "st_data": mem["st_data"] + 1}

    def off_by_one_tick(*a, **k):
        cims, rows, fire, t = tick(*a, **k)
        return {**cims, "v": cims["v"] + fire[:, None]}, rows, fire, t

    mp.setattr(riscv, "execute", off_by_one_store)
    mp.setattr(cim, "snn_tick", off_by_one_tick)


CASES = [
    ("dense-cim-imagenet-conv1", state_unchanged),
    ("dense-cim-imagenet-conv1", answer_altered),
    ("dense-riscv-googlenet-conv2", state_unchanged),
    ("dense-riscv-googlenet-conv2", answer_altered),
    ("snn-fc1200-infer", state_unchanged),
    ("snn-fc1200-infer", exchange_left_out),
    ("snn-fc1200-infer", answer_altered),
    ("snn-fc1200-serve-4chip", state_unchanged),
    ("snn-fc1200-serve-4chip", half_the_batch_left_out),
    ("snn-fc1200-serve-4chip", answer_altered),
]


@pytest.mark.parametrize("cell,fault", CASES,
                         ids=[f"{c}-{f.__name__}" for c, f in CASES])
def test_a_broken_path_is_not_correct(fresh_programs, cell, fault):
    fault(fresh_programs)
    res = rehearse(cell, chips=1)
    assert not res["correct"], res["checks"]
    assert res["failed"] > 0
