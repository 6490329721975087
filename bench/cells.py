"""The two drivers behind every cell: jobs run back to back (``SimCell``)
and a closed loop of clients on one server (``ServeCell``).

Each driver builds its pool at set-up, warms its shapes with one job or one
flush, measures a window, and afterwards compares everything the window
produced with the plain references: outputs, and the simulated rounds and
cycles of every job.  What a job is, and how its results are fetched and
compared, is the configuration's kind's (``bench/kinds/<kind>.py``); the
drivers know none.  Host spans (``bench.*``) mark what the harness is
doing, so that the trace can say what the host did while the device idled.
"""
from __future__ import annotations

import dataclasses
import time

import jax
import numpy as np
from jax.profiler import TraceAnnotation

from bench import check, jobs, kinds


@dataclasses.dataclass
class Done:
    """One job or request of the window, as the harness saw it."""
    entry: int               # pool index
    out: tuple | None        # fetched host arrays; None when it failed
    rounds: int
    cycles: int
    error: str | None = None


def sim_cycles(done) -> int:
    """Simulated cycles of every job or request that returned a result."""
    return sum(d.cycles for d in done if d.out is not None)


class SimCell:
    """Jobs back to back, each from a fresh ``Controller`` on the ``vmap``
    megaloop, cycling through a pool built from the seed.  ``kind`` is the
    configuration's kind module, ``kinds.find(config["kind"])`` where not
    given."""

    lanes_per_chip = 1   # jobs each kernel call covers
    lost = 0             # jobs started and never returned

    def __init__(self, config: dict, traffic: dict, seed: int, kind=None):
        from repro.core.controller import Controller

        self._controller = Controller
        self.config, self.traffic = config, traffic
        self.kind = kind or kinds.find(config["kind"])
        self.pool = []
        with TraceAnnotation("bench.build"):
            for i in range(traffic["pool"]):
                job = self.kind.job(config, traffic, seed, i)
                self.pool.append((job, self.kind.build(config, traffic, job)))
        self.platform = self.pool[0][1][0]

    def run_one(self, entry: int) -> Done:
        job, (cfg, states, pending, _) = self.pool[entry]
        with TraceAnnotation("bench.controller"):
            ctl = self._controller(cfg, states, pending, backend="vmap",
                                   quantum=self.config["quantum"])
        try:
            with TraceAnnotation("bench.dispatch"):
                ctl.run(max_rounds=self.traffic["max_rounds"],
                        check_every=self.traffic["check_every"])
        except RuntimeError as e:
            return Done(entry, None, ctl.rounds_run, 0, str(e))
        with TraceAnnotation("bench.fetch"):
            out = jax.device_get(self.kind.fetch(ctl.result_states(), job,
                                                 cfg))
        return Done(entry, out, ctl.rounds_run, int(np.max(out[-1])))

    def warm(self) -> None:
        self.run_one(0)

    def window(self, seconds: float):
        """Jobs until ``seconds`` have passed; the window ends with the last
        job started before then.  Returns (done, window seconds)."""
        done = []
        t0 = time.perf_counter()
        while time.perf_counter() - t0 < seconds:
            done.append(self.run_one(len(done) % len(self.pool)))
        return done, time.perf_counter() - t0

    @staticmethod
    def rounds(done) -> int:
        return sum(d.rounds for d in done)

    def verify(self, done):
        """((correct, checks), failed jobs): every job of the window against
        the reference."""
        vals, failed = self.kind.compare(
            self.config, self.traffic,
            [(job, built[3]) for job, built in self.pool], done)
        return check.verdict(vals, self.kind.LIMITS), failed


class ServeCell:
    """A closed loop: ``clients`` clients each keep one request in one
    server (fanned over ``devices``); every flush returns all of them, and
    each client submits its next request at once (``jobs.serve_entry``
    says which).  The configuration's kind has to have a serving builder
    (``server`` and ``request``)."""

    def __init__(self, config: dict, traffic: dict, seed: int, devices,
                 kind=None):
        from repro.launch.mesh import make_serve_mesh

        self.config, self.traffic = config, traffic
        self.kind = kind or kinds.find(config["kind"])
        if not hasattr(self.kind, "server"):
            raise SystemExit(
                f"configuration kind {config['kind']!r} has no serving "
                f"builder: {self.kind.__file__} defines no server() and "
                f"request() for traffic of kind 'serve'")
        self.t_steps = jobs.serve_t_steps(traffic, seed)
        self.entries, self.pool = [], []   # (job, meta); requests
        with TraceAnnotation("bench.build"):
            for i, t in enumerate(self.t_steps):
                job = self.kind.job(config, traffic, seed, i, t)
                built = self.kind.build(config, traffic, job)
                self.entries.append((job, built[3]))
                self.pool.append(self.kind.request(built))
        self.platform = self.pool[0].cfg
        self.lanes_per_chip = traffic["bucket"] // len(devices)
        self.server = self.kind.server(config, traffic,
                                       make_serve_mesh(len(devices)))
        self.flushes = 0
        self.flush_rounds = []   # megaloop rounds of each flush
        self.flush_s = []        # wall seconds of each flush of the window
        self.lost = 0            # requests submitted and never returned

    def flush_once(self):
        """Submit one request per client, flush, fetch every result."""
        sent = {}
        with TraceAnnotation("bench.submit"):
            for c in range(self.traffic["clients"]):
                e = jobs.serve_entry(self.t_steps, c, self.flushes)
                sent[self.server.submit(self.pool[e])] = e
        with TraceAnnotation("bench.flush"):
            res = self.server.flush()
        self.flushes += 1
        with TraceAnnotation("bench.fetch"):
            outs = jax.device_get({
                k: self.kind.fetch(r.states, self.entries[sent[k]][0],
                                   self.pool[sent[k]].cfg)
                for k, r in res.items() if r.ok})
        done, lost = [], 0
        for k, e in sent.items():
            r = res.get(k)
            if r is None:
                lost += 1
                continue
            out = outs.get(k)
            cycles = 0 if out is None else int(np.max(out[-1]))
            done.append(Done(e, out, r.rounds, cycles, r.error))
        self.flush_rounds.append(max((d.rounds for d in done), default=0))
        return done, lost

    def warm(self) -> None:
        self.flush_once()
        self.flushes = 0
        self.flush_rounds = []

    def window(self, seconds: float):
        done, lost = [], 0
        t0 = time.perf_counter()
        while time.perf_counter() - t0 < seconds:
            t = time.perf_counter()
            d, l = self.flush_once()
            self.flush_s.append(time.perf_counter() - t)
            done += d
            lost += l
        self.lost = lost
        return done, time.perf_counter() - t0

    def rounds(self, done) -> int:
        return sum(self.flush_rounds)

    def verify(self, done):
        limits = {"requests_lost": 0, **self.kind.LIMITS}
        vals, failed = self.kind.compare(self.config, self.traffic,
                                         self.entries, done)
        vals["requests_lost"] = self.lost
        return check.verdict(vals, limits), failed + self.lost
