"""The two drivers behind every cell: jobs run back to back (``SimCell``)
and a closed loop of clients on one server (``ServeCell``).

Each driver builds its pool at set-up, warms its shapes with one job or one
flush, measures a window, and afterwards compares everything the window
produced with the plain references (``check.py``): outputs, and the
simulated rounds and cycles of every job.  Host spans (``bench.*``) mark
what the harness is doing, so that the trace can say what the host did
while the device idled.
"""
from __future__ import annotations

import dataclasses
import time

import jax
import numpy as np
from jax.profiler import TraceAnnotation

from bench import check, jobs, reference


@dataclasses.dataclass
class Done:
    """One job or request of the window, as the harness saw it."""
    entry: int               # pool index
    out: tuple | None        # fetched host arrays; None when it failed
    rounds: int
    cycles: int
    error: str | None = None
    latency_s: float = 0.0   # serve: submit to the flush that returned it


def _snn_groups(meta):
    """[(layer, r0, r1, seg, slot)] of every stripe's state-holding unit."""
    return [(g["group"].layer, g["group"].r0, g["group"].r1, *g["units"][0])
            for g in meta["groups"]]


def _snn_fetch(states):
    c = states["cims"]
    return jax.device_get((c["v"], c["spike_counts"], c["ticks"],
                           states["time"]))


def _snn_compare(groups_of, job_of, done, check_every, latency, limits):
    """(values, failed jobs): every disagreement of SNN results with the
    oracle; ``groups_of``/``job_of`` map a pool entry to its stripes and its
    job.  A job's rounds are due as ``reference.snn_rounds`` says, its
    cycles one channel latency a round."""
    vals = dict.fromkeys(limits, 0)
    refs = {}
    for e in sorted({d.entry for d in done if d.out is not None}):
        ticks = max(int(d.out[2].max()) for d in done
                    if d.entry == e and d.out is not None)
        ref = check.snn_reference(job_of(e), ticks)
        rounds = reference.snn_rounds(job_of(e).raster, ref[1],
                                            check_every)
        refs[e] = ref, rounds
    failed = 0
    for d in done:
        if d.out is None:
            vals["failed_jobs"] += 1
            failed += 1
            continue
        ref, rounds = refs[d.entry]
        spikes, mem = check.snn_wrong(groups_of(d.entry), *d.out[:3], ref)
        r_off = d.rounds != rounds
        c_off = d.cycles != rounds * latency
        vals["spike_totals_wrong"] += spikes
        vals["membranes_wrong"] += mem
        vals["jobs_rounds_off"] += r_off
        vals["jobs_cycles_off"] += c_off
        failed += bool(spikes or mem or r_off or c_off)
    return vals, failed


class SimCell:
    """Jobs back to back, each from a fresh ``Controller`` on the ``vmap``
    megaloop, cycling through a pool built from the seed."""

    lanes_per_chip = 1   # jobs each kernel call covers
    lost = 0             # jobs started and never returned

    def __init__(self, config: dict, traffic: dict, seed: int):
        from repro.core.controller import Controller

        self._controller = Controller
        self.config, self.traffic = config, traffic
        self.kind = traffic["kind"]
        self.pool = []
        with TraceAnnotation("bench.build"):
            for i in range(traffic["pool"]):
                if self.kind == "dense":
                    job = jobs.dense_job(config, traffic, seed, i)
                    built = jobs.placed(jobs.build_dense, config, traffic,
                                        job)
                else:
                    job = jobs.snn_job(config, traffic["t_steps"],
                                       traffic["rate"], seed, i)
                    built = jobs.placed(jobs.build_snn, config, job,
                                        traffic["use_kernel"])
                self.pool.append((job, built))
        self.platform = self.pool[0][1][0]

    def run_one(self, entry: int) -> Done:
        job, (cfg, states, pending, *meta) = self.pool[entry]
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
            st = ctl.result_states()
            if self.kind == "dense":
                h, p = job.a.shape[0], job.b.shape[1]
                out = jax.device_get((st["dram"]["data"][
                    cfg.dram_segment, job.o_word:job.o_word + h * p],
                    st["time"]))
            else:
                out = _snn_fetch(st)
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

    @staticmethod
    def sim_cycles(done) -> int:
        return sum(d.cycles for d in done if d.out is not None)

    def verify(self, done):
        """((correct, checks), failed jobs): every job of the window against
        the reference.  A dense layer's simulated time does not depend on
        its data, so its rounds and cycles are the traffic file's."""
        if self.kind != "dense":
            vals, failed = _snn_compare(
                lambda e: _snn_groups(self.pool[e][1][3]),
                lambda e: self.pool[e][0], done, self.traffic["check_every"],
                self.config["channel_latency"], check.SNN_LIMITS)
            return check.verdict(vals, check.SNN_LIMITS), failed
        vals = dict.fromkeys(check.DENSE_LIMITS, 0)
        wants, failed = {}, 0
        for d in done:
            if d.out is None:
                vals["failed_jobs"] += 1
                failed += 1
                continue
            job = self.pool[d.entry][0]
            if d.entry not in wants:
                wants[d.entry] = reference.dense_output(job.a, job.b)
            wrong = check.dense_wrong(job, d.out[0], want=wants[d.entry])
            r_off = d.rounds != self.traffic["expect_rounds"]
            c_off = d.cycles != self.traffic["expect_cycles"]
            vals["output_words_wrong"] += wrong
            vals["jobs_rounds_off"] += r_off
            vals["jobs_cycles_off"] += c_off
            failed += bool(wrong or r_off or c_off)
        return check.verdict(vals, check.DENSE_LIMITS), failed


class ServeCell:
    """A closed loop: ``clients`` clients each keep one request in one
    ``SnnServer`` (fanned over ``devices``); every flush returns all
    of them, and each client submits its next request at once."""

    def __init__(self, config: dict, traffic: dict, seed: int, devices):
        from repro.launch.mesh import make_serve_mesh
        from repro.serve.snn_serve import SnnRequest, SnnServer

        self.config, self.traffic = config, traffic
        self.jobs, self.pool, self.groups = [], [], []
        with TraceAnnotation("bench.build"):
            for i, t in enumerate(jobs.serve_t_steps(traffic, seed)):
                job = jobs.snn_job(config, t, traffic["rate"], seed, i)
                cfg, states, pending, meta = jobs.placed(
                    jobs.build_snn, config, job, traffic["use_kernel"])
                self.jobs.append(job)
                self.pool.append(SnnRequest(cfg, states, pending, meta))
                self.groups.append(_snn_groups(meta))
        self.platform = self.pool[0].cfg
        self.lanes_per_chip = traffic["bucket"] // len(devices)
        self.server = SnnServer(
            quantum=config["quantum"], check_every=traffic["check_every"],
            max_rounds=traffic["max_rounds"], bucket_size=traffic["bucket"],
            mesh=make_serve_mesh(len(devices)))
        self.flushes = 0
        self.flush_rounds = []   # megaloop rounds of each flush
        self.lost = 0            # requests submitted and never returned

    def flush_once(self):
        """Submit one request per client, flush, fetch every result."""
        n, clients = len(self.pool), self.traffic["clients"]
        sent = {}
        with TraceAnnotation("bench.submit"):
            for c in range(clients):
                e = (self.flushes * clients + c) % n
                t = time.perf_counter()
                sent[self.server.submit(self.pool[e])] = (e, t)
        with TraceAnnotation("bench.flush"):
            res = self.server.flush()
        t_ret = time.perf_counter()
        self.flushes += 1
        with TraceAnnotation("bench.fetch"):
            outs = jax.device_get({k: (r.states["cims"]["v"],
                                       r.states["cims"]["spike_counts"],
                                       r.states["cims"]["ticks"],
                                       r.states["time"])
                                   for k, r in res.items() if r.ok})
        done, lost = [], 0
        for k, (e, t) in sent.items():
            r = res.get(k)
            if r is None:
                lost += 1
                continue
            out = outs.get(k)
            cycles = 0 if out is None else int(np.max(out[3]))
            done.append(Done(e, out, r.rounds, cycles, r.error,
                             t_ret - t))
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
            d, l = self.flush_once()
            done += d
            lost += l
        self.lost = lost
        return done, time.perf_counter() - t0

    def rounds(self, done) -> int:
        return sum(self.flush_rounds)

    def verify(self, done):
        vals, failed = _snn_compare(
            lambda e: self.groups[e], lambda e: self.jobs[e], done,
            self.traffic["check_every"], self.config["channel_latency"],
            check.SERVE_LIMITS)
        vals["requests_lost"] = self.lost
        return check.verdict(vals, check.SERVE_LIMITS), failed + self.lost
