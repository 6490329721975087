"""Simulation controller: quantum stepping + time-decoupled synchronization
(paper §IV, Fig. 2/3) with four execution backends.

Per round (= the paper's ``exec`` + ``sync``):

  limit_i = min_{j≠i} (time_j + latency[j, i])      # decoupling bound
  states'_i, outbox_i = segment_step(states_i, pending_i, limit_i)
  pending' = merge(pending, route(outboxes))        # sync

Backends for the ``exec`` phase (DESIGN.md §2):
  sequential — one host thread steps segments one after another: the
               conventional SystemC baseline ("sq");
  vmap       — segments stacked and stepped as one vectorized program: the
               single-device parallel backend ("pll" on a 1-core host);
  threads    — one host thread per segment (the paper's literal mechanism;
               only wins on multi-core hosts);
  shard_map  — one mesh device per segment; routing becomes an all-gather
               over the ``segment`` axis.  This is the production backend
               the multi-pod dry-run lowers.

All four produce bit-identical simulation results (property-tested): time
decoupling changes wall-clock interleaving, never simulated semantics.

The stacked backends (``vmap``/``shard_map``) additionally run the round
loop itself device-resident: ``run()`` dispatches a fused *megastep* — one
jitted ``jax.lax.while_loop`` that executes up to ``rounds_per_dispatch``
exec+sync rounds per host dispatch, evaluating the termination predicate
and the sticky overflow watermarks on-device (``platform.termination_flags``)
so the host syncs one tiny scalar tuple per dispatch instead of four
``bool(jnp.any(...))`` round-trips per ``check_every`` rounds.  Results,
round counts, and overflow errors are bit-identical to per-round execution
(``fused=False``).  ``sequential``/``threads`` keep their honest host-side
per-round loop (they *are* the host-scheduling baselines) but share the
fused single-sync done-reducer.
"""
from __future__ import annotations

import concurrent.futures as cf
import dataclasses
import time as _time
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import channel as ch
from repro.obs import trace as obs_trace
from repro.vp import platform as pf


_FN_CACHE: dict = {}  # (cfg, quantum, s, obs) -> compiled fns; benchmarks
                      # rebuild controllers per workload with identical shapes

# the single host-transfer primitive for dispatch-boundary syncs: every
# fused-dispatch fetch (round count + flags + telemetry ring) goes through
# one call to this, so tests can monkeypatch it to count device syncs and
# prove the one-sync-per-dispatch contract (tests/test_conformance.py)
_HOST_FETCH = jax.device_get


@dataclasses.dataclass
class Controller:
    cfg: pf.VPConfig
    states: object  # stacked (S, ...) pytree
    pending: object  # stacked (S, IN_CAP)
    backend: str = "vmap"
    quantum: int = 10_000
    mesh: object = None  # shard_map backend only
    rounds_run: int = 0
    obs: object = None  # obs.trace.TraceConfig, or None = tracing compiled out

    def __post_init__(self):
        # own the state: round fns donate their inputs, so the caller's
        # arrays must not be shared with this controller
        self.states = jax.tree.map(jnp.copy, self.states)
        self.pending = jax.tree.map(jnp.copy, self.pending)
        # telemetry (obs/): attach one trace ring per segment INSIDE the
        # state pytree, so the megaloop carries it and the step appends to
        # it in traced code; host-side bookkeeping for drained batches.
        # Attached before the list-mode split so every backend carries it.
        self.dispatches = 0      # fused megaloop dispatches issued
        self.dispatch_syncs = 0  # _HOST_FETCH calls from the fused loop
        self.trace_lost = 0      # events dropped to ring capacity
        self._events = []        # drained batches (np structured arrays)
        self._finished = False   # a run() observed clean termination
        if self.obs is not None and "trace" not in self.states:
            cap = int(self.obs.capacity)
            self.states = {
                **self.states,
                "trace": jax.vmap(lambda _: obs_trace.ring_state(cap))(
                    jnp.arange(self.cfg.n_segments)),
            }
        # the CPU-free fast path (VPConfig.has_cpu=False: no slot scan, no
        # MMIO inbox handling, no dense completion) is only valid while
        # nothing but AER spikes can circulate.  The builder guarantees that
        # for its own wiring, but callers may hand-inject MMIO/DMA messages
        # into the initial pending box — detect that once and fall back to
        # the full step (one host check at construction, never per round)
        if not self.cfg.has_cpu:
            injected = np.asarray(
                self.pending["valid"] & (self.pending["kind"] != ch.MSG_SPIKE)
            )
            if injected.any():
                self.cfg = dataclasses.replace(self.cfg, has_cpu=True)
        self.lat = self.cfg.latency_matrix()
        # sequential/threads keep per-segment state as persistent lists —
        # the honest "sq" baseline must not pay per-round slice/stack of the
        # 4 MB DRAM image (that would inflate the parallel speedup)
        self._list_mode = self.backend in ("sequential", "threads")
        if self._list_mode:
            s = self.cfg.n_segments
            take = lambda t, i: jax.tree.map(lambda x: x[i], t)
            self._states_l = [take(self.states, i) for i in range(s)]
            self._pending_l = [take(self.pending, i) for i in range(s)]
        # threads backend: one persistent pool for the controller's life —
        # creating and tearing down a ThreadPoolExecutor every round would
        # penalize the paper's literal parallel mechanism with pure host
        # overhead (thread spawn/join per quantum)
        self._pool = (
            cf.ThreadPoolExecutor(max_workers=self.cfg.n_segments,
                                  thread_name_prefix="vp-seg")
            if self.backend == "threads" else None
        )
        step = pf.make_segment_step(self.cfg, self.quantum, self.obs)
        s = self.cfg.n_segments
        big = jnp.int32(2**30)
        # locals, NOT self.*, inside the jitted closures below: _FN_CACHE
        # outlives controllers, and a closure over `self` would pin the
        # first instance's entire copied state (MB of DRAM image per
        # segment) for process lifetime
        cfg = self.cfg
        lat = self.lat
        quantum = self.quantum

        def limits(times):
            # limit_i = min_{j != i}(t_j + lat[j, i]); single segment: t + q
            tl = times[:, None] + lat  # (src, dst)
            eye = jnp.eye(s, dtype=bool)
            tl = jnp.where(eye, big, tl)
            lim = tl.min(axis=0)
            if s == 1:
                lim = times + quantum
            return lim

        def vmap_round(states, pending):
            lim = limits(states["time"])
            states, outboxes, pending = jax.vmap(step)(states, pending, lim)
            fresh = ch.route(outboxes, lat, cfg.in_cap)
            pending = jax.vmap(ch.merge_pending)(pending, fresh)
            return states, pending

        def megaloop(round_fn):
            """Device-resident round loop: up to ``k`` rounds of ``round_fn``
            inside one ``lax.while_loop``, with the termination predicate and
            sticky overflow watermarks evaluated in traced code at the same
            points the host loop would (every ``check_every``-th round since
            ``run()`` started, ``r0`` rounds ago).  The host sees one scalar
            tuple per dispatch.  ``done`` means clean termination; ``over``
            means a watermark tripped at a check point — the host re-raises
            with the detailed message (the loop stops at the same round the
            per-round path would, so the message is identical too)."""

            def mega(states, pending, r0, k, check_every):
                def cond(carry):
                    _, _, i, done, over = carry
                    return ~(done | over) & (i < k)

                def body(carry):
                    st, pen, i, _, _ = carry
                    st, pen = round_fn(st, pen)
                    i = i + 1
                    at_check = ((r0 + i) % check_every) == 0

                    def checked(_):
                        done, in_over, out_over, st_over, late, _tr = \
                            pf.termination_flags(
                                st, pen, cfg.in_cap, cfg.out_cap, cfg.store_log)
                        # the trace-overflow flag (6) is informational and
                        # never stops the loop: telemetry loss must not
                        # change termination behavior (obs/trace.py).  Under
                        # the graceful-degradation overflow policy
                        # (faults.FaultConfig(on_overflow="drop")) the
                        # channel watermarks stop being fatal too — overflow
                        # is counted spike loss and the run continues; the
                        # program-bug flags (store log, late MMIO) still
                        # abort.  Static branch: the policy is part of the
                        # cached-function key, like every fault gate.
                        if cfg.faults is not None and cfg.faults.drop_overflow:
                            over = st_over | late
                        else:
                            over = in_over | out_over | st_over | late
                        return done & ~over, over

                    # cond, not where: non-check rounds skip the reductions
                    done, over = jax.lax.cond(
                        at_check, checked,
                        lambda _: (jnp.array(False), jnp.array(False)), None)
                    return st, pen, i, done, over

                zero, false = jnp.int32(0), jnp.array(False)
                return jax.lax.while_loop(
                    cond, body, (states, pending, zero, false, false)
                )

            return mega

        key = (self.cfg, self.quantum, s, self.obs)
        if key not in _FN_CACHE:
            _FN_CACHE[key] = {
                "vmap_round": jax.jit(vmap_round, donate_argnums=(0, 1)),
                "vmap_mega": jax.jit(megaloop(vmap_round), donate_argnums=(0, 1)),
                "flags": jax.jit(
                    lambda states, pending: jnp.stack(pf.termination_flags(
                        states, pending, cfg.in_cap, cfg.out_cap, cfg.store_log))
                ),
                "step_one": jax.jit(step),
                "limits": jax.jit(limits),
                "route": jax.jit(lambda outboxes: ch.route(outboxes, lat, cfg.in_cap)),
                "merge_one": jax.jit(ch.merge_pending, donate_argnums=(0,)),
            }
        fns = _FN_CACHE[key]
        self._vmap_round = fns["vmap_round"]
        self._vmap_mega = fns["vmap_mega"]
        self._flags_fn = fns["flags"]
        self._step_one = fns["step_one"]
        self._limits = fns["limits"]
        self._route = fns["route"]
        self._merge_one = fns["merge_one"]

        if self.backend == "shard_map":
            from jax.sharding import PartitionSpec as P

            assert self.mesh is not None, "shard_map backend needs a mesh"

            def shard_round(states, pending):
                def body(states1, pending1):
                    # leading segment axis is mapped: local shapes (1, ...)
                    my = jax.tree.map(lambda x: x[0], states1)
                    pen = jax.tree.map(lambda x: x[0], pending1)
                    seg_times = jax.lax.all_gather(my["time"], "segment")
                    i = jax.lax.axis_index("segment")
                    tl = seg_times + self.lat[:, i]
                    tl = jnp.where(jnp.arange(s) == i, big, tl)
                    lim = tl.min()
                    st, outbox, pen = step(my, pen, lim)
                    all_out = jax.lax.all_gather(outbox, "segment")  # (S, cap)
                    t_avail = all_out["t_emit"] + self.lat[
                        jnp.repeat(jnp.arange(s), self.cfg.out_cap).reshape(s, self.cfg.out_cap), i
                    ]
                    flat_valid = (all_out["valid"] & (all_out["dst"] == i)).reshape(-1)
                    rank = jnp.cumsum(flat_valid.astype(jnp.int32)) - 1
                    # dead lanes scatter out-of-bounds and drop (channel.py's
                    # "never write a dead slot" rule); past-cap lanes drop
                    # too — same drop-the-tail semantics as route(), so the
                    # graceful-degradation overflow policy loses the
                    # identical messages on this backend as on the others
                    # (count below still records true demand for the
                    # watermark and the lost_total accounting)
                    pos = jnp.where(flat_valid & (rank < self.cfg.in_cap),
                                    rank, self.cfg.in_cap)
                    fresh = ch.empty_pending(self.cfg.in_cap)
                    for f, src in (("kind", all_out["kind"]), ("addr", all_out["addr"]),
                                   ("data", all_out["data"]), ("t_avail", t_avail)):
                        fresh[f] = fresh[f].at[pos].set(src.reshape(-1), mode="drop")
                    fresh["valid"] = fresh["valid"].at[pos].set(flat_valid, mode="drop")
                    fresh["count"] = flat_valid.sum().astype(jnp.int32)
                    pen = ch.merge_pending(pen, fresh)
                    exp = lambda t: jax.tree.map(lambda x: x[None], t)
                    return exp(st), exp(pen)

                return jax.shard_map(
                    body,
                    mesh=self.mesh,
                    in_specs=(P("segment"), P("segment")),
                    out_specs=(P("segment"), P("segment")),
                    check_vma=False,
                )(states, pending)

            self._shard_round = jax.jit(shard_round, donate_argnums=(0, 1))
            # mesh-dependent, so per-instance rather than in _FN_CACHE; the
            # while_loop wraps the shard_map call and the flags reduce over
            # the sharded carry (XLA inserts the all-reduce)
            self._shard_mega = jax.jit(megaloop(shard_round), donate_argnums=(0, 1))

    # ------------------------------------------------------------------
    def _require_open(self):
        if getattr(self, "_closed", False):
            raise RuntimeError(
                "Controller is closed: close() released its host resources "
                "(the threads backend's worker pool); build a new Controller "
                "to run again"
            )

    def round(self):
        self._require_open()
        s = self.cfg.n_segments
        if self.backend == "vmap":
            self.states, self.pending = self._vmap_round(self.states, self.pending)
        elif self.backend == "shard_map":
            self.states, self.pending = self._shard_round(self.states, self.pending)
        elif self._list_mode:
            times = jnp.stack([st["time"] for st in self._states_l])
            lim = self._limits(times)

            def one(i):
                return self._step_one(self._states_l[i], self._pending_l[i], lim[i])

            if self.backend == "sequential":
                results = [one(i) for i in range(s)]
            else:
                results = list(self._pool.map(one, range(s)))
            self._states_l = [r[0] for r in results]
            stack = lambda xs: jax.tree.map(lambda *v: jnp.stack(v), *xs)
            outboxes = stack([r[1] for r in results])  # ~100 KB each: cheap
            fresh = self._route(outboxes)
            take = lambda t, i: jax.tree.map(lambda x: x[i], t)
            self._pending_l = [
                self._merge_one(r[2], take(fresh, i)) for i, r in enumerate(results)
            ]
        else:
            raise ValueError(self.backend)
        self.rounds_run += 1

    def _stacked(self):
        if self._list_mode:
            return jax.tree.map(lambda *v: jnp.stack(v), *self._states_l)
        return self.states

    def _pending_stacked(self):
        if self._list_mode:
            return jax.tree.map(lambda *v: jnp.stack(v), *self._pending_l)
        return self.pending

    @staticmethod
    def _flag_detail(flag_name, values, cap, kwarg=None):
        """Shared watermark formatter (both dispatch paths re-raise through
        ``_check_overflow``, so fused and per-round messages stay byte
        identical): names the tripped flag, the first segment past the cap,
        and the cap itself, then the full per-segment watermark vector.

        ``kwarg`` names the build()/build_snn keyword that sizes this cap;
        the watermark records true demand, so its peak IS the smallest
        capacity that would have absorbed the burst — the hint turns the
        abort into a one-edit remediation."""
        values = np.asarray(values)
        seg = int(np.flatnonzero(values > cap)[0])
        hint = "" if kwarg is None else (
            f"; smallest sufficient {kwarg}={int(values.max())}")
        return (f"flag '{flag_name}' tripped first at segment {seg} "
                f"({int(values[seg])} > cap {cap}; per-segment watermarks "
                f"{values.tolist()}{hint})")

    def _check_overflow(self, pending=None, states=None):
        drop = self.cfg.faults is not None and self.cfg.faults.drop_overflow
        pending = self._pending_stacked() if pending is None else pending
        states = self._stacked() if states is None else states
        msg = overflow_error(states, pending, in_cap=self.cfg.in_cap,
                             out_cap=self.cfg.out_cap,
                             store_log=self.cfg.store_log, drop=drop)
        if msg is not None:
            raise RuntimeError(msg)

    def done(self) -> bool:
        """Termination check + loud overflow validation (one device sync).

        The predicate itself lives in traced code
        (``platform.termination_flags`` — see its docstring for the exact
        semantics: running CPUs, in-flight CIM OPs, drainable spike-mode
        work, pending spike-count readbacks, pending messages); here it is
        evaluated as one fused jitted call returning a single (6,) bool
        array — done + the inbox/outbox/store-log watermarks, the
        late-SNN-MMIO flag, and the informational trace-ring overflow
        flag — instead of separate ``bool(jnp.any(...))`` host round-trips.
        """
        d, in_over, out_over, store_over, mmio_late, _trace_over = np.asarray(
            self._flags_fn(self._stacked(), self._pending_stacked())
        )
        drop = self.cfg.faults is not None and self.cfg.faults.drop_overflow
        if ((in_over or out_over) and not drop) or store_over or mmio_late:
            self._check_overflow()  # raises with the detailed watermark message
        return bool(d)

    def block_until_ready(self):
        """Wait for this controller's device state to materialize.

        Public replacement for benchmarks reaching into ``_states_l`` /
        ``_list_mode``; returns self so warm-up reads chain."""
        if self._list_mode:
            jax.block_until_ready((self._states_l, self._pending_l))
        else:
            jax.block_until_ready((self.states, self.pending))
        return self

    def close(self):
        """Release host resources (the threads backend's persistent pool).

        Idempotent; a closed controller refuses to ``run``/``round`` with a
        clear error instead of dying inside the round machinery.  Reading
        results (``result_states``/``stats``/``done``) stays valid."""
        if getattr(self, "_pool", None) is not None:
            self._pool.shutdown(wait=True)
            self._pool = None
        self._closed = True

    def __del__(self):
        try:
            self.close()
        except Exception:
            pass

    def _fetch(self, tree):
        """The dispatch-boundary host sync: one ``jax.device_get`` of the
        (round-count, done, over[, trace-ring]) tuple.  Counted so the
        one-sync-per-dispatch contract is testable with telemetry on."""
        self.dispatch_syncs += 1
        return _HOST_FETCH(tree)

    def _ingest(self, host_ring, on_telemetry=None):
        """Account a fetched (host-side) ring: collect events, track loss."""
        events, lost = obs_trace.drain(host_ring)
        self.trace_lost += lost
        if len(events):
            self._events.append(events)
            if on_telemetry is not None:
                on_telemetry(events)

    def drain_telemetry(self, on_telemetry=None):
        """Fetch + reset the device trace rings; returns the drained batch.

        For the host-loop backends this *is* a device sync, so ``run``
        calls it only at ``check_every`` boundaries (where ``done()``
        already syncs) and at the end; the fused megaloop never calls it —
        its drain piggybacks on the dispatch fetch (``_fetch``).  No-op
        (empty batch) when tracing is disabled.
        """
        if self.obs is None:
            return np.empty(0, obs_trace.EVENT_DTYPE)
        if self._list_mode:
            ring = jax.tree.map(
                lambda *v: jnp.stack(v), *[st["trace"] for st in self._states_l])
            host = _HOST_FETCH(ring)
            self._states_l = [
                {**st, "trace": obs_trace.reset(st["trace"])}
                for st in self._states_l
            ]
        else:
            ring = self.states["trace"]
            host = _HOST_FETCH(ring)
            self.states = {**self.states, "trace": obs_trace.reset(ring)}
        before = len(self._events)
        self._ingest(host, on_telemetry)
        return self._events[-1] if len(self._events) > before \
            else np.empty(0, obs_trace.EVENT_DTYPE)

    def trace_events(self):
        """All telemetry drained so far, one structured array
        (obs.trace.EVENT_DTYPE).  Batches are time-sorted per drain;
        export.to_chrome_trace handles global ordering."""
        if not self._events:
            return np.empty(0, obs_trace.EVENT_DTYPE)
        return np.concatenate(self._events)

    def run(self, max_rounds: int = 10_000, check_every: int = 4,
            fused: bool | None = None, rounds_per_dispatch: int = 256,
            on_telemetry=None):
        """Run to completion; returns (rounds, host_seconds).

        ``vmap``/``shard_map`` default to the device-resident megaloop
        (``fused=True``): up to ``rounds_per_dispatch`` rounds execute per
        host dispatch inside one jitted ``lax.while_loop`` that checks the
        termination predicate and overflow watermarks on-device at every
        ``check_every``-th round — bit-identical results, ``rounds_run``,
        and overflow errors to per-round execution (``fused=False``), the
        host just syncs ~K× less often.  ``sequential``/``threads`` always
        run the honest per-round host loop (they are the host-scheduling
        baselines; see docs/architecture.md) with the fused done-reducer.

        ``on_telemetry`` (requires ``obs``) receives each drained batch of
        trace events (np structured array) as it reaches the host — once
        per fused dispatch, or at ``check_every`` boundaries on the
        host-loop paths.  The fused drain piggybacks on the existing
        dispatch sync (the flags tuple and the ring travel in ONE
        ``jax.device_get``), so telemetry adds zero extra device syncs.
        """
        t0 = _time.perf_counter()
        self._require_open()
        if self._finished:
            # re-entry on a finished controller: termination is final
            # (platform.termination_flags — with an empty buffer and all
            # neurons subthreshold, idling can never un-idle), so a second
            # run() must be free.  The megaloop body unconditionally executes
            # one round before its first check, and the per-round path rounds
            # before checking too — without this short-circuit a re-entered
            # run would burn a dispatch, mutate rounds_run/dispatches, and
            # re-walk the watermark checks.  The serving loop
            # (serve/snn_serve.py) calls run() repeatedly, so this is load
            # bearing, not cosmetic.
            return self.rounds_run, _time.perf_counter() - t0
        if rounds_per_dispatch < 1:
            raise ValueError("rounds_per_dispatch must be >= 1")
        if fused is None:
            fused = self.backend in ("vmap", "shard_map")
        if fused and self.backend in ("vmap", "shard_map"):
            mega = self._vmap_mega if self.backend == "vmap" else self._shard_mega
            done = over = False
            ran = 0
            while ran < max_rounds:
                k = min(rounds_per_dispatch, max_rounds - ran)
                self.states, self.pending, i, d, o = mega(
                    self.states, self.pending,
                    jnp.int32(ran), jnp.int32(k), jnp.int32(check_every),
                )
                self.dispatches += 1
                # the one host sync per dispatch: scalars AND the telemetry
                # ring come back in a single transfer
                if self.obs is None:
                    i, d, o = self._fetch((i, d, o))
                else:
                    i, d, o, ring = self._fetch(
                        (i, d, o, self.states["trace"]))
                    self._ingest(ring, on_telemetry)
                    self.states = {**self.states,
                                   "trace": obs_trace.reset(self.states["trace"])}
                i = int(i)
                ran += i
                self.rounds_run += i
                done, over = bool(d), bool(o)
                if done or over:
                    break
            if over or not done:
                # a watermark tripped at a check point, or the loop exhausted
                # max_rounds without the predicate ever seeing the last rounds
                self._check_overflow()
            self._finished = done and not over
        else:
            for r in range(max_rounds):
                self.round()
                if (r + 1) % check_every == 0:
                    try:
                        finished = self.done()
                    finally:
                        # drain even when done() raises on a watermark, so
                        # the telemetry preceding a crash is preserved —
                        # same guarantee as the fused path (which drains on
                        # the dispatch fetch before re-raising)
                        if self.obs is not None:
                            self.drain_telemetry(on_telemetry)
                    if finished:
                        self._finished = True
                        break
            else:
                self._check_overflow()  # done() may never have seen the last rounds
            if self.obs is not None:
                self.drain_telemetry(on_telemetry)
        self.block_until_ready()
        return self.rounds_run, _time.perf_counter() - t0

    # ------------------------------------------------------------------
    def result_states(self):
        """Stacked (S, ...) states regardless of backend."""
        return self._stacked()

    def sim_time(self):
        return np.asarray(self._stacked()["time"])

    def stats(self):
        """Historical coarse stats dict — a back-compat shim over the typed
        metrics registry (obs/metrics.py ``legacy_stats``); prefer
        ``metrics()`` for new code."""
        from repro.obs import metrics as obs_metrics

        return obs_metrics.legacy_stats(self._stacked())

    def metrics(self):
        """Typed metrics snapshot: ``{name: ndarray}`` over every metric in
        the obs/metrics.py registry (counters, gauges, histograms —
        including the channel occupancy/routed counters the stats() dict
        never exposed)."""
        from repro.obs import metrics as obs_metrics

        return obs_metrics.collect(self._stacked(), self._pending_stacked())


def overflow_error(states, pending, *, in_cap: int, out_cap: int,
                   store_log: int, drop: bool = False):
    """The detailed watermark error message, or ``None`` when clean.

    Loud overflow sentinels: merge_pending and the segment step keep sticky
    high-water marks of the capacity they needed; past-cap messages are
    silently lost (bulk appends/merges truncate, single appends clip onto
    the last slot), so any watermark beyond capacity means messages were
    dropped at some point — even if the box drained since.  Under graceful
    degradation (``faults.FaultConfig(on_overflow="drop")``, ``drop=True``)
    inbox/outbox overflow is counted spike loss, not an abort — only the
    program-bug flags (store log, late MMIO) stay fatal.

    Module-level so both raisers share one formatter: ``Controller``
    (fused and per-round paths — messages stay byte identical) and the
    serving job axis (serve/snn_serve.py converts a job's flag into a
    per-request error against the job's OWN caps instead of killing the
    bucket).
    """
    watermark = np.asarray(pending["max_count"])
    if not drop and (watermark > in_cap).any():
        return (
            "pending inbox overflow: "
            f"{Controller._flag_detail('inbox', watermark, in_cap, 'in_cap')}; "
            "raise in_cap (builder kwarg) or thin the workload's traffic"
        )
    out_peak = np.asarray(states["stats"]["outbox_peak"])
    if not drop and (out_peak > out_cap).any():
        return (
            "outbox overflow: "
            f"{Controller._flag_detail('outbox', out_peak, out_cap, 'out_cap')}; "
            "raise out_cap (builder kwarg) or thin the workload's traffic"
        )
    store_peak = np.asarray(states["stats"]["store_peak"])
    if (store_peak > store_log).any():
        return (
            "DRAM store-log overflow: "
            f"{Controller._flag_detail('store_log', store_peak, store_log, 'store_log')}"
            " stores in one quantum; raise store_log "
            "(builder kwarg) or shrink the quantum"
        )
    mmio_late = np.asarray(states["stats"]["snn_mmio_late"])
    if (mmio_late > 0).any():
        return (
            "late SNN MMIO ops: "
            f"{Controller._flag_detail('snn_mmio_late', mmio_late, 0)}: a "
            "CIM_REG_SPIKE store executed at/after its target tick's grid "
            "time, or a CIM_REG_COUNTS readback was served after the unit "
            "ticked past the requested count — the result would depend on "
            "round timing, not the tick grid.  Issue the op earlier in "
            "the program, or raise tick_period (builder kwarg) so the "
            "injection window covers it"
        )
    return None


# ---------------------------------------------------------------------------
# job-axis megaloop (fleet serving — serve/snn_serve.py)
#
# The Controller vmaps over the *segments of one platform*; serving stacks a
# second leading axis of J independent platforms ("jobs") and runs them all
# inside ONE device-resident while_loop.  The jobs share a compiled shape
# (one VPConfig) but carry their own rasters, weights, fault seeds/masks and
# trace rings in the stacked state.
#
# vmap-of-while_loop would be wrong here: JAX batches a while_loop by running
# the body while ANY lane's cond holds, WITHOUT masking the finished lanes —
# a done job would keep mutating.  So the job loop is a single while_loop
# whose carry holds per-job (done, over, rounds) vectors and freezes finished
# jobs functionally: every state/pending leaf is `where(active, new, old)`.
# A frozen job's final state is its state at the first check round that saw
# it done — the same round its solo run stops at — so batched results are
# bit-identical to solo runs under the same (r0, check_every) cadence.
#
# Per-job caps ride as (J,) traced operands into the vmapped termination
# flags (platform.job_termination_flags): a cap-padded bucket (physical boxes
# sized to the bucket maximum) still trips each job's watermark against its
# OWN cap, at the same check round as solo.

_JOB_FN_CACHE: dict = {}  # (cfg, quantum, obs) -> jitted batched megaloop


def _job_megaloop(cfg, quantum, obs):
    step = pf.make_segment_step(cfg, quantum, obs)
    s = cfg.n_segments
    lat = cfg.latency_matrix()
    big = jnp.int32(2**30)

    def limits(times):
        tl = times[:, None] + lat
        tl = jnp.where(jnp.eye(s, dtype=bool), big, tl)
        lim = tl.min(axis=0)
        if s == 1:
            lim = times + quantum
        return lim

    def vmap_round(states, pending):
        lim = limits(states["time"])
        states, outboxes, pending = jax.vmap(step)(states, pending, lim)
        fresh = ch.route(outboxes, lat, cfg.in_cap)
        pending = jax.vmap(ch.merge_pending)(pending, fresh)
        return states, pending

    job_round = jax.vmap(vmap_round)

    def mega(states, pending, rounds, done, over,
             in_cap, out_cap, store_log, r0, k, check_every):
        """One dispatch of the batched job loop.

        ``states``/``pending`` are (J, S, ...) stacks; ``rounds``/``done``/
        ``over`` are the (J,) per-job carries from the previous dispatch
        (zeros/False for a fresh batch — padding lanes enter with
        ``done=True`` and are frozen from the first round); the caps are
        (J,) int32 per-job capacities.  ``r0`` is the shared round count of
        the still-active jobs (active jobs are lockstep: they have all been
        active since round 0, so they share one cadence) and ``check_every``
        the check period, exactly as in ``Controller.run``.  Returns
        ``(states, pending, rounds, done, over)``; the scalar iteration
        count stays internal so the sharded variant's outputs are all
        per-job.
        """

        def cond(carry):
            _st, _pen, i, _r, done, over = carry
            return jnp.any(~(done | over)) & (i < k)

        def body(carry):
            st, pen, i, rounds, done, over = carry
            active = ~(done | over)

            def freeze(new, old):
                keep = lambda n, o: jnp.where(
                    active.reshape((-1,) + (1,) * (n.ndim - 1)), n, o)
                return jax.tree.map(keep, new, old)

            st_n, pen_n = job_round(st, pen)
            st, pen = freeze(st_n, st), freeze(pen_n, pen)
            rounds = rounds + active.astype(jnp.int32)
            i = i + 1
            at_check = ((r0 + i) % check_every) == 0

            def checked(_):
                d, in_o, out_o, st_o, late, _tr = pf.job_termination_flags(
                    st, pen, in_cap, out_cap, store_log)
                # same policy split as the solo megaloop: under the
                # graceful-degradation overflow policy the channel
                # watermarks are counted loss, not aborts
                if cfg.faults is not None and cfg.faults.drop_overflow:
                    o = st_o | late
                else:
                    o = in_o | out_o | st_o | late
                return d & ~o, o

            d, o = jax.lax.cond(
                at_check, checked,
                lambda _: (jnp.zeros_like(done), jnp.zeros_like(over)), None)
            done = done | (active & d)
            over = over | (active & o)
            return st, pen, i, rounds, done, over

        st, pen, _i, rounds, done, over = jax.lax.while_loop(
            cond, body, (states, pending, jnp.int32(0), rounds, done, over))
        return st, pen, rounds, done, over

    return mega


def job_mega_fn(cfg, quantum: int = 10_000, obs=None):
    """Cached jitted job-axis megaloop for ``cfg`` (single device).

    The jit retraces per batch size J, so one cache entry serves every
    bucket size of a workload shape — same lifetime story as
    ``_FN_CACHE``.
    """
    key = (cfg, quantum, obs)
    if key not in _JOB_FN_CACHE:
        _JOB_FN_CACHE[key] = jax.jit(
            _job_megaloop(cfg, quantum, obs), donate_argnums=(0, 1))
    return _JOB_FN_CACHE[key]


def sharded_job_mega_fn(cfg, mesh, quantum: int = 10_000, obs=None,
                        axis: str = "jobs"):
    """The job megaloop fanned across ``mesh`` devices over the job axis.

    Each device runs the batched while_loop on its local job shard
    independently — there are no collectives inside a round (routing is
    within-platform), so a device whose jobs all finish exits its loop
    early while the others keep running.  J must divide the mesh axis
    (the server pads buckets with inert ``done=True`` lanes to arrange
    that).  Mesh-dependent, so per-call rather than in the global cache —
    mirrors Controller's per-instance ``_shard_mega``.
    """
    from jax.sharding import PartitionSpec as P

    mega = _job_megaloop(cfg, quantum, obs)
    job = P(axis)
    rep = P()
    fn = jax.shard_map(
        mega, mesh=mesh,
        in_specs=(job, job, job, job, job, job, job, job, rep, rep, rep),
        out_specs=(job, job, job, job, job),
        check_vma=False,
    )
    return jax.jit(fn, donate_argnums=(0, 1))
