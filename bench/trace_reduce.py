"""Reduce a profiler trace of the window to the numbers the per-layer
readers need.

``load`` reads the ``.xplane.pb`` that ``jax.profiler`` wrote.  Device
planes are ``/device:TPU:<i>``; each has a line of the XLA operations that
ran on it, and busy time is the union of their intervals, clipped to the
window.  The window is the harness's own host span ``bench.window``, on the
same clock, or ``bench.traced`` where the traffic file bounds the traced
slice of the window.  Host spans named ``bench.*`` say what the harness
was doing while the device idled.
"""
from __future__ import annotations

import dataclasses
from pathlib import Path

import numpy as np

OPS_LINE = "XLA Ops"
WINDOW_SPAN = "bench.window"
SLICE_SPAN = "bench.traced"


@dataclasses.dataclass
class Trace:
    """Intervals in ns on one clock.  ``ops[c]``: (name, start, end) of every
    operation of chip ``c``; ``spans``: (name, start, end) of the harness's
    host spans; ``window``: (start, end)."""
    ops: list
    spans: list
    window: tuple

    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) / 1e9

    def _busy_intervals(self, chip: int) -> np.ndarray:
        lo, hi = self.window
        iv = np.array([(max(s, lo), min(e, hi)) for _, s, e in self.ops[chip]
                       if e > lo and s < hi], np.float64).reshape(-1, 2)
        return union(iv)

    def busy_s(self) -> float:
        """Seconds in which an operation ran, averaged over the chips."""
        per_chip = [float(np.sum(np.diff(self._busy_intervals(c), axis=1)))
                    for c in range(len(self.ops))]
        return float(np.mean(per_chip)) / 1e9 if per_chip else 0.0

    def idle_pct(self) -> float | None:
        w = self.window_s()
        if w <= 0 or not any(self.ops):
            return None
        return 100.0 * (1.0 - self.busy_s() / w)

    def kernel_events(self, match) -> list:
        """(chip, name, seconds) of every operation ``match(name)`` accepts."""
        lo, hi = self.window
        return [(c, n, (e - s) / 1e9) for c, ops in enumerate(self.ops)
                for n, s, e in ops if match(n) and s >= lo and e <= hi]

    def top_ops(self, k: int) -> list:
        """The ``k`` operation names that took most device time, in seconds
        summed over the chips."""
        tot: dict = {}
        lo, hi = self.window
        for ops in self.ops:
            for n, s, e in ops:
                if e > lo and s < hi:
                    tot[n] = tot.get(n, 0.0) + (min(e, hi) - max(s, lo)) / 1e9
        return sorted(([n, t] for n, t in tot.items()),
                      key=lambda x: -x[1])[:k]

    def idle_gaps_by_span(self, k: int) -> list:
        """Idle time of chip 0 inside the window, summed by the innermost
        harness span that covers each gap's middle (``host`` where none
        does); the ``k`` largest."""
        if not self.ops:
            return []
        busy = self._busy_intervals(0)
        edges = np.concatenate([[self.window[0]], busy.reshape(-1),
                                [self.window[1]]]).reshape(-1, 2)
        tot: dict = {}
        for s, e in edges:
            if e <= s:
                continue
            mid = (s + e) / 2
            covering = [(se - ss, n) for n, ss, se in self.spans
                        if ss <= mid <= se
                        and n not in (WINDOW_SPAN, SLICE_SPAN)]
            name = min(covering)[1] if covering else "host"
            tot[name] = tot.get(name, 0.0) + (e - s) / 1e9
        return sorted(([n, t] for n, t in tot.items()),
                      key=lambda x: -x[1])[:k]


@dataclasses.dataclass
class Context:
    """What a per-layer reader gets: the trace, the rounds simulated in the
    traced window (None where only a slice of it was traced), the chip's
    peaks, and the units each kernel call covers on one chip."""
    trace: Trace
    rounds: int | None
    peak: dict
    kernel_units: int


def union(iv: np.ndarray) -> np.ndarray:
    """Disjoint, sorted union of (start, end) intervals."""
    if len(iv) == 0:
        return iv.reshape(0, 2)
    iv = iv[np.argsort(iv[:, 0])]
    out = [list(iv[0])]
    for s, e in iv[1:]:
        if s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return np.asarray(out, np.float64)


def from_profile(planes, n_chips: int) -> Trace:
    """Build a Trace from ``jax.profiler.ProfileData`` planes (or any
    objects with the same ``name``/``lines``/``events`` shape)."""
    ops = [[] for _ in range(n_chips)]
    spans, marks = [], {}
    for plane in planes:
        name = plane.name
        if name.startswith("/device:TPU:"):
            chip = int(name.rsplit(":", 1)[1])
            if chip >= n_chips:
                continue
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ops[chip] += [(e.name, e.start_ns, e.end_ns)
                                  for e in line.events]
        elif name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name in (WINDOW_SPAN, SLICE_SPAN):
                        marks[e.name] = (e.start_ns, e.end_ns)
                    if e.name.startswith("bench."):
                        spans.append((e.name, e.start_ns, e.end_ns))
    window = marks.get(SLICE_SPAN, marks.get(WINDOW_SPAN))
    if window is None:
        raise ValueError(f"no {WINDOW_SPAN!r} span in the trace")
    return Trace(ops, spans, window)


def load(trace_dir, n_chips: int) -> Trace:
    """The Trace of the newest ``.xplane.pb`` under ``trace_dir``."""
    import jax

    files = sorted(Path(trace_dir).rglob("*.xplane.pb"),
                   key=lambda p: p.stat().st_mtime)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    data = jax.profiler.ProfileData.from_file(str(files[-1]))
    return from_profile(data.planes, n_chips)


def kernel_roofline(ctx: Context, kernel: str, ops_bytes) -> float | None:
    """Roofline share, in %, of the calls of the Pallas kernel whose entry
    function is ``kernel`` in the window, each doing
    ``ops_bytes(ctx.kernel_units)`` of work; None where the trace holds no
    such call.  A kernel call's operation is named after the jitted
    function that made it (``%vmap_jit_lif_step_tiles___.9 = ...``), since
    neither ``pallas_call`` carries a ``name=``."""
    from bench import kernels

    events = ctx.trace.kernel_events(
        lambda name: kernel in name.split(" = ", 1)[0])
    if not events:
        return None
    ops, nbytes = ops_bytes(ctx.kernel_units)
    return kernels.roofline_pct(ops * len(events), nbytes * len(events),
                                sum(s for _, _, s in events), ctx.peak)
