"""Profiler-facing names: the phases of a megaloop round and the
controller's host spans, in the profiler's own trace.

The trace rings of ``obs/trace.py`` stamp events in *simulated* cycles of
the modelled platform.  The names here live on the host and device clock
of a ``jax.profiler`` capture instead:

* **phases** — one ``jax.named_scope`` per phase of a round (``phase``),
  so every device operation of the compiled megaloop carries its phase in
  the ``op_name`` of its HLO metadata.  Scopes are metadata only: the
  optimized program is the same with or without them.
* **host spans** — ``jax.profiler.TraceAnnotation`` around the
  controller's host work (``span``), each with the controller's serial
  number and the dispatch number as arguments.

A profiler trace names each device operation by its HLO instruction, not
its metadata; ``controller.dispatched_megaloop_hlo()`` gives the compiled
text that maps one to the other.  See docs/observability.md, "Profiler
spans".
"""
from __future__ import annotations

import jax

# one per phase of a round; every device operation of a round falls under
# exactly one (the innermost, where they nest)
PHASES = (
    "vp.inbox",         # consume the pending inbox (+ DRAM read responses)
    "vp.slot_scan",     # RISC-V instruction slots; passive time advance
    "vp.store_replay",  # DRAM store-log replay
    "vp.cim_op",        # dense CIM completion (crossbar_vmm)
    "vp.lif_tick",      # LIF integration (lif_step)
    "vp.aer_out",       # AER spike emission over the units' fan-out
    "vp.readback",      # spike-count readback (hybrid jobs)
    "vp.stats",         # counters and watermarks
    "vp.telemetry",     # obs trace-ring lanes and append (obs=None: none)
    "vp.limits",        # the decoupling bound
    "vp.route",         # outboxes -> fresh inboxes
    "vp.merge",         # fresh inboxes into the pending boxes
    "vp.check",         # loop counter, termination predicate, watermarks
    "vp.freeze",        # job loop: finished lanes keep their state
)

# host spans of core/controller.py, parent first
SPANS = {
    "vp.controller.init": None,
    "vp.controller.copy": "vp.controller.init",
    "vp.controller.mmio_check": "vp.controller.init",
    "vp.controller.fn_cache": "vp.controller.init",
    "vp.run": None,
    "vp.dispatch": "vp.run",
    "vp.sync": "vp.run",
    "vp.overflow_check": "vp.run",
    "vp.block": "vp.run",
}


def phase(name: str):
    """The ``jax.named_scope`` of phase ``name`` (one of ``PHASES``)."""
    assert name in PHASES, name
    return jax.named_scope(name)


def span(name: str, **args):
    """The host span ``name`` (one of ``SPANS``); ``args`` (controller
    serial, dispatch number) ride as the annotation's arguments.  Off the
    profiler it costs one TraceMe construction."""
    assert name in SPANS, name
    return jax.profiler.TraceAnnotation(name, **args)
