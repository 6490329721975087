"""Jit'd public wrappers for the fused LIF step kernel.

The kernel compiles for the TPU; on the CPU backend (tests) its body runs
under the Pallas interpreter with identical semantics
(``repro.kernels.interpret_mode``, read at trace time).
"""
from __future__ import annotations

import jax.numpy as jnp

from repro.kernels import interpret_mode
from repro.kernels.lif_step.kernel import lif_step_tiles


def lif_step_units(weights, spikes, v, refrac, thresh, leak, refrac_period,
                   extra=None, f_and=None, f_xor=None, dead=None, dth=None):
    """Batched over units: weights (U, R, C) int8; spikes (U, C) int32;
    v/refrac (U, R) int32; thresh/leak/refrac_period (U,) int32;
    extra (U, R) int32 or None (merged charge from a wide layer's other
    column tiles) -> (v', refrac', fired) each (U, R) int32.

    ``f_and``/``f_xor``/``dead``/``dth`` are the optional fault-injection
    inputs (repro.faults; see kernel.py) — None selects the unfaulted
    kernel unchanged.

    Used by the spike-mode CIM tick (vp/cim.py) when the platform is built
    with ``use_kernel=True``.
    """
    return lif_step_tiles(weights, spikes, v, refrac, thresh, leak,
                          refrac_period, extra, f_and, f_xor, dead, dth,
                          interpret=interpret_mode())


def lif_step(weights, spikes, v, refrac, thresh, leak, refrac_period):
    """Single pool: weights (R, C) int8, spikes (C,), v/refrac (R,), scalars."""
    to1 = lambda x: jnp.asarray(x, jnp.int32)[None]
    v2, r2, f2 = lif_step_units(
        weights[None], spikes[None], v[None], refrac[None],
        to1(thresh), to1(leak), to1(refrac_period),
    )
    return v2[0], r2[0], f2[0]
