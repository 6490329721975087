"""Pallas TPU kernel: fused LIF neuron-pool step.

One program instance owns one unit's (TILE_R)-neuron block: the synaptic
contraction (spike-count vector × int8 synapse tile) runs on the MXU in
fp32 (spike counts ≤ fan-in and |w| ≤ 127 keep the accumulator far inside
fp32's exact-integer range), then leak / threshold / reset / refractory all
happen element-wise on the VPU without the membrane state ever leaving
VMEM.  This fusion is the point: the eager formulation materialises three
(U, R) intermediates per tick; the kernel writes only the new state.

Grid: (units, row_tiles).  Weights arrive pre-transposed (U, C, R) so the
contraction is a plain (1, C) × (C, TILE_R) dot per block.  Every per-unit
operand carries a singleton middle axis — spikes (U, 1, C), neuron tiles
(U, 1, R), the LIF parameters thresh/leak/refrac_period (U, 1, 1) — and the
unit axis is squeezed out of each block, so every block's last two
dimensions are either whole array dimensions or (1, 128)-multiples, as the
TPU lowering requires.

Validated in interpret mode against ref.py (tests/test_snn.py sweeps shapes
and parameters; int32-exactness makes equality bit-strict), compiled for a
described TPU v5e by tests/test_tpu_compile.py, and checked bit-equal to
ref.py on the chip by chip_smoke.py.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.lif_step.ref import SPIKE_SAT

TILE_R = 128  # neurons per program (lane-aligned)


def _contract(s, w):
    """(1, C) fp32 spike counts × (C, TILE_R) fp32 weights -> int32 charge.

    Counts reach SPIKE_SAT = 511, which bf16 cannot hold exactly, so the dot
    is pinned to full fp32 precision.  At the default precision a TPU v5e
    rounds the operands to bf16, and the charge then differs from ref.py's
    int32 contraction (chip_smoke.py's kernel phase checks bit-equality)."""
    return jax.lax.dot(s, w, precision=jax.lax.Precision.HIGHEST,
                       preferred_element_type=jnp.float32).astype(jnp.int32)


def _kernel(s_ref, w_ref, x_ref, v_ref, r_ref, th_ref, lk_ref, rp_ref,
            vo_ref, ro_ref, so_ref):
    """s (1, C) int32; w (C, TILE_R) int8; x (1, TILE_R) int32 extra
    charge; v/r (1, TILE_R) int32; th/lk/rp (1, 1) int32
    -> v'/r'/fired (1, TILE_R) int32."""
    # fan-in saturation (mirrors ref.py): bounds the accumulator inside
    # fp32's exact-integer range so the MXU contraction never rounds
    s = jnp.clip(s_ref[...], -SPIKE_SAT, SPIKE_SAT).astype(jnp.float32)
    syn = _contract(s, w_ref[...].astype(jnp.float32))
    # extra charge from the layer's other column tiles (wide multi-crossbar
    # layers): already int32-exact, summed after the local contraction
    syn = syn + x_ref[...]
    v = v_ref[...]
    refrac = r_ref[...]
    thresh, leak, rp = th_ref[...], lk_ref[...], rp_ref[...]
    active = refrac == 0
    v1 = jnp.maximum(v + jnp.where(active, syn, 0) - leak, 0)
    fired = active & (v1 >= thresh)
    vo_ref[...] = jnp.where(fired, 0, v1)
    ro_ref[...] = jnp.where(fired, rp, jnp.maximum(refrac - 1, 0))
    so_ref[...] = fired.astype(jnp.int32)


def _kernel_faults(s_ref, w_ref, a_ref, f_ref, x_ref, v_ref, r_ref, th_ref,
                   lk_ref, rp_ref, dd_ref, dt_ref, vo_ref, ro_ref, so_ref):
    """Fault-injecting variant of ``_kernel`` (repro.faults): the crossbar
    reads through the AND/XOR masks — ``(w & a) ^ f`` in int8 before the
    fp32 promotion — dead lanes (dd != 0) are gated out of integration and
    firing with the membrane pinned to 0, and the threshold drifts per
    neuron (``max(th + dt, 1)``).  Same VMEM-resident fusion; neutral
    masks (a = -1, f = 0, dd = dt = 0) reproduce ``_kernel`` bit-exactly,
    which is what lets one variant serve every fault-family combination.

    a/f (C, TILE_R) int8; dd/dt (1, TILE_R) int32; rest as ``_kernel``.
    """
    s = jnp.clip(s_ref[...], -SPIKE_SAT, SPIKE_SAT).astype(jnp.float32)
    w = (w_ref[...] & a_ref[...]) ^ f_ref[...]  # (C, TILE_R)
    syn = _contract(s, w.astype(jnp.float32))
    syn = syn + x_ref[...]
    v = v_ref[...]
    refrac = r_ref[...]
    thresh, leak, rp = th_ref[...], lk_ref[...], rp_ref[...]
    dead = dd_ref[...] != 0
    active = (refrac == 0) & ~dead
    th_eff = jnp.maximum(thresh + dt_ref[...], 1)
    v1 = jnp.maximum(v + jnp.where(active, syn, 0) - leak, 0)
    fired = active & (v1 >= th_eff)
    vo_ref[...] = jnp.where(dead, 0, jnp.where(fired, 0, v1))
    ro_ref[...] = jnp.where(fired, rp, jnp.maximum(refrac - 1, 0))
    so_ref[...] = fired.astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=("interpret",))
def lif_step_tiles(weights, spikes, v, refrac, thresh, leak, refrac_period,
                   extra=None, f_and=None, f_xor=None, dead=None, dth=None,
                   *, interpret: bool):
    """weights (U, R, C) int8; spikes (U, C) int32; v/refrac (U, R) int32;
    thresh/leak/refrac_period (U,) int32; extra (U, R) int32 or None
    -> (v', refrac', fired) each (U, R).

    R is padded to the tile multiple; C (the contraction) stays whole — a
    256-deep fan-in fits VMEM comfortably (256×128 int8 = 32 KB/tile).

    Fault inputs (repro.faults, all optional): f_and/f_xor int8 (U, R, C)
    crossbar read masks, dead bool (U, R), dth int32 (U, R).  When any is
    given the fault kernel variant runs with neutral values substituted
    for the absent ones (bit-identical semantics for those stages); when
    all are None the original kernel runs untouched.
    """
    u, r, c = weights.shape
    rp_pad = -(-r // TILE_R) * TILE_R
    pad_w = lambda x: jnp.pad(
        x, ((0, 0), (0, rp_pad - r), (0, 0))).transpose(0, 2, 1)  # (U, C, Rp)
    wt = pad_w(weights)
    pad_r = lambda x: jnp.pad(x, ((0, 0), (0, rp_pad - r)))
    vp, rfp = pad_r(v), pad_r(refrac)
    if extra is None:
        extra = jnp.zeros((u, r), jnp.int32)
    xp = pad_r(extra.astype(jnp.int32))
    # padded neurons must never fire: give the pad lanes an unreachable
    # threshold by masking v to 0 (thresh >= 1 contract) — v pad is 0 and
    # syn pad is 0 (zero weights + zero extra), so fired_pad = (0 >= thresh)
    # = False.  (Fault pads are neutral-0: masked pad weight is
    # (0 & 0) ^ 0 = 0 and dth pad 0 keeps th_eff = thresh >= 1.)

    grid = (u, rp_pad // TILE_R)
    # singleton middle axis on every per-unit operand; the squeezed unit
    # axis (None) keeps each block's last two dims TPU-tileable
    row = lambda x: x.reshape(u, 1, -1)
    tile_spec = pl.BlockSpec((None, 1, TILE_R), lambda i, j: (i, 0, j))
    unit_spec = pl.BlockSpec((None, 1, 1), lambda i, j: (i, 0, 0))
    w_spec = pl.BlockSpec((None, c, TILE_R), lambda i, j: (i, 0, j))
    in_specs = [
        pl.BlockSpec((None, 1, c), lambda i, j: (i, 0, 0)),
        w_spec,
        tile_spec, tile_spec, tile_spec,
        unit_spec, unit_spec, unit_spec,
    ]
    operands = [row(spikes), wt, row(xp), row(vp), row(rfp), row(thresh),
                row(leak), row(refrac_period)]
    kernel = _kernel
    if any(x is not None for x in (f_and, f_xor, dead, dth)):
        kernel = _kernel_faults
        fa = pad_w(jnp.full((u, r, c), -1, jnp.int8) if f_and is None
                   else f_and)
        fx = pad_w(jnp.zeros((u, r, c), jnp.int8) if f_xor is None else f_xor)
        dd = pad_r(jnp.zeros((u, r), jnp.int32) if dead is None
                   else dead.astype(jnp.int32))
        dt = pad_r(jnp.zeros((u, r), jnp.int32) if dth is None
                   else dth.astype(jnp.int32))
        in_specs = in_specs[:2] + [w_spec, w_spec] + in_specs[2:] + \
            [tile_spec, tile_spec]
        operands = operands[:2] + [fa, fx] + operands[2:] + [row(dd), row(dt)]
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=[tile_spec, tile_spec, tile_spec],
        out_shape=[jax.ShapeDtypeStruct((u, 1, rp_pad), jnp.int32)] * 3,
        interpret=interpret,
    )(*operands)
    return tuple(o[:, 0, :r] for o in out)
