"""Pallas TPU kernel: bit-sliced memristor-crossbar VMM.

TPU adaptation of the analog pipeline (DESIGN.md §2): the 256×256 crossbar
maps onto 2×2 MXU-aligned 128×128 tiles held in VMEM; the DAC's bit-serial
drive becomes ``in_res`` per-slice int matmuls accumulated with shift-add in
an fp32/int32 VMEM scratch; the ADC is a saturating clamp on the way out.

Grid: (batch_tiles, row_tiles) — each program instance owns a (TILE_B,
TILE_R) block of outputs and loops the full contraction (C) and the bit
slices in registers/VMEM.  Block shapes are multiples of (8, 128) so both
the MXU contraction (K = C) and the lane dimension stay hardware-aligned.

Validated in interpret mode against ref.py (tests/test_kernels.py sweeps
shapes, resolutions and dtypes with hypothesis), compiled for a described
TPU v5e by tests/test_tpu_compile.py, and checked bit-equal to ref.py on the
chip by chip_smoke.py.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

TILE_B = 8  # batch (input vectors) per program
TILE_R = 128  # output rows per program


def _kernel(x_ref, w_ref, o_ref, *, in_res: int, out_res: int):
    """x (TILE_B, C) int32; w (C, TILE_R) int8 -> o (TILE_B, TILE_R) int32."""
    x = x_ref[...]
    w = w_ref[...].astype(jnp.float32)
    _vmm_body(x, w, o_ref, in_res, out_res)


def _kernel_faults(x_ref, w_ref, a_ref, f_ref, o_ref, *, in_res: int,
                   out_res: int):
    """Fault-injecting variant (repro.faults): the crossbar reads through
    the AND/XOR masks — ``(w & a) ^ f`` in int8 before the fp32 promotion —
    modeling stuck-at / bit-flip / row / column failures at read time.
    a/f (C, TILE_R) int8; neutral masks (a = -1, f = 0) reproduce
    ``_kernel`` bit-exactly."""
    x = x_ref[...]
    w = ((w_ref[...] & a_ref[...]) ^ f_ref[...]).astype(jnp.float32)
    _vmm_body(x, w, o_ref, in_res, out_res)


def _vmm_body(x, w, o_ref, in_res: int, out_res: int):
    lo = -(1 << (in_res - 1))
    hi = (1 << (in_res - 1)) - 1
    xq = jnp.clip(x, lo, hi)
    sign = jnp.sign(xq).astype(jnp.float32)
    mag = jnp.abs(xq)
    acc = jnp.zeros(o_ref.shape, jnp.float32)
    for k in range(in_res):  # bit-serial DAC drive
        plane = ((mag >> k) & 1).astype(jnp.float32) * sign
        # MXU matmul per slice; shift-add (S+H) accumulation
        acc = acc + jax.lax.dot(plane, w, preferred_element_type=jnp.float32) * float(1 << k)
    hi_out = float((1 << (out_res - 1 + 8)) - 1)
    acc = jnp.clip(acc, -hi_out - 1.0, hi_out)  # ADC saturation
    o_ref[...] = acc.astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=("in_res", "out_res", "interpret"))
def crossbar_vmm_tiles(x, weights, in_res: int = 8, out_res: int = 8,
                       f_and=None, f_xor=None, *, interpret: bool):
    """x (B, C) int32, weights int8 (R, C) -> (B, R) int32.

    B and R are padded to tile multiples; C (the contraction) stays whole —
    a 256-deep contraction fits VMEM comfortably (256×128 int8 = 32 KB/tile).

    ``f_and`` / ``f_xor`` (int8 (R, C), optional, repro.faults): crossbar
    read-time fault masks, padded and transposed exactly like the weights;
    None runs the unfaulted kernel unchanged.
    """
    b, c = x.shape
    r = weights.shape[0]
    bp = -(-b // TILE_B) * TILE_B
    rp = -(-r // TILE_R) * TILE_R
    xp = jnp.pad(x, ((0, bp - b), (0, 0)))
    pad_w = lambda w: jnp.pad(w, ((0, rp - r), (0, 0))).T  # (C, Rp)
    wp = pad_w(weights)

    grid = (bp // TILE_B, rp // TILE_R)
    w_spec = pl.BlockSpec((c, TILE_R), lambda i, j: (0, j))
    in_specs = [pl.BlockSpec((TILE_B, c), lambda i, j: (i, 0)), w_spec]
    operands = [xp, wp]
    kernel = _kernel
    if f_and is not None:
        kernel = _kernel_faults
        in_specs += [w_spec, w_spec]
        operands += [pad_w(f_and), pad_w(f_xor)]
    out = pl.pallas_call(
        functools.partial(kernel, in_res=in_res, out_res=out_res),
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((TILE_B, TILE_R), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((bp, rp), jnp.int32),
        interpret=interpret,
    )(*operands)
    return out[:b, :r]
