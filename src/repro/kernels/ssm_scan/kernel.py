"""Pallas TPU kernel: chunked selective scan (Mamba-1 recurrence).

Grid: (batch, d_blocks, seq_chunks) with the sequence dimension iterated
*sequentially* (minor-most grid dim on TPU runs on the same core), carrying
the (D_BLOCK, N) state in a VMEM scratch accumulator across chunk steps —
the canonical TPU accumulator pattern.  Within a chunk, a ``fori_loop``
advances the recurrence step by step entirely in VMEM: the (S, D, N)
decay/drive tensors stream through HBM exactly once, instead of the ~4
materialized round-trips of the jnp formulation (the falcon-mamba train
cell's memory-bound roofline term — see EXPERIMENTS.md §Perf).

Validated in interpret mode against ref.py.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

D_BLOCK = 128
CHUNK = 64


def _kernel(da_ref, dbx_ref, c_ref, y_ref, h_ref, *, chunk: int):
    sc = pl.program_id(2)

    @pl.when(sc == 0)
    def _init():
        h_ref[...] = jnp.zeros_like(h_ref)

    da = da_ref[...]  # (1, chunk, D_BLOCK, N)
    dbx = dbx_ref[...]
    c = c_ref[...]  # (1, chunk, N)

    def body(t, carry):
        h = carry
        h = da[0, t] * h + dbx[0, t]
        y = (h * c[0, t][None, :]).sum(axis=1)  # (D_BLOCK,)
        y_ref[0, t, :] = y
        return h

    h = jax.lax.fori_loop(0, chunk, body, h_ref[...])
    h_ref[...] = h


@functools.partial(jax.jit, static_argnames=("interpret",))
def ssm_scan(da, dbx, c, *, interpret: bool):
    """da, dbx (B, S, D, N) fp32; c (B, S, N) fp32 -> y (B, S, D) fp32.

    h0 = 0 (prefill/train); decode uses the O(1) jnp path instead.
    """
    b, s, d, n = da.shape
    assert s % CHUNK == 0 and d % D_BLOCK == 0, (s, d)
    grid = (b, d // D_BLOCK, s // CHUNK)
    return pl.pallas_call(
        functools.partial(_kernel, chunk=CHUNK),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, CHUNK, D_BLOCK, n), lambda bi, di, si: (bi, si, di, 0)),
            pl.BlockSpec((1, CHUNK, D_BLOCK, n), lambda bi, di, si: (bi, si, di, 0)),
            pl.BlockSpec((1, CHUNK, n), lambda bi, di, si: (bi, si, 0)),
        ],
        out_specs=pl.BlockSpec((1, CHUNK, D_BLOCK), lambda bi, di, si: (bi, si, di)),
        out_shape=jax.ShapeDtypeStruct((b, s, d), jnp.float32),
        scratch_shapes=[pltpu.VMEM((D_BLOCK, n), jnp.float32)],
        interpret=interpret,
    )(da, dbx, c)
