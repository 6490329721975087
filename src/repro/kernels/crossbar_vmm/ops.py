"""Jit'd public wrappers for the crossbar VMM kernel.

The kernel compiles for the TPU; on the CPU backend (tests) its body runs
under the Pallas interpreter with identical semantics
(``repro.kernels.interpret_mode``, read at trace time).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels import interpret_mode
from repro.kernels.crossbar_vmm.kernel import crossbar_vmm_tiles


def crossbar_vmm(weights, x, in_res: int = 8, out_res: int = 8,
                 f_and=None, f_xor=None):
    """weights int8 (R, C); x int32 (C,) -> int32 (R,); optional crossbar
    fault masks f_and/f_xor int8 (R, C) (repro.faults)."""
    return crossbar_vmm_tiles(x[None, :], weights, in_res, out_res,
                              f_and, f_xor, interpret=interpret_mode())[0]


def crossbar_vmm_batch(weights, x, in_res: int = 8, out_res: int = 8,
                       f_and=None, f_xor=None):
    """Batched over units: weights (U, R, C) int8; x (U, C) int32 -> (U, R).

    ``f_and``/``f_xor`` (int8 (U, R, C), optional): per-unit crossbar fault
    masks — None keeps the unfaulted kernel byte-identical.

    Used by the CIM quantum-boundary completion (vp/cim.py) when the
    platform is built with ``use_kernel=True``.
    """
    return jax.vmap(
        lambda w, v, a, f: crossbar_vmm(w, v, in_res, out_res, a, f)
    )(weights, x, f_and, f_xor)


def crossbar_matmul(weights, x, in_res: int = 8, out_res: int = 8):
    """weights (R, C) int8, x (C, N) int32 -> (R, N) — tiled GEMM form."""
    return crossbar_vmm_tiles(x.T, weights, in_res, out_res,
                              interpret=interpret_mode()).T
