"""Jit'd public wrapper for the selective-scan kernel."""
from __future__ import annotations

from repro.kernels import interpret_mode
from repro.kernels.ssm_scan.kernel import ssm_scan as _ssm_scan


def ssm_scan(da, dbx, c):
    """da, dbx (B, S, D, N); c (B, S, N) -> y (B, S, D); h0 = 0."""
    return _ssm_scan(da, dbx, c, interpret=interpret_mode())
