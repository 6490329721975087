"""Operations and bytes of the kernels' calls, and the peaks table."""
from __future__ import annotations

import pytest

from bench import kernels


def test_crossbar_vmm_counts_one_bit_serial_vmm_per_unit():
    ops, nbytes = kernels.crossbar_vmm(1, 256, 256, in_res=8)
    assert ops == 2 * 256 * 256 * 8
    assert nbytes == 256 * 256 + 4 * 256 + 4 * 256
    assert kernels.crossbar_vmm(4) == (4 * ops, 4 * nbytes)


def test_lif_step_counts_one_contraction_per_unit():
    ops, nbytes = kernels.lif_step(1, 256, 256)
    assert ops == 2 * 256 * 256
    assert nbytes == 256 * 256 + 4 * 256 + 24 * 256 + 12
    assert kernels.lif_step(3) == (3 * ops, 3 * nbytes)


def test_least_time_names_the_binding_bound():
    peak = kernels.peaks("TPU v5 lite")
    t, bound = kernels.least_time(*kernels.lif_step(64), peak)
    assert bound == "memory"
    assert t == pytest.approx(kernels.lif_step(64)[1]
                              / peak["hbm_bytes_per_s"])
    t, bound = kernels.least_time(1e15, 1.0, peak)
    assert bound == "compute"
    assert t == pytest.approx(1e15 / peak["int8_ops_per_s"])


def test_roofline_pct_is_none_without_kernel_time():
    peak = kernels.peaks("TPU v5 lite")
    assert kernels.roofline_pct(1, 1, 0.0, peak) is None
    assert kernels.roofline_pct(0, 819e9, 2.0, peak) == pytest.approx(50.0)


def test_peaks_table_has_v5e_and_refuses_others():
    peak = kernels.peaks("TPU v5 lite")
    assert peak["bf16_flops_per_s"] == 197e12
    assert peak["int8_ops_per_s"] == 393e12
    assert peak["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError, match="cpu"):
        kernels.peaks("cpu")
