"""Operations and bytes of each Pallas kernel's call, from its shapes, and
the roofline arithmetic over the peaks table (``peaks.json``).

The counts are the algorithm's, not the implementation's: padding the
kernel adds (rows to tile multiples, the crossbar's input batch to 8) is
not work.  Both kernels contract int8 conductances with small integers, so
the compute bound is the chip's int8 peak.
"""
from __future__ import annotations

import json
from pathlib import Path

PEAKS = Path(__file__).resolve().parent / "peaks.json"


def peaks(device_kind: str) -> dict:
    """The peaks of ``device_kind``; a device missing from the table is an
    error, never a default."""
    table = json.loads(PEAKS.read_text())
    if device_kind not in table:
        raise KeyError(f"device kind {device_kind!r} is not in {PEAKS.name} "
                       f"(known: {sorted(table)})")
    return table[device_kind]


def crossbar_vmm(units: int, rows: int = 256, cols: int = 256,
                 in_res: int = 8):
    """One bit-serial VMM per unit: ``in_res`` input bit planes, each a
    rows x cols multiply-accumulate; reads the int8 crossbar and an int32
    input vector, writes an int32 output vector."""
    ops = 2 * units * rows * cols * in_res
    nbytes = units * (rows * cols + 4 * cols + 4 * rows)
    return ops, nbytes


def lif_step(units: int, rows: int = 256, cols: int = 256):
    """One LIF tick per unit: a rows x cols contraction of the int8
    synapses with the int32 spike counts; reads membrane, refractory
    counter and the merged charge of the other column tiles, writes
    membrane, refractory counter and spikes (int32 each), and reads three
    int32 parameters."""
    ops = 2 * units * rows * cols
    nbytes = units * (rows * cols + 4 * cols + 4 * 3 * rows
                      + 4 * 3 * rows + 4 * 3)
    return ops, nbytes


def least_time(ops: float, nbytes: float, peak: dict):
    """(seconds, bound): the larger of ops over the int8 peak and bytes over
    HBM bandwidth, and which of the two it is ("compute" or "memory")."""
    t_ops = ops / peak["int8_ops_per_s"]
    t_mem = nbytes / peak["hbm_bytes_per_s"]
    return (t_ops, "compute") if t_ops >= t_mem else (t_mem, "memory")


def roofline_pct(ops: float, nbytes: float, kernel_s: float, peak: dict):
    """Share of the roofline, in %, of work that took ``kernel_s`` seconds
    of device time; None where no kernel time was seen."""
    if kernel_s <= 0:
        return None
    return 100.0 * least_time(ops, nbytes, peak)[0] / kernel_s
