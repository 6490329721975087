"""Share of the device's busy time in the traced window spent under the
phase ``vp.slot_scan`` (the RISC-V instruction-slot scan), in %: the self
time of the megaloop's operations under that scope (``bench/phases.py``)
over the union of all device operations, averaged over the chips."""
from bench import phases


def read(ctx):
    return phases.busy_pct(ctx, "vp.slot_scan")
