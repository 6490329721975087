"""Share of its roofline that ``lif_step`` reaches, in %: the least time
the chip could take for the work of every call in the traced window
(``bench/kernels.py``, from the call's shapes) over the kernel's device
time."""
from bench import kernels, trace_reduce


def read(ctx):
    return trace_reduce.kernel_roofline(ctx, "lif_step_tiles",
                                        kernels.lif_step)
