"""Share of the traced window in which no operation ran on the device, in
%, averaged over the chips the cell uses."""


def read(ctx):
    return ctx.trace.idle_pct()
