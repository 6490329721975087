"""Device busy time per simulated round, in ms: the union of the device's
operation intervals in the traced window (averaged over the chips) over
the megaloop rounds run in it."""


def read(ctx):
    if not ctx.rounds or not any(ctx.trace.ops):
        return None
    return 1000.0 * ctx.trace.busy_s() / ctx.rounds
