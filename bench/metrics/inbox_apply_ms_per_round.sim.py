"""Device time under the phase ``vp.inbox`` (applying the pending inbox)
per simulated round, in ms: the self time of the megaloop's operations
under that scope in the traced window (``bench/phases.py``), averaged over
the chips, over the rounds run in it."""
from bench import phases


def read(ctx):
    return phases.ms_per_round(ctx, "vp.inbox")
