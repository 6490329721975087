"""Share of the megaloop's device time in the traced window that falls
under one of the round's phase scopes, in %: the check that the program's
``vp.*`` scopes cover the round (``bench/phases.py``).  The rest is time
of operations XLA adds outside any scope, such as the loop's own control
and the copies of its carry."""
from bench import phases


def read(ctx):
    return phases.coverage_pct(ctx)
