"""The comparison that decides ``correct``: the helpers every
configuration kind's ``compare`` (``bench/kinds/<kind>.py``) is built from.

Every number a kind compares counts disagreements, so each limit in its
``LIMITS`` is 0: the platform's integer arithmetic and its simulated time
are exact by design, and a single differing word, spike count, membrane,
round or cycle is a wrong simulation.  The numbers are printed beside
their limits.
"""
from __future__ import annotations

import numpy as np

from bench import reference


def dense_wrong(job, o_words, precision: str = "exact",
                want=None) -> int:
    """Output words of one dense job that differ from A @ B."""
    if want is None:
        want = reference.dense_output(job.a, job.b, precision)
    got = np.asarray(o_words, np.int64).reshape(want.shape)
    return int((got != want).sum())


def snn_reference(job, max_ticks: int, precision: str = "exact"):
    """The oracle's per-tick history of one SNN job, long enough for a
    platform whose units ticked up to ``max_ticks`` times."""
    n_ticks = max(max_ticks, reference.feedforward_horizon(
        len(job.raster), len(job.weights)))
    return reference.lif_run(job.weights, job.thresh, job.leak,
                             job.refrac_period, job.raster, n_ticks,
                             precision)


def snn_wrong(groups, v, counts, ticks, ref):
    """(spike totals wrong, membranes wrong) of one SNN job.

    ``groups``: [(layer, r0, r1, seg, slot)] — each stripe's neuron rows
    and the unit that holds their state; ``v``/``counts``/``ticks``: the
    platform's per-unit membranes, emitted-spike counts and tick counts
    (S, slots, 256) / (S, slots); ``ref``: ``snn_reference``'s history.
    Spike totals are compared with the oracle's totals once the chain has
    gone quiet, so a unit that stopped ticking early is wrong; membranes
    keep leaking, so they are compared after as many ticks as the unit
    ran.  A unit that ticked more often than the reference ran is wrong in
    full.
    """
    ref_v, ref_c = ref
    spikes_bad = v_bad = 0
    for layer, r0, r1, s, k in groups:
        n = r1 - r0
        t = int(ticks[s, k])
        if not 0 <= t < len(ref_v[layer]):
            spikes_bad += n
            v_bad += n
            continue
        spikes_bad += int((counts[s, k, :n] != ref_c[layer][-1, r0:r1]).sum())
        v_bad += int((v[s, k, :n] != ref_v[layer][t, r0:r1]).sum())
    return spikes_bad, v_bad


def verdict(values: dict, limits: dict):
    """(correct, {name: {"value", "limit"}}) in ``limits`` order."""
    checks = {k: {"value": int(values[k]), "limit": lim}
              for k, lim in limits.items()}
    return all(c["value"] <= c["limit"] for c in checks.values()), checks
