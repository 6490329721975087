"""Winner-take-all decision making on a recurrent neuromorphic VP.

Recurrent connectivity is what real neuromorphic workloads are made of
(TrueNorth/RANC cores): this example runs a two-layer *cyclic* network —
an Elman-style self-recurrent evidence layer feeding a winner-take-all
output pool whose lateral inhibition silences every neuron but the
winner, plus a feedback edge that lets the emerging decision bias the
evidence layer one tick later.  All three cyclic paths ride the same
tick-bucketed AER machinery as feed-forward spikes (one tick of axonal
delay per hop, wherever the edge points), and the run is verified
bit-exactly against the cycle-aware pure-jnp oracle over the shared tick
horizon.

  PYTHONPATH=src python examples/snn_recurrent.py
"""
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1] / "src"))

import numpy as np

from repro import snn
from repro.compile_cache import enable_compile_cache
from repro.core.controller import Controller

N_IN, N_EVID, N_CLASSES = 24, 16, 4
T_STEPS = 16
SETTLE = 6  # extra ticks for the WTA competition to ring down
N_TICKS = T_STEPS + 2 + SETTLE

enable_compile_cache()
rng = np.random.default_rng(5)

# evidence layer: neuron j accumulates the input block of class j % 4
# (+3 on its own block, light noise elsewhere); mild random
# self-recurrence keeps evidence reverberating after the input fades
blk = N_IN // N_CLASSES
w_evid = rng.integers(-1, 1, (N_EVID, N_IN)).astype(np.int8)
for j in range(N_EVID):
    c = j % N_CLASSES
    w_evid[j, c * blk:(c + 1) * blk] = 3
evid_lateral = rng.integers(-1, 2, (N_EVID, N_EVID)).astype(np.int8)
evidence = snn.SNNLayer(w_evid, snn.LIFParams(thresh=2 * blk, leak=1),
                        lateral=evid_lateral)

# output pool: class templates + winner-take-all lateral inhibition
w_out = np.zeros((N_CLASSES, N_EVID), np.int8)
for c in range(N_CLASSES):
    w_out[c, c::N_CLASSES] = 6  # every 4th evidence neuron votes for class c
wta = (-8 * (1 - np.eye(N_CLASSES, dtype=np.int64))).astype(np.int8)
output = snn.SNNLayer(w_out, snn.LIFParams(thresh=10, leak=0), lateral=wta)

# the decision feeds back: the leading class excites its own evidence
fb = np.zeros((N_EVID, N_CLASSES), np.int8)
for c in range(N_CLASSES):
    fb[c::N_CLASSES, c] = 2
edges = (snn.RecurrentEdge(src=1, dst=0, weights=fb),)

layers = [evidence, output]
descs = snn.segmentation_for(layers, "uniform", n_segments=2, edges=edges)
print(f"2-segment VP, cyclic net: {N_EVID}-neuron Elman evidence layer, "
      f"{N_CLASSES}-way WTA output, feedback edge; horizon {N_TICKS} ticks\n")
print(f"{'stimulus':>9s}{'output spike counts':>24s}{'winner':>8s}{'oracle ok':>11s}")

for stim in range(N_CLASSES):
    # stimulate the input block that favors class `stim`
    x = np.full(N_IN, 0.15)
    x[stim * (N_IN // N_CLASSES):(stim + 1) * (N_IN // N_CLASSES)] = 0.9
    raster = snn.rate_encode(x, T_STEPS, seed=100 + stim)
    counts, totals = snn.oracle_run(layers, raster, edges=edges, n_ticks=N_TICKS)

    cfg, states, pending, meta = snn.build_snn(
        layers, descs, raster, edges=edges, n_ticks=N_TICKS)
    ctl = Controller(cfg, states, pending, backend="vmap", quantum=32)
    ctl.run(max_rounds=400, check_every=2)
    got = snn.output_spike_counts(ctl.result_states(), meta)
    ok = np.array_equal(got, counts)
    winner = int(np.argmax(got))
    marker = "*" if winner == stim else "!"
    print(f"{stim:>9d}{str(got.tolist()):>24s}{winner:>7d}{marker}"
          f"{'yes' if ok else 'NO':>11s}")
    assert ok, "VP must match the cycle-aware oracle bit-exactly"

print("\nevery run verified bit-exactly against the cycle-aware jnp oracle")
