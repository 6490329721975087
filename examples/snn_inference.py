"""Rate-coded digit classification on a 2-segment neuromorphic VP.

The VP's second programming model: instead of streaming dense vectors into
the CIM crossbars, the crossbars run in *spike mode* — synapse matrices
integrating address-event (AER) spikes into LIF membrane potentials, with
inter-layer spikes crossing segment boundaries through the same
time-decoupled channels the dense benchmarks use.

A 2-layer network classifies 8×8 digit glyphs: layer 1's synapses are
template correlators (+4 on template pixels, −1 off), layer 2 amplifies the
winning class.  The input glyph is rate-coded into a Bernoulli spike train;
the class whose output neuron spikes most wins.  The run is verified
bit-exactly against the pure-jnp SNN oracle.

  PYTHONPATH=src python examples/snn_inference.py
"""
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1] / "src"))

import numpy as np

from repro import snn
from repro.compile_cache import enable_compile_cache
from repro.core.controller import Controller

enable_compile_cache()

GLYPHS = {  # 8x8 digit templates
    0: ["..####..", ".#....#.", "#......#", "#......#",
        "#......#", "#......#", ".#....#.", "..####.."],
    1: ["...##...", "..###...", "...##...", "...##...",
        "...##...", "...##...", "...##...", ".######."],
    7: ["########", "......##", ".....##.", "....##..",
        "...##...", "..##....", ".##.....", "##......"],
}


def glyph_rates(rows, noise_rng=None, flip=0.05):
    x = np.array([[c == "#" for c in r] for r in rows], float).reshape(-1)
    if noise_rng is not None:
        flips = noise_rng.random(64) < flip
        x = np.where(flips, 1.0 - x, x)
    return x * 0.8 + 0.1  # on-pixels spike at 0.9, off at 0.1


classes = sorted(GLYPHS)
templates = np.stack([glyph_rates(GLYPHS[c]) > 0.5 for c in classes])  # (3, 64)

# layer 1: template correlators; layer 2: diagonal amplifier
w1 = np.where(templates, 4, -1).astype(np.int8)  # (3, 64)
w2 = (np.eye(len(classes)) * 8).astype(np.int8)
layers = [
    snn.SNNLayer(w1, snn.LIFParams(thresh=60, leak=1)),
    snn.SNNLayer(w2, snn.LIFParams(thresh=8, leak=0)),
]

T_STEPS = 24
rng = np.random.default_rng(7)
descs = snn.segmentation_for(len(layers), "uniform", n_segments=2)
print(f"2-segment VP, one spike-mode CIM unit per segment; {T_STEPS}-step rate code\n")
print(f"{'digit':>6s}{'output spike counts':>28s}{'predicted':>11s}{'oracle ok':>11s}")

for digit in classes:
    raster = snn.rate_encode(glyph_rates(GLYPHS[digit], rng), T_STEPS,
                             seed=100 + digit)
    expected, _ = snn.oracle_run(layers, raster)
    cfg, states, pending, meta = snn.build_snn(layers, descs, raster)
    ctl = Controller(cfg, states, pending, backend="vmap", quantum=32)
    ctl.run(max_rounds=200, check_every=1)
    counts = snn.output_spike_counts(ctl.result_states(), meta)
    pred = classes[int(np.argmax(counts))]
    ok = bool(np.array_equal(counts, expected))
    mark = "✓" if pred == digit else "✗"
    print(f"{digit:>6d}{str(counts.tolist()):>28s}{pred:>9d} {mark}{str(ok):>10s}")

from repro.core import channel as ch

print("\nAER traffic histogram bin (MSG_SPIKE):",
      int(ctl.stats()["txn_histogram"][ch.MSG_SPIKE]), "spike events routed in last run")
