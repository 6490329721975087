"""The bfloat16 control fails the comparison, in every kind of cell."""
from __future__ import annotations

import pytest

from bench import kinds
from bench.tests.helpers import TINY_SNN, tiny_cell


@pytest.mark.parametrize("seed", [1, 2**31 + 3, 2**40 + 5])
def test_dense_control_is_refused(seed):
    _, config, traffic, _ = tiny_cell("dense-cim-imagenet-conv1")
    traffic = {**traffic, "layer": {"h": 32, "w": 64, "p": 4}}
    vals = kinds.find(config["kind"]).control(config, traffic, seed)
    assert vals["output_words_wrong"] > 0


@pytest.mark.parametrize("cell", ["snn-fc1200-infer",
                                  "snn-fc1200-serve-4chip"])
@pytest.mark.parametrize("seed", [1, 2**31 + 3, 2**40 + 5])
def test_snn_control_is_refused(cell, seed):
    _, config, traffic, _ = tiny_cell(cell)
    assert config["layer_sizes"] == TINY_SNN["layer_sizes"]
    vals = kinds.find(config["kind"]).control(config, traffic, seed)
    assert vals["membranes_wrong"] > 0


def test_the_exact_reference_in_the_controls_place_passes():
    from bench import check, reference

    _, config, traffic, _ = tiny_cell("snn-fc1200-infer")
    job = kinds.find(config["kind"]).job(config, traffic, 7, 0)
    ticks = reference.feedforward_horizon(6, len(job.weights))
    ref = check.snn_reference(job, ticks)
    groups = [(l, 0, x.shape[1], 0, l) for l, x in enumerate(ref[0])]
    import numpy as np

    width = max(x.shape[1] for x in ref[0])
    v = np.zeros((1, len(groups), width), np.int64)
    c = np.zeros_like(v)
    for l, (vl, cl) in enumerate(zip(*ref)):
        v[0, l, :vl.shape[1]] = vl[ticks]
        c[0, l, :cl.shape[1]] = cl[ticks]
    t = np.full((1, len(groups)), ticks)
    assert check.snn_wrong(groups, v, c, t, ref) == (0, 0)
