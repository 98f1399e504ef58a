"""The dense network's reference (``networks/dense.py`` on
``reference.py``) against the program's engine at a tiny size on the
CPU, for every readout, with and without active pruning and a hidden
layer."""

import numpy as np
import pytest

import harness
from networks import dense as reference
from generator import HERE, plugin


def _cfg(readout, pruning, sizes):
    return {"network": "dense", "layer_sizes": sizes, "num_steps": 20,
            "weight_bits": 8, "weight_seed": 5, "readout": readout,
            "active_pruning": pruning, "patience": 2,
            "mesh": {"data": 1, "model": 1}, "lanes_per_device": 8,
            "lif": {"decay_shift": 4, "v_threshold": 128, "v_rest": 0,
                    "v_min": -(1 << 20), "v_max": (1 << 20) - 1}}


@pytest.mark.parametrize("readout,pruning,sizes", [
    ("count", False, [784, 10]),
    ("count", False, [784, 24, 16, 10]),
    ("first_spike", True, [784, 10]),
    ("membrane", False, [784, 24, 10]),
])
def test_reference_equals_engine(readout, pruning, sizes):
    cfg = _cfg(readout, pruning, sizes)
    seed = 3_000_000_019
    weights = reference.make_weights(cfg)
    eng = harness.build_engine(cfg, weights, seed)
    pixels, _ = plugin(HERE, "inputs", "digits").render_pool(40, 1)
    rids = [eng.submit(p) for p in pixels]
    res = eng.run()
    ref = reference.serve(reference.spec_of(cfg), weights, pixels,
                          seed + np.array(rids), block=16)
    for i, rid in enumerate(rids):
        r = res[rid]
        assert (r.pred, r.steps, r.adds) == (
            ref["pred"][i], ref["steps"][i], ref["adds"][i]), rid
        np.testing.assert_array_equal(r.spike_counts, ref["counts"][i])
    # early exit is exercised: not every request ran the whole window
    assert ref["steps"].min() < cfg["num_steps"]
