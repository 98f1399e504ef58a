"""The dense network against numbers recorded from the dense stack's
reference and readers as they stood before the network became a file of
its own (``golden_dense.json``), at both configurations: the weight and
control codes, the reference's answers to a fixed sample of 64 requests
(fixed pixels and seeds), and ``snn_mfu`` and ``stack_kernel_roofline``
on a fixed synthetic run.  Every number has to be equal, not close."""

import hashlib
import json
import os
from types import SimpleNamespace

import numpy as np
import pytest

import harness
import trace_reduce as tr
from generator import HERE, plugin
from helpers import ROOT

with open(os.path.join(os.path.dirname(__file__), "golden_dense.json")) as f:
    GOLDEN = json.load(f)

KERNEL = ("%snn_stack_kernel.1 = (s32[64,128]{1,0:T(8,128)S(1)}) "
          "custom-call(%pad.24, %pad_add_fusion), "
          "custom_call_target=\"tpu_custom_call\"")


def _digest(arrays):
    h = hashlib.sha256()
    for a in arrays:
        a = np.asarray(a)
        h.update(str(a.dtype).encode() + str(a.shape).encode())
        h.update(np.ascontiguousarray(a).tobytes())
    return h.hexdigest()


def _pixels():
    n, seed = GOLDEN["pixels"]["digits_render_pool"]
    px = plugin(HERE, "inputs", "digits").render_pool(n, seed)[0]
    assert _digest([px]) == GOLDEN["pixels"]["sha256"]
    return px


def _synthetic_run(cfg, net):
    """Three launches of 83, 84 and 82.5 us in a 3 ms traced step, 157
    busy lanes over three steps, 1,000 requests retired in 2.5 s."""
    events = [(KERNEL, 0, 83_000), ("copy.1", 83_000, 90_000),
              (KERNEL, 1_000_000, 1_084_000),
              (KERNEL, 2_000_000, 2_082_500)]
    trace = tr.reduce(tr.Trace(devices={"/device:TPU:0": events},
                               spans=[("engine.step", 0, 3_000_000)]))
    return SimpleNamespace(
        trace=trace, peaks={"int8_ops_per_s": 393e12,
                            "hbm_bytes_per_s": 819e9},
        chips=1, launches=3, chunk_steps=4, config=cfg, network=net,
        notes=[],
        window=SimpleNamespace(seconds=2.5, step_calls=3, busy_lanes=157),
        retired_steps={r: 1 + (r * 7) % 20 for r in range(1000)})


@pytest.mark.parametrize("config", ["snn-paper-784x10",
                                    "snn-wide-784x2048x2048x10"])
def test_dense_network_prints_the_recorded_numbers(config):
    bench = harness.Bench(ROOT)
    cfg = bench.config(config)
    want = GOLDEN["configs"][config]
    net = harness.network(cfg)
    weights = net.make_weights(cfg)
    assert _digest(weights) == want["weights_sha256"]
    assert _digest(net.control_weights(weights)) == want["control_sha256"]

    ref = net.serve(net.spec_of(cfg), weights, _pixels(),
                    np.array(GOLDEN["seeds"], np.int64))
    for k in ("pred", "steps", "adds", "counts"):
        np.testing.assert_array_equal(ref[k], np.array(want[k]), err_msg=k)

    run = _synthetic_run(cfg, net)
    assert bench.reader("snn_mfu")(run) == want["snn_mfu"]
    assert bench.reader("stack_kernel_roofline")(run) == \
        want["stack_kernel_roofline"]
    assert run.notes == [want["roofline_note"]]
