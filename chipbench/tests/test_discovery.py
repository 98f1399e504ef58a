"""A cell is added with files and entries alone.  Dropped into a copy of
the benchmark: a new input kind (uniform-noise pixels), a new arrival
process (on/off bursts), a traffic mix that names them, and a new
configuration; and, in a second copy, a new network with a configuration
that names it.  All are found by name and run, and no file that was in
the copy changes, ``BENCHMARK.json`` aside."""

import filecmp
import json
import os

import numpy as np

from helpers import ROOT, add_entries, copy_bench, run, write_json

NOISE = '''
import numpy as np


def pool(params):
    rng = np.random.default_rng(int(params["pool_seed"]))
    on = rng.random((int(params["pool"]), 784)) < float(params["density"])
    return np.where(on, 255, 0).astype(np.uint8)
'''

BURSTS = '''
import math


class Schedule:
    """``per_burst`` requests due at the start of every ``period_s``."""

    def __init__(self, params, rng, seconds, lanes_total):
        self.period = float(params["period_s"])
        self.n = int(params["per_burst"])
        self.k = 0

    def take(self, t, queue_depth):
        out = []
        while self.k * self.period <= t:
            out += [self.k * self.period] * self.n
            self.k += 1
        return out

    def next_due(self):
        return self.k * self.period
'''


def _write(path, text):
    with open(path, "w") as f:
        f.write(text)


def test_new_kinds_mix_and_config_found_by_name(tmp_path):
    root = copy_bench(tmp_path)
    bench = os.path.join(root, "chipbench")
    before = {os.path.relpath(os.path.join(d, f), root)
              for d, _, fs in os.walk(root) for f in fs}
    _write(os.path.join(bench, "inputs", "uniform.py"), NOISE)
    _write(os.path.join(bench, "arrivals", "bursts.py"), BURSTS)
    write_json(os.path.join(bench, "traffic", "noise.bursts.json"),
               {"arrivals": {"kind": "bursts", "period_s": 0.1,
                             "per_burst": 12},
                "inputs": {"kind": "uniform", "pool": 16, "pool_seed": 3,
                           "density": 0.49}})
    with open(os.path.join(bench, "configs", "snn-paper-784x10.json")) as f:
        cfg = json.load(f)
    cfg.update(name="snn-tiny-784x16x10", layer_sizes=[784, 16, 10],
               lanes_per_device=8)
    write_json(os.path.join(bench, "configs", "snn-tiny-784x16x10.json"), cfg)
    add_entries(
        root,
        configs=[{"name": "snn-tiny-784x16x10", "source": "test",
                  "file": "chipbench/configs/snn-tiny-784x16x10.json",
                  "reduced": [], "why": "test"}],
        workloads=[{"name": "tiny.noise.bursts",
                    "config": "snn-tiny-784x16x10",
                    "traffic": "noise.bursts", "chips": 1, "why": "test"}])
    spec = json.load(open(os.path.join(root, "BENCHMARK.json")))
    next(m for m in spec["end_to_end"]
         if m["name"] == "throughput_rps")["workloads"].append(
        "tiny.noise.bursts")
    write_json(os.path.join(root, "BENCHMARK.json"), spec)

    captured = {}
    line, _ = run("tiny.noise.bursts", seconds=0.5, root=root,
                  keep=captured)
    assert line["correct"] is True
    assert set(line["metrics"]) == {"throughput_rps", "setup_s"}
    # the new kinds did the work: noise pixels, and bursts of 12 due at
    # 0.1 s steps (the first burst at the window's start)
    traffic, window = captured["traffic"], captured["window"]
    assert set(np.unique(traffic.pixels)) == {0, 255}
    due = np.array(sorted(window.due.values())) - window.t0
    assert len(due) % 12 == 0 and len(due) >= 36
    np.testing.assert_allclose(np.unique(due.round(6))[:3], [0, 0.1, 0.2])

    # every file that was in the copy is unchanged, BENCHMARK.json aside
    for rel in before - {"BENCHMARK.json"}:
        assert filecmp.cmp(os.path.join(root, rel), os.path.join(ROOT, rel),
                           shallow=False), rel


# The dense stack with every weight matrix stored transposed, (N, K), and
# drawn from the seed after the configuration's: a harness that took the
# weights, the reference or the engine's parameters anywhere but from this
# file would fail on the shapes, or compare against another network.
DENSE_T = '''
import os

import jax.numpy as jnp

import reference
from generator import plugin

dense = plugin(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
               "networks", "dense")
spec_of = dense.spec_of
macs_per_lane_step = dense.macs_per_lane_step
weight_bytes = dense.weight_bytes
lane_state_bytes = dense.lane_state_bytes


def _t(weights):
    return tuple(w.T for w in weights)


def make_weights(cfg):
    return _t(dense.make_weights(dict(cfg,
                                      weight_seed=cfg["weight_seed"] + 1)))


def control_weights(weights):
    return _t(dense.control_weights(_t(weights)))


def _layer(l, x, w, en):
    adds = (jnp.sum(x, axis=-1, dtype=jnp.int32)
            * jnp.sum(en, axis=-1, dtype=jnp.int32))
    cur = jnp.einsum("rk,nk->rn", x.astype(jnp.int32), w,
                     preferred_element_type=jnp.int32)
    return cur, adds


def serve(spec, weights, pixels, seeds, block=4096):
    shapes = tuple((n,) for n in spec.layer_sizes[1:])
    return reference.serve(spec.dp, shapes, _layer, weights, pixels, seeds,
                           block)


def program(cfg, weights):
    return dense.program(cfg, _t(weights))
'''


def test_new_network_found_by_name(tmp_path):
    root = copy_bench(tmp_path)
    bench = os.path.join(root, "chipbench")
    before = {os.path.relpath(os.path.join(d, f), root)
              for d, _, fs in os.walk(root) for f in fs}
    _write(os.path.join(bench, "networks", "dense_t.py"), DENSE_T)
    with open(os.path.join(bench, "configs", "snn-paper-784x10.json")) as f:
        cfg = json.load(f)
    cfg.update(name="snn-t-784x16x10", network="dense_t",
               layer_sizes=[784, 16, 10], lanes_per_device=8)
    write_json(os.path.join(bench, "configs", "snn-t-784x16x10.json"), cfg)
    add_entries(
        root,
        configs=[{"name": "snn-t-784x16x10", "source": "test",
                  "file": "chipbench/configs/snn-t-784x16x10.json",
                  "reduced": [], "why": "test"}],
        workloads=[{"name": "t.digits.backlog", "config": "snn-t-784x16x10",
                    "traffic": "digits.backlog", "chips": 1,
                    "why": "test"}])
    spec = json.load(open(os.path.join(root, "BENCHMARK.json")))
    next(m for m in spec["end_to_end"]
         if m["name"] == "throughput_rps")["workloads"].append(
        "t.digits.backlog")
    write_json(os.path.join(root, "BENCHMARK.json"), spec)

    line, checks = run("t.digits.backlog", seconds=0.5, root=root)
    assert line["correct"] is True, checks
    assert line["attempted"] > 0 and line["failed"] == 0
    assert set(line["metrics"]) == {"throughput_rps", "setup_s"}

    for rel in before - {"BENCHMARK.json"}:
        assert filecmp.cmp(os.path.join(root, rel), os.path.join(ROOT, rel),
                           shallow=False), rel
