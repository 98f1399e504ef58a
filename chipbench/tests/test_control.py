"""The control: the reference in the program's place at the next
precision below the configuration's (int4 for its 8-bit weight codes).
The comparison that decides ``correct`` has to reject it, and has to
accept the reference at full precision."""

import numpy as np
import pytest

import harness
import reference
from generator import Traffic, Window
from helpers import ROOT

SEEDS = (2_147_483_659, 3_000_000_019, 11)


def _window(cfg, mix, seed, n=512):
    traffic = Traffic(mix, seed, cfg["lanes_per_device"])
    w = Window()
    for rid in range(n):
        traffic.rid_to_index[rid] = int(traffic.order[rid % len(
            traffic.order)])
        w.due[rid] = 0.0
    return traffic, w


def _served(cfg, traffic, w, seed, weights):
    rids = np.array(sorted(w.due))
    px = traffic.pixels[[traffic.rid_to_index[int(r)] for r in rids]]
    out = reference.serve(reference.spec_of(cfg), weights, px, seed + rids)
    return {int(r): (out["pred"][i], out["steps"][i], out["adds"][i],
                     out["counts"][i]) for i, r in enumerate(rids)}


@pytest.mark.parametrize("seed", SEEDS)
@pytest.mark.parametrize("config", ["snn-paper-784x10",
                                    "snn-wide-784x2048x2048x10"])
def test_control_fails_and_reference_passes(config, seed):
    bench = harness.Bench(ROOT)
    cfg = bench.config(config)
    mix = bench.traffic("digits.backlog")
    n = 512 if config == "snn-paper-784x10" else 64
    traffic, w = _window(cfg, mix, seed, n)
    weights = reference.make_weights(cfg)

    good = _served(cfg, traffic, w, seed, weights)
    checks, compared, failed = harness.compare(cfg, seed, traffic, list(w.due), good)
    assert compared == n and failed == 0
    assert all(v == 0 for v, _ in checks.values())

    ctl = _served(cfg, traffic, w, seed, reference.control_weights(weights))
    checks, compared, failed = harness.compare(cfg, seed, traffic, list(w.due), ctl)
    assert compared == n and failed > 0
    assert any(v > lim for v, lim in checks.values())
