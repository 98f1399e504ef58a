"""The conv network (``networks/conv.py``) and its cell on the CPU: the
work counts by hand, a short run of ``conv.digits.backlog`` that is
``correct``, and the conv stages' tile counts on the ``snn.sync`` spans
that ``conv_tile_skip_share`` reads."""

from types import SimpleNamespace

import jax
import numpy as np

import harness
from generator import Traffic, plugin
from helpers import ROOT, run
from repro.serve import spans

CONFIG = "snn-conv-12c5-64c5-1024x10"
CELL = "conv.digits.backlog"


def _cfg():
    return harness.Bench(ROOT).config(CONFIG)


def test_work_counts_by_hand():
    net = harness.network(_cfg())
    # conv1: 24 x 24 outputs x 12 channels x 25 taps; conv2: 8 x 8 x 64 x
    # (12 x 25); FC: 1024 x 10
    assert net.macs_per_lane_step(_cfg()) == (24 * 24 * 12 * 25
                                              + 8 * 8 * 64 * 12 * 25
                                              + 1024 * 10) == 1_411_840
    assert net.weight_bytes(_cfg()) == 2 * (12 * 25 + 64 * 12 * 25
                                            + 1024 * 10) == 59_480
    # 784 pixels at 5 B, 1728 + 1024 + 10 neurons at 9 B, 10 classes at
    # 8 B, 21 B of per-lane registers
    assert net.lane_state_bytes(_cfg()) == (784 * 5 + 2762 * 9 + 10 * 8
                                            + 21) == 28_879
    shapes = [w.shape for w in net.make_weights(_cfg())]
    assert shapes == [(12, 1, 5, 5), (64, 12, 5, 5), (1024, 10)]


def test_cell_runs_correct_on_the_cpu():
    line, checks = run(CELL, seconds=0.5)
    assert line["correct"] is True, checks
    assert line["attempted"] > 0 and line["failed"] == 0
    assert set(line["metrics"]) == {"throughput_rps", "setup_s"}
    assert all(c["value"] == 0 for c in line["checks"].values())


def test_sync_spans_carry_the_conv_tile_counts(tmp_path):
    bench = harness.Bench(ROOT)
    cfg = bench.config(CONFIG)
    lanes = cfg["lanes_per_device"]
    net = harness.network(cfg)
    eng = harness.build_engine(cfg, net.make_weights(cfg), seed=7)
    traffic = Traffic(bench.traffic("digits.backlog"), 7, lanes)
    for _ in range(lanes):
        traffic._submit(eng)
    eng.step()                                    # compiles the chunk
    with jax.profiler.trace(str(tmp_path)):
        window = traffic.run(eng, 0.3)
    got = [s.counts for s in spans.recorded().spans
           if s.start_ns >= window.t0 * 1e9 and s.name == "snn.sync"
           and "conv_tile_pairs" in s.counts]
    # a block holds 80 + 80 conv tile pairs a step (core.telemetry)
    per_chunk = 160 * (lanes // 8) * eng.chunk_steps
    assert got and all(c["conv_tile_pairs"] == per_chunk for c in got)
    assert all(0 <= c["conv_tiles_skipped"] <= c["tiles_skipped"]
               for c in got)
    share = plugin(bench.dir, "metrics", "conv_tile_skip_share").read(
        SimpleNamespace(trace=object(), window=window))
    assert share is not None and 0.0 <= share <= 1.0
    # the dense cells' spans carry no conv counts, and read nothing
    empty = SimpleNamespace(trace=object(), window=SimpleNamespace(
        t0=window.t1 + 1e3, t1=window.t1 + 2e3))
    assert plugin(bench.dir, "metrics", "conv_tile_skip_share").read(
        empty) is None
    assert np.isfinite(share)
