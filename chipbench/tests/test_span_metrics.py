"""The readers of the program's spans (``program_spans.py`` and the six
metrics on it): window clipping, the cases with nothing to read, the
arithmetic on synthetic span lists, and the tile pairs that the engine
puts on its ``snn.sync`` spans at both configurations' widths."""

import sys
from types import SimpleNamespace

import jax
import numpy as np
import pytest

import harness
from networks import dense
from generator import Traffic
from helpers import ROOT

import repro.serve
from repro.core.telemetry import tiles_total
from repro.serve import spans

METRICS = ("engine_step_ms", "engine_sync_ms", "lane_tile_io_ms",
           "harvest_ms_per_request", "admit_ms_per_request",
           "tile_skip_share")
MS = 1_000_000                       # ns
T0 = 1_000 * MS                      # window start, ns


def _read(name, run):
    return harness.Bench(ROOT).reader(name)(run)


def _run(t0=T0, t1=T0 + 100 * MS, trace=True):
    return SimpleNamespace(
        trace=object() if trace else None,
        window=SimpleNamespace(t0=t0 * 1e-9, t1=t1 * 1e-9))


def _round(at, *, step=10, sync=1, readback=2, harvest=3, upload=1,
           admit=2, n_out=3, n_in=2, skipped=5, pairs=20):
    """One ``step()``'s spans starting ``at`` ms after T0 (durations ms)."""
    s = T0 + int(at * MS)
    out = [spans.Span("snn.sync", s, s + sync * MS, "snn.step",
                      {"tiles_skipped": skipped, "tile_pairs": pairs}),
           spans.Span("snn.readback", s, s + readback * MS, "snn.step", {}),
           spans.Span("snn.harvest", s, s + harvest * MS, "snn.step",
                      {"n": n_out, "rids": tuple(range(n_out))}),
           spans.Span("snn.admit", s, s + admit * MS, "snn.step",
                      {"n": n_in, "rids": tuple(range(n_in))}),
           spans.Span("snn.upload", s, s + upload * MS, "snn.step", {}),
           spans.Span("snn.dispatch", s, s + MS, "snn.step",
                      {"launches": 1, "lanes_busy": 64, "chunk_steps": 4})]
    return out + [spans.Span("snn.step", s, s + step * MS, None,
                             {"engine": 0})]


@pytest.fixture
def recorded(monkeypatch):
    """Sets what ``spans.recorded()`` returns."""
    def put(span_list, dropped=0, dropped_end_ns=-1):
        monkeypatch.setattr(spans, "recorded", lambda: spans.Recorded(
            tuple(span_list), dropped, dropped_end_ns))
    return put


def test_arithmetic(recorded):
    recorded(_round(0) + _round(20, step=30, sync=3, readback=4, upload=2,
                                harvest=9, admit=1, n_out=6, n_in=0,
                                skipped=0, pairs=20))
    run = _run()
    assert _read("engine_step_ms", run) == pytest.approx(20.0)
    assert _read("engine_sync_ms", run) == pytest.approx(2.0)
    assert _read("lane_tile_io_ms", run) == pytest.approx(4.5)
    assert _read("harvest_ms_per_request", run) == pytest.approx(12 / 9)
    assert _read("admit_ms_per_request", run) == pytest.approx(3 / 2)
    assert _read("tile_skip_share", run) == pytest.approx(5 / 40)


def test_only_spans_wholly_inside_the_window(recorded):
    # one round before the window, one across its start, one across its
    # end, one inside
    recorded(_round(-50, step=99) + _round(-5, step=50)
             + _round(99.5, step=50)
             + _round(10, step=7, harvest=5, n_out=1))
    run = _run()
    assert _read("engine_step_ms", run) == pytest.approx(7.0)
    assert _read("harvest_ms_per_request", run) == pytest.approx(5.0)


def test_nothing_to_read(recorded, monkeypatch):
    recorded(_round(0))
    for name in METRICS:            # no device trace: a run on the CPU
        assert _read(name, _run(trace=False)) is None
    recorded([])
    for name in METRICS:            # no spans in the window
        assert _read(name, _run()) is None
    recorded(_round(0), dropped=3, dropped_end_ns=T0 + MS)
    for name in METRICS:            # spans dropped inside the window
        assert _read(name, _run()) is None
    recorded(_round(0), dropped=3, dropped_end_ns=T0 - MS)
    assert _read("engine_step_ms", _run()) == pytest.approx(10.0)
    # requests or tile pairs that sum to 0 give no share
    recorded(_round(0, n_out=0, n_in=0, pairs=0, skipped=0))
    for name in ("harvest_ms_per_request", "admit_ms_per_request",
                 "tile_skip_share"):
        assert _read(name, _run()) is None
    # a program without the span recorder
    recorded(_round(0))
    monkeypatch.setitem(sys.modules, "repro.serve.spans", None)
    monkeypatch.delattr(repro.serve, "spans")
    for name in METRICS:
        assert _read(name, _run()) is None


@pytest.mark.parametrize("config", ["snn-paper-784x10",
                                    "snn-wide-784x2048x2048x10"])
def test_tile_pairs_at_the_configurations(config, tmp_path):
    """A real window of the cell's engine, under the profiler: every
    sync after the first carries the chunk's tile pairs, which are
    tiles_total x batch blocks x chunk steps, and the six readers read."""
    bench = harness.Bench(ROOT)
    cfg = bench.config(config)
    lanes = cfg["lanes_per_device"]
    eng = harness.build_engine(cfg, dense.make_weights(cfg), seed=7)
    traffic = Traffic(bench.traffic("digits.backlog"), 7, lanes)
    for _ in range(lanes):
        traffic._submit(eng)
    eng.step()                                    # compiles the chunk
    with jax.profiler.trace(str(tmp_path)):
        window = traffic.run(eng, 0.2)
    run = SimpleNamespace(trace=object(), window=window)
    got = [s for s in spans.recorded().spans
           if s.start_ns >= window.t0 * 1e9]
    tiled = [s.counts for s in got
             if s.name == "snn.sync" and "tile_pairs" in s.counts]
    per_chunk = (sum(tiles_total(cfg["layer_sizes"])) * (lanes // 8)
                 * eng.chunk_steps)
    assert per_chunk == {"snn-paper-784x10": 7 * 8 * 4,
                         "snn-wide-784x2048x2048x10": 384 * 8 * 4}[config]
    assert tiled and all(c["tile_pairs"] == per_chunk for c in tiled)
    assert window.step_calls >= 1
    values = {m: _read(m, run) for m in METRICS}
    assert all(v is not None and np.isfinite(v) for v in values.values()), \
        values
    assert 0.0 <= values["tile_skip_share"] <= 1.0
    step_ms = window.step_s * 1e3 / window.step_calls
    assert values["engine_step_ms"] == pytest.approx(step_ms, rel=0.1)
