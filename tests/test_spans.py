"""Engine phase spans (``repro.serve.spans``).

Contracts under test:
  * the on/off switch is the profiler's: ``TraceMe.is_enabled()`` (a
    private JAX API, guarded here across upgrades) is false outside a
    ``jax.profiler.trace`` and true inside it, and a run outside a trace
    records nothing;
  * inside a trace, one ``SNNStreamEngine`` run and one
    ``ShardedSNNStreamEngine`` run on four virtual devices (subprocess,
    as in test_sharded_engine.py) each record the seven phase spans,
    nested under ``snn.step``;
  * the counts are the engine's: ``snn.harvest`` carries the requests
    retired, ``snn.admit`` those admitted, ``snn.sync`` the previous
    chunk's skipped tile pairs out of the launch geometry's,
    ``snn.readback`` and ``snn.upload`` the bytes of the packed tile;
  * results are bit-identical with recording on and off;
  * each in-memory span has a host event of the same name and counts in
    the ``.xplane.pb`` the profiler writes.
"""

import collections
import dataclasses
import glob
import json
import os
import subprocess
import sys
import textwrap
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax._src.lib import _profiler

from repro.configs.snn_mnist import SNN_CONFIG
from repro.core.telemetry import tiles_total
from repro.serve import SNNStreamEngine, spans

SRC = os.path.join(os.path.dirname(__file__), "..", "src")
SIZES = (24, 12, 10)
CHUNK = 3
N_REQ = 20


def _net(rng, sizes):
    return {"layers": [
        {"w_q": jnp.asarray(rng.integers(-256, 256, (a, b)), jnp.int16),
         "scale": jnp.float32(1.0)}
        for a, b in zip(sizes[:-1], sizes[1:])]}


def _as_tuple(r):
    return (r.pred, r.steps, r.adds, r.early_exit, r.spike_counts.tolist())


def _serve(trace_dir=None):
    """Serve N_REQ images to the end with ``step()`` alone.  Returns the
    results, the spans recorded meanwhile and the submitted ids."""
    rng = np.random.default_rng(0)
    cfg = dataclasses.replace(SNN_CONFIG, layer_sizes=SIZES, num_steps=10)
    eng = SNNStreamEngine(_net(rng, SIZES), cfg, batch_size=8,
                          chunk_steps=CHUNK, patience=1, seed=11,
                          engine_id=5)
    rids = [eng.submit(im) for im in
            rng.integers(0, 256, (N_REQ, SIZES[0]), dtype=np.uint8)]
    t0 = time.perf_counter_ns()

    def go():
        while eng.pending:
            eng.step()
    if trace_dir is None:
        go()
    else:
        with jax.profiler.trace(trace_dir):
            go()
    got = [s for s in spans.recorded().spans if s.start_ns >= t0]
    return eng.results, got, rids


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    d = str(tmp_path_factory.mktemp("spans-trace"))
    results, got, rids = _serve(d)
    return {"dir": d, "results": results, "spans": got, "rids": rids}


def test_profiler_switch(tmp_path):
    assert not _profiler.TraceMe.is_enabled() and not spans.enabled()
    with jax.profiler.trace(str(tmp_path)):
        assert _profiler.TraceMe.is_enabled() and spans.enabled()
    assert not _profiler.TraceMe.is_enabled() and not spans.enabled()


def test_nothing_recorded_outside_a_trace():
    results, got, rids = _serve()
    assert len(results) == N_REQ and got == []


def _check_nesting(got):
    """Every span lies in an ``snn.step``, and the seven are there."""
    names = collections.Counter(s.name for s in got)
    assert set(names) == set(spans.NAMES), names
    assert all(s.parent is None for s in got if s.name == "snn.step")
    assert all(s.parent == "snn.step" for s in got if s.name != "snn.step")
    steps = [s for s in got if s.name == "snn.step"]
    for s in got:
        assert any(p.start_ns <= s.start_ns and s.end_ns <= p.end_ns
                   for p in steps)


def test_engine_spans_nest_under_step(traced):
    _check_nesting(traced["spans"])
    steps = [s for s in traced["spans"] if s.name == "snn.step"]
    assert {s.counts["engine"] for s in steps} == {5}
    assert all(s.end_ns >= s.start_ns for s in traced["spans"])


def test_harvest_and_admit_counts_match_the_engine(traced):
    got = traced["spans"]
    harvested = [r for s in got if s.name == "snn.harvest"
                 for r in s.counts["rids"]]
    admitted = [r for s in got if s.name == "snn.admit"
                for r in s.counts["rids"]]
    assert sorted(harvested) == sorted(traced["results"])
    assert admitted == traced["rids"]          # FIFO, each once
    for name in ("snn.harvest", "snn.admit"):
        assert all(s.counts["n"] == len(s.counts["rids"])
                   for s in got if s.name == name)


def test_sync_and_dispatch_counts(traced):
    got = traced["spans"]
    dispatches = [s.counts for s in got if s.name == "snn.dispatch"]
    assert all(c["launches"] == 1 and c["chunk_steps"] == CHUNK
               and 0 <= c["lanes_busy"] <= 8 for c in dispatches)
    tiled = [s.counts for s in got
             if s.name == "snn.sync" and "tile_pairs" in s.counts]
    # every sync but the first reads back the chunk before it: 8 lanes
    # are one batch block
    assert len(tiled) == len(dispatches) - 1
    per_chunk = sum(tiles_total(SIZES)) * 1 * CHUNK
    assert all(c["tile_pairs"] == per_chunk
               and 0 <= c["tiles_skipped"] <= per_chunk for c in tiled)


def test_tile_crossings_count_their_bytes(traced):
    got = traced["spans"]
    # a lane row, in 4-byte words: px 6, rng 24, v 12 + 10, en 3 + 3,
    # v_peak 12 + 10, counts 10, first 10, six per-lane scalars
    row = 4 * (6 + 24 + 22 + 6 + 22 + 10 + 10 + 6)
    for name in ("snn.readback", "snn.upload"):
        crossings = [s.counts for s in got if s.name == name]
        assert crossings and all(c == {"bytes": 8 * row}
                                 for c in crossings)


def test_results_identical_with_recording_on_and_off(traced):
    off, _, _ = _serve()
    on = traced["results"]
    assert set(on) == set(off)
    assert all(_as_tuple(on[r]) == _as_tuple(off[r]) for r in off)


def _xplane_stats(counts):
    """Counts as the trace holds them: a sequence space-separated, an
    empty one left out."""
    out = []
    for k, v in counts.items():
        v = " ".join(str(x) for x in v) if isinstance(v, tuple) else v
        if v != "":
            out.append((k, v))
    return tuple(sorted(out))


def test_each_span_is_in_the_xplane_with_its_counts(traced):
    from jax.profiler import ProfileData
    files = glob.glob(os.path.join(traced["dir"], "**", "*.xplane.pb"),
                      recursive=True)
    assert len(files) == 1
    host = collections.Counter()
    for plane in ProfileData.from_file(files[0]).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name in spans.NAMES:
                        host[(e.name, tuple(sorted(list(e.stats))))] += 1
    mine = collections.Counter((s.name, _xplane_stats(s.counts))
                               for s in traced["spans"])
    assert host == mine


def test_sharded_engine_spans_4way():
    code = textwrap.dedent("""
        import dataclasses, json, tempfile, time
        import jax, jax.numpy as jnp, numpy as np
        from repro.configs.snn_mnist import SNN_CONFIG
        from repro.serve import ShardedSNNStreamEngine, spans
        assert len(jax.devices()) == 4, jax.devices()
        sizes = (24, 12, 10)
        cfg = dataclasses.replace(SNN_CONFIG, layer_sizes=sizes,
                                  num_steps=10)

        def serve(trace):
            rng = np.random.default_rng(0)
            params_q = {"layers": [
                {"w_q": jnp.asarray(rng.integers(-256, 256, (a, b)),
                                    jnp.int16), "scale": jnp.float32(1.0)}
                for a, b in zip(sizes[:-1], sizes[1:])]}
            eng = ShardedSNNStreamEngine(params_q, cfg, lanes_per_device=2,
                                         chunk_steps=3, patience=1, seed=11)
            rids = [eng.submit(im) for im in
                    rng.integers(0, 256, (20, 24), dtype=np.uint8)]
            t0 = time.perf_counter_ns()

            def go():
                while eng.pending:
                    eng.step()
            if trace:
                with jax.profiler.trace(tempfile.mkdtemp()):
                    go()
            else:
                go()
            res = {str(k): [r.pred, r.steps, r.adds, r.early_exit,
                            r.spike_counts.tolist()]
                   for k, r in eng.results.items()}
            got = [[s.name, s.parent, s.start_ns, s.end_ns, s.counts]
                   for s in spans.recorded().spans if s.start_ns >= t0]
            return res, got, rids

        off, none, _ = serve(False)
        on, got, rids = serve(True)
        print(json.dumps({"same": on == off, "none": none, "spans": got,
                          "rids": rids, "retired": sorted(map(int, on))}))
    """)
    env = dict(os.environ,
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               JAX_PLATFORMS="cpu", PYTHONPATH=SRC)
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, env=env, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    r = json.loads(out.stdout.strip().splitlines()[-1])
    assert r["same"] and r["none"] == []
    got = [spans.Span(n, s, e, p, {k: tuple(v) if isinstance(v, list)
                                    else v for k, v in c.items()})
           for n, p, s, e, c in r["spans"]]
    _check_nesting(got)
    harvested = sorted(x for s in got if s.name == "snn.harvest"
                       for x in s.counts["rids"])
    admitted = [x for s in got if s.name == "snn.admit"
                for x in s.counts["rids"]]
    assert harvested == r["retired"] and len(harvested) == N_REQ
    assert sorted(admitted) == sorted(r["rids"])
    # four devices, one 2-lane batch block each
    per_chunk = sum(tiles_total(SIZES)) * 4 * CHUNK
    tiled = [s.counts for s in got
             if s.name == "snn.sync" and "tile_pairs" in s.counts]
    assert tiled and all(c["tile_pairs"] == per_chunk for c in tiled)
