"""Sharded streaming SNN engine: data-parallel lane-mesh bit-identity.

Contracts under test:
  * the sharded engine reproduces the single-device engine bit-for-bit on
    shared seeds — predictions, retirement steps, spike registers and the
    frozen executed-add counters — on a 4-way forced-host mesh, including
    mid-chunk retirement and re-admission into freed slots (subprocess,
    same pattern as test_distributed.py so the rest of the suite keeps
    seeing the single real CPU device);
  * property: random window splits × random admission schedules give
    chunked sharded execution bit-identical to one-shot single-device
    execution, for both the fused-gated path and the jnp-scan fallback
    (in-process — the mesh covers whatever devices exist: 1 locally, 4 in
    the CI multi-device lane);
  * admission/compute overlap (speculative chunk dispatch) changes no
    results and actually fires in steady state;
  * mesh plumbing: divisibility validation, lane partition specs, and the
    per-device VMEM scoping of backend resolution.
"""

import dataclasses
import json
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st
from jax.sharding import PartitionSpec as P

from repro.configs.snn_mnist import SNN_CONFIG
from repro.core import prng, snn
from repro.serve import ShardedSNNStreamEngine
from repro.serve.snn_engine import lane_partition_specs

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def run_sub(code: str, n_dev: int = 4) -> str:
    env = dict(os.environ,
               XLA_FLAGS=f"--xla_force_host_platform_device_count={n_dev}",
               JAX_PLATFORMS="cpu",
               PYTHONPATH=SRC)
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                         capture_output=True, text=True, env=env,
                         timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    return out.stdout


def small_net(rng, sizes):
    layers = []
    for a, b in zip(sizes[:-1], sizes[1:]):
        w = jnp.asarray(rng.integers(-256, 256, (a, b)), jnp.int16)
        layers.append({"w_q": w, "scale": jnp.float32(1.0)})
    return {"layers": layers}


SUB_PRELUDE = """
    import dataclasses, json
    import jax, jax.numpy as jnp, numpy as np
    from repro.configs.snn_mnist import SNN_CONFIG
    from repro.serve import ShardedSNNStreamEngine, SNNStreamEngine

    def small_net(rng, sizes):
        return {"layers": [
            {"w_q": jnp.asarray(rng.integers(-256, 256, (a, b)), jnp.int16),
             "scale": jnp.float32(1.0)}
            for a, b in zip(sizes[:-1], sizes[1:])]}

    def as_tuple(r):
        return (r.pred, r.steps, r.adds, r.early_exit,
                r.spike_counts.tolist())
"""


def test_sharded_matches_single_device_4way():
    """Bit-identity on a 4-way mesh for BOTH chunk backends, with enough
    load (20 images over 8 slots) to force re-admission into freed slots
    and a patience low enough to retire lanes mid-chunk."""
    out = run_sub(SUB_PRELUDE + """
    assert len(jax.devices()) == 4, jax.devices()
    rng = np.random.default_rng(0)
    cfg = dataclasses.replace(SNN_CONFIG, layer_sizes=(24, 12, 10),
                              num_steps=10)
    params_q = small_net(rng, cfg.layer_sizes)
    imgs = rng.integers(0, 256, (20, 24), dtype=np.uint8)
    summary = {}
    for backend in ("reference", "fused"):
        ref = SNNStreamEngine(params_q, cfg, batch_size=8, chunk_steps=3,
                              patience=1, seed=11, backend=backend)
        for im in imgs:
            ref.submit(im)
        r1 = ref.run()
        sh = ShardedSNNStreamEngine(params_q, cfg, lanes_per_device=2,
                                    chunk_steps=3, patience=1, seed=11,
                                    backend=backend)
        assert sh.n_devices == 4 and sh.local_batch == 2
        for im in imgs:
            sh.submit(im)
        r2 = sh.run()
        assert set(r1) == set(r2) == set(range(20))
        for rid in r1:
            assert as_tuple(r1[rid]) == as_tuple(r2[rid]), (backend, rid)
        summary[backend] = {
            "early_exits": sum(r.early_exit for r in r2.values()),
            "mid_chunk": sum(r.steps % 3 != 0 for r in r2.values()
                             if r.early_exit),
            "frozen_adds": sum(r.adds for r in r2.values()),
        }
    print(json.dumps(summary))
    """)
    res = json.loads(out.strip().splitlines()[-1])
    for backend in ("reference", "fused"):
        s = res[backend]
        assert s["early_exits"] > 0, res          # the gate actually fired
        assert s["mid_chunk"] > 0, res            # and fired mid-chunk
    # both backends walked the identical schedule and froze identical adds
    assert res["reference"] == res["fused"], res


def test_overlap_speculation_fires_and_changes_nothing_4way():
    """Steady state (full tile, gate never fires): the speculative chunk
    k+1 dispatch is used, and overlap=False produces identical results."""
    out = run_sub(SUB_PRELUDE + """
    rng = np.random.default_rng(3)
    cfg = dataclasses.replace(SNN_CONFIG, layer_sizes=(16, 10), num_steps=12)
    params_q = small_net(rng, cfg.layer_sizes)
    imgs = rng.integers(0, 256, (8, 16), dtype=np.uint8)
    runs = {}
    for overlap in (True, False):
        eng = ShardedSNNStreamEngine(params_q, cfg, lanes_per_device=2,
                                     chunk_steps=4, patience=10_000,
                                     seed=5, backend="reference",
                                     overlap=overlap)
        for im in imgs:
            eng.submit(im)
        res = eng.run()
        runs[overlap] = sorted(as_tuple(r) for r in res.values())
        if overlap:
            stats = dict(eng.stats)
    assert runs[True] == runs[False]
    print(json.dumps(stats))
    """)
    stats = json.loads(out.strip().splitlines()[-1])
    assert stats["spec_used"] > 0, stats


@settings(max_examples=6, deadline=None)
@given(seed=st.integers(0, 2**20), chunk_steps=st.integers(1, 8),
       burst=st.integers(1, 5),
       backend=st.sampled_from(["reference", "fused"]))
def test_random_admission_matches_one_shot(seed, chunk_steps, burst,
                                           backend):
    """Property: a random window split (chunk_steps) × a random admission
    schedule (bursty submits interleaved with engine steps) retires every
    request with results bit-identical to a one-shot single-device window
    (the patience sentinel disables early exit, so the fused path still
    runs its in-kernel gate — it just never triggers)."""
    rng = np.random.default_rng(seed)
    cfg = dataclasses.replace(SNN_CONFIG, layer_sizes=(12, 6), num_steps=8)
    params_q = small_net(rng, cfg.layer_sizes)
    n_imgs = int(rng.integers(3, 9))
    imgs = rng.integers(0, 256, (n_imgs, 12), dtype=np.uint8)
    eng = ShardedSNNStreamEngine(params_q, cfg, lanes_per_device=2,
                                 chunk_steps=chunk_steps, patience=10_000,
                                 seed=seed, backend=backend)
    submitted = 0
    for _ in range(n_imgs * (cfg.num_steps // chunk_steps + 2) + 4):
        take = min(int(rng.integers(0, burst + 1)), n_imgs - submitted)
        for im in imgs[submitted:submitted + take]:
            eng.submit(im)
        submitted += take
        eng.step()
        if submitted == n_imgs and eng.pending == 0:
            break
    for im in imgs[submitted:]:     # bursts of 0 can leave some unsent
        eng.submit(im)
    results = eng.run()
    assert set(results) == set(range(n_imgs))
    for rid in range(n_imgs):
        out = snn.snn_apply_int(
            params_q, jnp.asarray(imgs[rid][None]),
            prng.seed_state(seed + rid, (1, cfg.n_in)), cfg,
            backend="reference")
        r = results[rid]
        assert r.pred == int(np.asarray(out["pred"])[0])
        np.testing.assert_array_equal(r.spike_counts,
                                      np.asarray(out["spike_counts"])[0])
        assert r.steps == cfg.num_steps and not r.early_exit
        assert r.adds == int(np.asarray(out["active_adds"]).sum())


def test_mesh_validation():
    from jax.sharding import AxisType
    rng = np.random.default_rng(0)
    params_q = small_net(rng, (12, 6))
    mesh = jax.make_mesh((len(jax.devices()),), ("data",), (AxisType.Auto,))
    with pytest.raises(ValueError, match="axis"):
        ShardedSNNStreamEngine(params_q, SNN_CONFIG, mesh=mesh,
                               axis_name="model", backend="reference")
    if len(jax.devices()) > 1:        # 1 divides everything
        with pytest.raises(ValueError, match="divide"):
            ShardedSNNStreamEngine(params_q, SNN_CONFIG, mesh=mesh,
                                   batch_size=len(jax.devices()) + 1,
                                   backend="reference")
    # passing both tile knobs with inconsistent values must fail loudly,
    # not silently prefer one of them
    with pytest.raises(ValueError, match="conflicting"):
        ShardedSNNStreamEngine(params_q, SNN_CONFIG, mesh=mesh,
                               lanes_per_device=16,
                               batch_size=8 * len(jax.devices()),
                               backend="reference")


def test_stream_mesh_knobs_flow_into_engine():
    """configs.snn_mnist.SNNStreamMeshConfig is the deployment surface:
    every knob must actually reach the engine make_stream_engine builds."""
    from repro.configs.snn_mnist import (SNNStreamMeshConfig,
                                         make_stream_engine)
    rng = np.random.default_rng(0)
    params_q = small_net(rng, (12, 6))
    cfg = dataclasses.replace(SNN_CONFIG, layer_sizes=(12, 6), num_steps=8)
    knobs = SNNStreamMeshConfig(num_devices=1, lanes_per_device=3,
                                chunk_steps=7, overlap=False)
    eng = make_stream_engine(params_q, cfg, knobs, patience=5, seed=3,
                             backend="reference")
    assert eng.n_devices == 1 and eng.local_batch == 3
    assert eng.batch_size == 3 * eng.n_devices
    assert eng.chunk_steps == 7 and eng.overlap is False
    assert eng.patience == 5 and eng.axis_name == knobs.axis_name


def test_lane_partition_specs_cover_every_leaf():
    """Every LaneState leaf shards on the mesh batch axis — the structural
    invariant behind collective-free shard_map execution."""
    specs = lane_partition_specs(3, "data")
    leaves = jax.tree.leaves(specs, is_leaf=lambda x: isinstance(x, P))
    # v/en/v_peak are per-layer tuples
    assert len(leaves) == len(specs._fields) - 3 + 3 * 3
    assert all(s == P("data") for s in leaves)


def test_resolve_backend_vmem_check_is_per_device():
    """The VMEM feasibility estimate uses EXACTLY the batch block the
    per-device launch allocates (fused_snn.block_b_for is the shared
    source of truth), and data sharding never shrinks what fits."""
    from repro.kernels import fused_snn
    for b in (1, 2, 7, 8, 9, 64, 256):
        blk = fused_snn.block_b_for(b)
        assert blk % 8 == 0 or blk == fused_snn.DEFAULT_BLOCK_B
        assert 8 <= blk <= fused_snn.DEFAULT_BLOCK_B
        # shrinking the tile never grows the block (monotone in batch)
        assert fused_snn.block_b_for(max(1, b // 4)) <= blk
    assert fused_snn.block_b_for(None) == fused_snn.DEFAULT_BLOCK_B
    cfg = dataclasses.replace(SNN_CONFIG, layer_sizes=(784, 128, 64, 10))
    for local in (256, 256 // 4):
        assert snn.fused_unsupported_reason(
            cfg, 3, cfg.layer_sizes, trace_steps=4,
            local_batch=local) is None
    # resolve_backend plumbs local_batch through without changing the
    # CPU-host resolution ("auto" stays on the jnp reference scan here)
    assert snn.resolve_backend(cfg, "auto", 3, layer_sizes=cfg.layer_sizes,
                               trace_steps=4, local_batch=64) == "reference"


def test_speculation_discarded_on_chunk_length_retune():
    """Regression: a speculative chunk dispatched at chunk length L must
    be DISCARDED (spec_wasted) when the adaptive controller's chunk
    length moves before the commit — e.g. a tier/coordinator feeding the
    controller an out-of-band observation between engine steps.  With
    the old guard (tile-object identity only) the stale-length chunk was
    committed as if it were the requested one: the lanes silently
    advanced by the WRONG number of window steps for that dispatch."""
    from repro.serve.telemetry import AdaptiveDispatchConfig, ChunkSummary
    rng = np.random.default_rng(2)
    cfg = dataclasses.replace(SNN_CONFIG, layer_sizes=(16, 10),
                              num_steps=24)
    params_q = small_net(rng, cfg.layer_sizes)
    n_lanes = max(1, 8 // len(jax.devices())) * len(jax.devices())
    imgs = rng.integers(0, 256, (n_lanes, 16), dtype=np.uint8)
    adaptive = AdaptiveDispatchConfig(adaptive=True, min_chunk_steps=2,
                                      grow_patience=10_000)
    eng = ShardedSNNStreamEngine(
        params_q, cfg, lanes_per_device=n_lanes // len(jax.devices()),
        chunk_steps=4, patience=10_000, seed=7, backend="reference",
        overlap=True, adaptive=adaptive)
    for im in imgs:
        eng.submit(im)
    eng.step()                       # commit chunk 1, speculate chunk 2
    assert eng._spec is not None and eng._spec_steps == 4
    # external retune mid-speculation: an every-lane-retired observation
    # (frac = 1.0, 3 trigger-widths over the 0.25 trigger) takes the
    # proportional shrink law from 4 straight to the min_chunk_steps
    # clamp at 2 — one observation, not two limping single steps
    eng.controller.observe(ChunkSummary(
        density_in=0.2, layer_densities=(0.2,), executed_adds=0,
        tiles_skipped=0, lanes_retired=n_lanes, lanes_active=n_lanes,
        active_lane_steps=n_lanes * 4))
    assert eng.controller.chunk_steps == 2
    before = dict(eng.stats)
    steps_before = int(np.asarray(eng.lanes.steps).max())
    eng.step()
    # the stale 4-step speculation was discarded, not committed
    assert eng.stats["spec_wasted"] == before["spec_wasted"] + 1
    assert eng.stats["spec_used"] == before["spec_used"]
    # and the committed chunk ran at the retuned length (2 steps)
    assert int(np.asarray(eng.lanes.steps).max()) == steps_before + 2
    # the engine still finishes every request correctly
    res = eng.run()
    assert set(res) == set(range(n_lanes))
    for rid in range(n_lanes):
        out = snn.snn_apply_int(
            params_q, jnp.asarray(imgs[rid][None]),
            prng.seed_state(7 + rid, (1, cfg.n_in)), cfg,
            backend="reference")
        assert res[rid].pred == int(np.asarray(out["pred"])[0])
        assert res[rid].steps == cfg.num_steps


def test_speculation_survives_external_compaction():
    """Regression: a speculative chunk dispatched inside step() must be
    discarded when a LATER _admit_and_compact (e.g. run(max_chunks=1)'s
    trailing harvest, or a fresh run() call) replaces the lane tile —
    the spec is keyed to the exact LaneState object it was computed from,
    not to 'nothing changed during this step'.  (With the old guard this
    exact scenario corrupted 8 of 20 requests — predictions and energy
    counters attributed to the wrong lanes.)"""
    rng = np.random.default_rng(0)
    cfg = dataclasses.replace(SNN_CONFIG, layer_sizes=(24, 12, 10),
                              num_steps=10)
    params_q = small_net(rng, cfg.layer_sizes)
    imgs = rng.integers(0, 256, (20, 24), dtype=np.uint8)
    lanes_per_dev = max(1, 8 // len(jax.devices()))  # global tile of ~8
    runs = {}
    for overlap in (True, False):
        eng = ShardedSNNStreamEngine(params_q, cfg,
                                     lanes_per_device=lanes_per_dev,
                                     chunk_steps=3, patience=1, seed=11,
                                     backend="reference", overlap=overlap)
        for im in imgs:
            eng.submit(im)
        # run(max_chunks=1) strands a dispatched speculative chunk across
        # its trailing _admit_and_compact; the follow-up run() must not
        # adopt it after the tile was compacted
        eng.run(max_chunks=1)
        res = eng.run()
        assert set(res) == set(range(len(imgs)))
        runs[overlap] = [(res[r].pred, res[r].steps, res[r].adds,
                          res[r].spike_counts.tolist())
                         for r in sorted(res)]
    assert runs[True] == runs[False]
