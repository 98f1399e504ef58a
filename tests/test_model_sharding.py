"""Model-axis sharding of the neuron datapath: the 2-D (data × model)
mesh contracts.

Contracts under test:
  * **bit-identity across mesh shapes** — 4×1 (pure data), 1×4 (pure
    model), 2×2 (data × model) forced-host meshes all reproduce the
    single-device engine prediction-for-prediction AND
    telemetry-for-telemetry, for both the fused-gated path and the
    jnp-scan fallback, including mid-chunk retirement / re-admission
    (subprocess, same pattern as test_sharded_engine.py);
  * property: random window splits × random admission schedules on a 2-D
    mesh stay bit-identical to a one-shot single-device reference window
    (in-process — the model axis covers whatever devices exist: 1
    locally, real shards in the CI 4-device lane);
  * **failover placement-independence** (the PR-7 contract, extended):
    lanes snapshot from a model-sharded engine adopt onto a plain
    single-device engine and resume bit-exactly — the LaneState
    checkpoint never encodes the mesh it ran on;
  * **VMEM feasibility is per model shard**: SNN_CONFIG_WIDE
    (784-2048-2048-10) resolves to the VMEM-resident ``fused`` backend
    on a 4-way model axis where single-device resolution must fall back
    to ``fused_streamed``;
  * mesh/spec plumbing: ``make_2d_device_mesh`` validation,
    ``layer_shard_ways`` semantics (non-dividing layers replicate),
    ``stack_vmem_bytes(model_shards=1)`` bit-identical to the historical
    estimate, and the partition-spec helpers (lane state never shards on
    the model axis; weights shard columns only where ways > 1).
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

from repro.configs.snn_mnist import SNN_CONFIG, SNN_CONFIG_WIDE
from repro.core import prng, snn
from repro.kernels import fused_snn

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
    from repro.configs.snn_mnist import (SNN_CONFIG, SNNStreamMeshConfig,
                                         make_stream_engine)
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


def test_mesh_shapes_bit_identical_to_single_device():
    """4×1 / 1×4 / 2×2 forced-host meshes vs the single-device engine,
    both backends, with mid-chunk retirement (patience=1) and enough load
    (20 images over 8 global lanes) to force re-admission.  The 1×4 case
    covers the mixed stack: the 16-wide hidden layer shards 4-way while
    the 10-class head does not divide and must replicate."""
    out = run_sub(SUB_PRELUDE + """
    assert len(jax.devices()) == 4, jax.devices()
    rng = np.random.default_rng(0)
    cfg = dataclasses.replace(SNN_CONFIG, layer_sizes=(24, 16, 10),
                              num_steps=10)
    params_q = small_net(rng, cfg.layer_sizes)
    imgs = rng.integers(0, 256, (20, 24), dtype=np.uint8)
    expect_ways = {1: (1, 1), 2: (2, 2), 4: (4, 1)}
    summary = {}
    for backend in ("reference", "fused"):
        ref = SNNStreamEngine(params_q, cfg, batch_size=8, chunk_steps=3,
                              patience=1, seed=11, backend=backend)
        for im in imgs:
            ref.submit(im)
        r1 = ref.run()
        for nd, md, lpd in ((4, 1, 2), (1, 4, 8), (2, 2, 4)):
            knobs = SNNStreamMeshConfig(num_devices=nd, model_devices=md,
                                        lanes_per_device=lpd, chunk_steps=3)
            eng = make_stream_engine(params_q, cfg, knobs, patience=1,
                                     seed=11, backend=backend)
            assert eng.model_devices == md
            assert eng.model_ways == expect_ways[md], eng.model_ways
            for im in imgs:
                eng.submit(im)
            r2 = eng.run()
            assert set(r1) == set(r2) == set(range(20)), (backend, nd, md)
            for rid in r1:
                assert as_tuple(r1[rid]) == as_tuple(r2[rid]), \\
                    (backend, nd, md, rid)
            summary[f"{backend}:{nd}x{md}"] = sum(
                r.early_exit for r in r2.values())
    print(json.dumps(summary))
    """)
    res = json.loads(out.strip().splitlines()[-1])
    # the stability gate actually fired on every mesh shape — the
    # identity above covered the pruning/compaction paths, not a no-op
    assert all(v > 0 for v in res.values()), res


def test_model_sharded_telemetry_bit_identical():
    """Telemetry-for-telemetry: per-lane spike/enable counts (and the
    per-lane executed adds) from a model-sharded step are bit-identical
    to the unsharded step — every model peer derives them from the full
    gathered spike vector.  The per-shard skipped-tile counts concatenate
    model-inner on the block axis; on 128-aligned shard widths
    (512/4 = 128 — the tile grid partitions exactly) they SUM to the
    unsharded layer's count, and a replicated layer's count appears once
    per peer, each copy equal to the unsharded value."""
    out = run_sub(SUB_PRELUDE + """
    from jax.sharding import PartitionSpec as P
    from repro.core import prng, snn
    from repro.core.lif import LIFStateInt
    from repro.distributed.sharding import make_2d_device_mesh
    from repro.kernels.fused_snn import layer_shard_ways

    rng = np.random.default_rng(1)
    sizes = (784, 512, 512, 10)
    cfg = dataclasses.replace(SNN_CONFIG, layer_sizes=sizes, num_steps=1,
                              active_pruning=True)
    params_q = small_net(rng, sizes)
    weights = tuple(jnp.asarray(l["w_q"], jnp.int32)
                    for l in params_q["layers"])
    B = 8
    pixels = jnp.asarray(rng.integers(0, 256, (B, sizes[0]), np.uint8))
    rng_state = prng.seed_state(3, (B, sizes[0]))
    states = tuple(LIFStateInt(v=jnp.zeros((B, n), jnp.int32),
                               enable=jnp.ones((B, n), bool))
                   for n in sizes[1:])

    # unsharded oracle
    _, st1, x1, adds1, tel1 = snn.snn_int_stack_step(
        rng_state, pixels, states, weights, cfg.lif, active_pruning=True)

    mesh = make_2d_device_mesh(1, 4)
    ways = layer_shard_ways(sizes, 4)
    assert ways == (4, 4, 1)

    def body(rng_state, pixels, states, weights):
        return snn.snn_int_stack_step_sharded(
            rng_state, pixels, states, weights, cfg.lif,
            model_axis="model", ways=ways, active_pruning=True,
            contraction="pallas", interpret=True)

    rep = P()
    w_specs = tuple(P(None, "model") if w > 1 else P() for w in ways)
    st_specs = tuple(LIFStateInt(v=rep, enable=rep) for _ in states)
    tel_spec = {"n_spk": rep, "n_en": rep,
                "tiles": P(None, ("data", "model"))}
    f = jax.shard_map(
        body, mesh=mesh,
        in_specs=(rep, rep, st_specs, w_specs),
        out_specs=(rep, st_specs, rep, rep, tel_spec), check_vma=False)
    _, st2, x2, adds2, tel2 = f(rng_state, pixels, states, weights)

    assert (np.asarray(x1) == np.asarray(x2)).all()
    assert (np.asarray(adds1) == np.asarray(adds2)).all()
    for a, b in zip(st1, st2):
        assert (np.asarray(a.v) == np.asarray(b.v)).all()
        assert (np.asarray(a.enable) == np.asarray(b.enable)).all()
    # per-lane counts replicate bit-exactly over the model axis
    assert (np.asarray(tel1["n_spk"]) == np.asarray(tel2["n_spk"])).all()
    assert (np.asarray(tel1["n_en"]) == np.asarray(tel2["n_en"])).all()
    # tile counts: (L, nb) unsharded vs (L, nb*4) model-inner concat
    t1 = np.asarray(tel1["tiles"])
    t2 = np.asarray(tel2["tiles"])
    nb = t1.shape[1]
    assert t2.shape == (t1.shape[0], nb * 4)
    per_shard = t2.reshape(t1.shape[0], 4, nb)
    for l, w in enumerate(ways):
        if w > 1:     # 128-aligned shards partition the tile grid exactly
            assert (per_shard[l].sum(axis=0) == t1[l]).all(), l
        else:         # replicated: every peer counted the full layer
            assert (per_shard[l] == t1[l][None, :]).all(), l
    print("TEL_OK")
    """)
    assert "TEL_OK" in out


def test_failover_from_model_sharded_engine():
    """PR-7 placement-independence, extended to the model axis: lanes
    snapshot from a 2×2 (data × model) engine mid-window adopt onto a
    plain single-device engine and finish bit-identical to a run that
    never moved."""
    out = run_sub(SUB_PRELUDE + """
    rng = np.random.default_rng(4)
    cfg = dataclasses.replace(SNN_CONFIG, layer_sizes=(24, 16, 10),
                              num_steps=12)
    params_q = small_net(rng, cfg.layer_sizes)
    imgs = rng.integers(0, 256, (8, 24), dtype=np.uint8)

    base = SNNStreamEngine(params_q, cfg, batch_size=8, chunk_steps=3,
                           patience=10_000, seed=9, backend="reference")
    for im in imgs:
        base.submit(im)
    want = base.run()

    knobs = SNNStreamMeshConfig(num_devices=2, model_devices=2,
                                lanes_per_device=4, chunk_steps=3)
    src = make_stream_engine(params_q, cfg, knobs, patience=10_000,
                             seed=9, backend="reference")
    assert src.model_devices == 2 and src.model_ways == (2, 2)
    for im in imgs:
        src.submit(im)
    src.run(max_chunks=2)                 # mid-window: 6 of 12 steps done
    rows = src.snapshot_lanes()
    assert len(rows) == 8, len(rows)

    dst = SNNStreamEngine(params_q, cfg, batch_size=8, chunk_steps=3,
                          patience=10_000, seed=9, backend="reference")
    for rid, row in rows:
        dst.adopt(rid, row)
    got = dst.run()
    assert set(got) == set(want)
    for rid in want:
        assert as_tuple(got[rid]) == as_tuple(want[rid]), rid
    print("FAILOVER_OK")
    """)
    assert "FAILOVER_OK" in out


@settings(max_examples=4, deadline=None)
@given(seed=st.integers(0, 2**20), chunk_steps=st.integers(1, 8),
       burst=st.integers(1, 5),
       backend=st.sampled_from(["reference", "fused"]))
def test_random_admission_2d_mesh_matches_one_shot(seed, chunk_steps,
                                                   burst, backend):
    """Property: a random window split × a random admission schedule on a
    2-D (data × model) mesh retires every request bit-identical to a
    one-shot single-device reference window.  The model axis takes as
    many devices as are visible (capped at the hidden width's divisors):
    1 locally — the always-2-D mesh path with a trailing 1 axis — and a
    real 4-way shard in the CI multi-device lane."""
    from repro.configs.snn_mnist import SNNStreamMeshConfig, \
        make_stream_engine
    rng = np.random.default_rng(seed)
    n_dev = len(jax.devices())
    md = 4 if n_dev % 4 == 0 else (2 if n_dev % 2 == 0 else 1)
    cfg = dataclasses.replace(SNN_CONFIG, layer_sizes=(12, 8, 6),
                              num_steps=8)
    params_q = small_net(rng, cfg.layer_sizes)
    n_imgs = int(rng.integers(3, 9))
    imgs = rng.integers(0, 256, (n_imgs, 12), dtype=np.uint8)
    knobs = SNNStreamMeshConfig(num_devices=n_dev // md, model_devices=md,
                                lanes_per_device=2 * md,
                                chunk_steps=chunk_steps)
    eng = make_stream_engine(params_q, cfg, knobs, patience=10_000,
                             seed=seed, backend=backend)
    assert eng.model_devices == md
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


# ---- feasibility: WIDE goes resident-fused on a 4-way model axis ----------

def test_wide_resolves_fused_on_model_axis(monkeypatch):
    """The acceptance stack: SNN_CONFIG_WIDE (784-2048-2048-10) exceeds
    the VMEM budget single-device (auto → fused_streamed, explicit fused
    raises) but each 4-way model shard fits, so auto resolves to the
    resident ``fused`` backend."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cfg = SNN_CONFIG_WIDE
    n_layers = len(cfg.layer_sizes) - 1
    kw = dict(layer_sizes=cfg.layer_sizes, trace_steps=4, local_batch=256)
    assert snn.resolve_backend(cfg, "auto", n_layers,
                               **kw) == "fused_streamed"
    assert snn.resolve_backend(cfg, "auto", n_layers, model_shards=4,
                               **kw) == "fused"
    with pytest.raises(ValueError, match="VMEM"):
        snn.resolve_backend(cfg, "fused", n_layers, **kw)
    # the reason string names the model axis it was scoped to
    r = snn.fused_unsupported_reason(cfg, n_layers, cfg.layer_sizes,
                                     trace_steps=4, local_batch=256,
                                     model_shards=2)
    assert r is None or "2-way model axis" in r


def test_wide_shard_fits_vmem_budget():
    """The per-device weight shard of WIDE under a 4-way model axis stays
    inside the VMEM budget — the quantity the bench artifact commits."""
    sizes = SNN_CONFIG_WIDE.layer_sizes
    full = fused_snn.stack_vmem_bytes(sizes, num_steps=4)
    shard = fused_snn.stack_vmem_bytes(sizes, num_steps=4, model_shards=4)
    assert full > fused_snn.VMEM_BUDGET_BYTES
    assert shard <= fused_snn.VMEM_BUDGET_BYTES
    assert shard < full


def test_stack_vmem_bytes_unsharded_is_historical():
    """model_shards=1 must reproduce the historical estimate bit-for-bit
    — the resolution chain of every existing config is frozen."""
    for sizes in ((784, 10), (784, 128, 64, 10), (784, 2048, 2048, 10),
                  (12, 6), (300, 200, 100, 50)):
        for streamed in (False, True):
            a = fused_snn.stack_vmem_bytes(sizes, streamed=streamed)
            b = fused_snn.stack_vmem_bytes(sizes, streamed=streamed,
                                           model_shards=1)
            assert a == b, (sizes, streamed)


def test_layer_shard_ways():
    """Layers shard only where the model width divides the raw output
    size; everything replicates at model_shards<=1."""
    assert fused_snn.layer_shard_ways((784, 2048, 2048, 10), 4) == (4, 4, 1)
    assert fused_snn.layer_shard_ways((784, 2048, 2048, 10), 1) == (1, 1, 1)
    assert fused_snn.layer_shard_ways((24, 16, 10), 2) == (2, 2)
    assert fused_snn.layer_shard_ways((24, 15, 10), 2) == (1, 2)
    assert fused_snn.layer_shard_ways((784, 10), 0) == (1,)


# ---- mesh + partition-spec plumbing ---------------------------------------

def test_make_2d_device_mesh_validation():
    from repro.distributed.sharding import make_2d_device_mesh
    n = len(jax.devices())
    mesh = make_2d_device_mesh(n, 1)
    assert mesh.shape == {"data": n, "model": 1}
    mesh = make_2d_device_mesh(1, n, axis_names=("d", "m"))
    assert mesh.shape == {"d": 1, "m": n}
    # data_devices=None absorbs what the model axis leaves over
    mesh = make_2d_device_mesh(model_devices=n)
    assert mesh.shape == {"data": 1, "model": n}
    with pytest.raises(ValueError, match="distinct"):
        make_2d_device_mesh(1, 1, axis_names=("x", "x"))
    with pytest.raises(ValueError, match=">= 1"):
        make_2d_device_mesh(1, 0)
    with pytest.raises(ValueError, match="devices"):
        make_2d_device_mesh(n + 1, 1)
    with pytest.raises(ValueError, match="divide"):
        make_2d_device_mesh(model_devices=n + 1)


def test_weight_partition_specs():
    from repro.serve.snn_engine import weight_partition_specs
    assert weight_partition_specs((4, 4, 1), None) == (P(), P(), P())
    specs = weight_partition_specs((4, 4, 1), "model")
    assert specs == (P(None, "model"), P(None, "model"), P())


def test_lane_partition_specs_ignore_model_axis():
    """Placement-independence: lane state NEVER shards on the model axis
    — the same LaneState specs with or without one, which is what keeps
    snapshot/adopt rows mesh-agnostic (the failover contract)."""
    from repro.serve.snn_engine import lane_partition_specs
    a = lane_partition_specs(3, "data")
    b = lane_partition_specs(3, "data", model_axis="model")
    assert a == b
    leaves = jax.tree.leaves(b, is_leaf=lambda x: isinstance(x, P))
    assert all(s == P("data") for s in leaves)


def test_telemetry_partition_specs_model_axis():
    from repro.core.telemetry import telemetry_partition_specs
    t = telemetry_partition_specs("data")
    assert t.tiles_skipped == P(None, None, "data")
    t2 = telemetry_partition_specs("data", "model")
    assert t2.n_spk == t.n_spk and t2.n_en == t.n_en
    assert t2.tiles_skipped == P(None, None, ("data", "model"))


def test_engine_rejects_model_axis_equal_to_data_axis():
    from repro.serve import ShardedSNNStreamEngine
    rng = np.random.default_rng(0)
    params_q = small_net(rng, (12, 6))
    with pytest.raises(ValueError, match="differ"):
        ShardedSNNStreamEngine(params_q, SNN_CONFIG, axis_name="data",
                               model_axis_name="data",
                               backend="reference")


def test_partial_contraction_op_matches_dense():
    """The per-shard Pallas partial contraction is bit-identical to the
    dense integer contraction on the same operands, and its skip counter
    matches the jnp tile-geometry mirror."""
    from repro.core import lif
    from repro.core.telemetry import layer_tile_skips
    from repro.kernels import ops
    rng = np.random.default_rng(5)
    for B, n_in, n_out in ((4, 40, 24), (8, 200, 130), (3, 12, 6)):
        x = jnp.asarray(rng.random((B, n_in)) < 0.15)
        en = jnp.asarray(rng.random((B, n_out)) < 0.8)
        w = jnp.asarray(rng.integers(-256, 256, (n_in, n_out)), jnp.int32)
        cur, skipped = ops.partial_contraction_op(x, en, w,
                                                  sparse_skip=True)
        want = lif.synaptic_current_int(x, w, en)
        assert (np.asarray(cur) == np.asarray(want)).all(), (B, n_in)
        mirror = layer_tile_skips(x, en, sparse_skip=True)
        assert (np.asarray(skipped) == np.asarray(mirror)).all()
