"""Event-driven sparse megakernel: int8 packing, tile skipping, streaming.

Contracts under test:
  * the int8 weight packing (hi/lo planes) is exact over the full signed
    9-bit code range and matches the independent ``ref.weight_pack_ref``
    oracle;
  * sparse-skipping fused == dense fused == reference — predictions,
    spike counts, first-spike latches, membrane traces AND the
    executed-add energy counter — across spike densities (0%,
    paper-typical, ~100%), random pruning masks and random window chunk
    splits (property test);
  * the same bit-identity holds through the single-device and sharded
    streaming engines, including early-exit retirement;
  * ``fused_streamed`` (weights double-buffered out of HBM) matches the
    reference on oversized stacks in ONE Pallas launch, while an explicit
    ``fused`` request raises; ``resolve_backend`` walks the
    fused → fused_streamed → staged chain on TPU;
  * ``fused_snn.spike_dot``, the kernels' two-pass int8 contraction over
    the packed planes, equals the int32 oracle across densities,
    including all-zero spike tiles.

The suite is REPRO_SPARSE_SKIP-sensitive by design: CI runs it twice with
the env default forced on and off (plus the explicit parametrisations
below), so a regression in either tile path cannot hide behind the other.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.configs.snn_mnist import (SNN_CONFIG, SNN_CONFIG_DEEP,
                                     SNN_CONFIG_WIDE)
from repro.core import prng, snn
from repro.kernels import fused_snn, ops, ref
from repro.serve import ShardedSNNStreamEngine, SNNStreamEngine

_KEYS = ["spike_counts", "v_trace", "first_spike_t", "v_final",
         "active_adds", "prng_state", "steps"]

# pixel levels spanning the density axis: px > r (uniform u8) spikes with
# probability px/256 — 0%, the paper-typical MNIST foreground rate, ~100%
DENSITY_PX = {"zero": 0, "paper": 33, "full": 255}


def _net(rng, sizes):
    return {"layers": [
        {"w_q": jnp.asarray(rng.integers(-256, 256, (a, b)), jnp.int16),
         "scale": jnp.float32(1.0)}
        for a, b in zip(sizes[:-1], sizes[1:])]}


def test_weight_packing_roundtrip():
    """Every signed 9-bit code packs/unpacks exactly, and the kernel's
    packer agrees with the independent oracle plane-for-plane."""
    codes = np.arange(-256, 256, dtype=np.int16).reshape(32, 16)
    hi_ref, lo_ref = ref.weight_pack_ref(codes)
    packed = np.asarray(fused_snn.pack_weights(jnp.asarray(codes)))
    np.testing.assert_array_equal(packed[0], hi_ref)
    np.testing.assert_array_equal(packed[1], lo_ref)
    rebuilt = 2 * packed[0].astype(np.int32) + packed[1]
    np.testing.assert_array_equal(rebuilt, codes.astype(np.int32))
    assert set(np.unique(packed[1])) <= {0, 1}
    with pytest.raises(ValueError, match="9-bit"):
        ref.weight_pack_ref(np.asarray([256], np.int16))


def test_fused_rejects_unpackable_codes(rng):
    """Codes outside the signed 9-bit range would wrap the int8 hi plane
    silently — the fused backends must refuse them where the weights are
    concrete (the pre-packing kernel was exact on full int16)."""
    params_q = _net(rng, (32, 10))
    params_q["layers"][0]["w_q"] = jnp.full((32, 10), 300, jnp.int16)
    px = jnp.zeros((2, 32), jnp.uint8)
    state = prng.seed_state(1, px.shape)
    cfg = dataclasses.replace(SNN_CONFIG, layer_sizes=(32, 10), num_steps=4)
    with pytest.raises(ValueError, match="9-bit"):
        snn.snn_apply_int(params_q, px, state, cfg, backend="fused")
    with pytest.raises(ValueError, match="9-bit"):
        SNNStreamEngine(params_q, cfg, batch_size=2, backend="fused")
    # the staged kernels take the same int8 planes
    with pytest.raises(ValueError, match="9-bit"):
        snn.snn_apply_int(params_q, px, state, cfg, backend="staged")
    # the un-packing backends still accept wider codes
    snn.snn_apply_int(params_q, px, state, cfg, backend="reference")
    SNNStreamEngine(params_q, cfg, batch_size=2, backend="reference")


@pytest.mark.parametrize("density", sorted(DENSITY_PX))
@pytest.mark.parametrize("sparse_skip", [False, True])
@pytest.mark.parametrize("prune", [False, True])
def test_sparse_dense_ref_bit_identity(rng, density, sparse_skip, prune):
    """Kernel vs oracle at the density extremes and the paper-typical
    rate, dense and sparse tile paths, with and without active pruning."""
    sizes = (300, 140, 10)
    b = 5
    px = jnp.full((b, sizes[0]), DENSITY_PX[density], jnp.uint8)
    state = prng.seed_state(11, (b, sizes[0]))
    weights = tuple(l["w_q"] for l in _net(rng, sizes)["layers"])
    kw = dict(num_steps=7, decay_shift=4, v_threshold=128,
              active_pruning=prune)
    got = ops.fused_snn_stack_op(px, state, weights,
                                 sparse_skip=sparse_skip, interpret=True,
                                 **kw)
    want = ref.fused_snn_stack_ref(px, state, weights, **kw)
    for key in _KEYS:
        np.testing.assert_array_equal(np.asarray(got[key]),
                                      np.asarray(want[key]), err_msg=key)


@pytest.mark.parametrize("streamed", [False, True])
def test_random_pruning_masks_skip_paths(rng, streamed):
    """Carried enable masks with randomly pruned neurons (whole output
    tiles included) drive the prune-skip predicate without changing
    results vs the oracle."""
    sizes = (256, 256, 10)
    b = 4
    weights = tuple(l["w_q"] for l in _net(rng, sizes)["layers"])
    px = jnp.asarray(rng.integers(0, 256, (b, sizes[0]), dtype=np.uint8))
    state = prng.seed_state(5, (b, sizes[0]))
    # layer-0 mask prunes one whole 128-lane tile (fully-skippable output
    # tile) plus random scatter; layer-1 mask is random scatter only
    en0 = np.ones((b, sizes[1]), bool)
    en0[:, :128] = False
    en0 &= rng.random((b, sizes[1])) < 0.7
    en1 = rng.random((b, sizes[2])) < 0.5
    init = {
        "v": (jnp.zeros((b, sizes[1]), jnp.int32),
              jnp.zeros((b, sizes[2]), jnp.int32)),
        "en": (jnp.asarray(en0), jnp.asarray(en1)),
        "counts": jnp.zeros((b, sizes[2]), jnp.int32),
        "first": jnp.full((b, sizes[2]), 6, jnp.int32),
        "steps": jnp.zeros((b,), jnp.int32),
    }
    kw = dict(num_steps=6, decay_shift=4, v_threshold=128,
              active_pruning=True)
    want = ref.fused_snn_stack_ref(px, state, weights, init=init, **kw)
    for sparse_skip in (False, True):
        got = ops.fused_snn_stack_op(px, state, weights, init=init,
                                     sparse_skip=sparse_skip,
                                     streamed=streamed, interpret=True,
                                     **kw)
        for key in _KEYS:
            np.testing.assert_array_equal(
                np.asarray(got[key]), np.asarray(want[key]),
                err_msg=f"{key} sparse_skip={sparse_skip}")


@settings(max_examples=6, deadline=None)
@given(seed=st.integers(1, 2**31),
       density=st.sampled_from(sorted(DENSITY_PX)),
       n_chunks=st.integers(1, 4),
       backend=st.sampled_from(["fused", "fused_streamed"]))
def test_sparse_chunked_property(seed, density, n_chunks, backend):
    """Property: sparse-skipping fused == dense fused == reference over
    random window chunk splits, at every density level, on the resident
    AND weight-streamed kernels — state, traces and add counters."""
    rng = np.random.default_rng(seed % (2**31))
    cfg = dataclasses.replace(SNN_CONFIG_DEEP, num_steps=8)
    params_q = _net(rng, cfg.layer_sizes)
    px = jnp.asarray(
        np.minimum(rng.integers(0, 256, (4, cfg.n_in)),
                   DENSITY_PX[density]).astype(np.uint8))
    state0 = prng.seed_state(seed, px.shape)
    T = cfg.num_steps
    cuts = sorted(rng.choice(np.arange(1, T), size=min(n_chunks - 1, T - 1),
                             replace=False).tolist()) if n_chunks > 1 else []
    bounds = [0] + cuts + [T]

    def run(cfg_v, be):
        st_ = snn.snn_window_init(params_q, state0, cfg_v)
        traces, adds = [], []
        for lo, hi in zip(bounds[:-1], bounds[1:]):
            st_, out = snn.snn_window_chunk(params_q, px, st_, cfg_v,
                                            chunk_steps=hi - lo, backend=be)
            traces.append(np.asarray(out["v_trace"]))
            adds.append(np.asarray(out["active_adds"]))
        return st_, np.concatenate(traces), np.concatenate(adds)

    ref_state, ref_tr, ref_adds = run(
        dataclasses.replace(cfg, sparse_skip=False), "reference")
    for sparse_skip in (False, True):
        got_state, got_tr, got_adds = run(
            dataclasses.replace(cfg, sparse_skip=sparse_skip), backend)
        np.testing.assert_array_equal(got_tr, ref_tr)
        np.testing.assert_array_equal(got_adds, ref_adds)
        for field in snn.SNNWindowState._fields:
            a, b = getattr(got_state, field), getattr(ref_state, field)
            for x, y in zip(a if isinstance(a, tuple) else [a],
                            b if isinstance(b, tuple) else [b]):
                np.testing.assert_array_equal(
                    np.asarray(x), np.asarray(y),
                    err_msg=f"{field} skip={sparse_skip} split={bounds}")


def _engine_results(eng, imgs):
    ids = [eng.submit(im) for im in imgs]
    res = eng.run()
    return {i: (res[i].pred, res[i].steps, res[i].adds, res[i].early_exit,
                tuple(res[i].spike_counts.tolist())) for i in ids}


@settings(max_examples=4, deadline=None)
@given(seed=st.integers(1, 2**31),
       density=st.sampled_from(sorted(DENSITY_PX)))
def test_engines_sparse_bit_identity(seed, density):
    """Single-device AND sharded streaming engines: the sparse and dense
    fused chunk paths reproduce the reference engine request-for-request
    (early-exit steps and frozen add counters included)."""
    rng = np.random.default_rng(seed % (2**31))
    cfg = dataclasses.replace(SNN_CONFIG, num_steps=8)
    params_q = _net(rng, cfg.layer_sizes)
    imgs = np.minimum(rng.integers(0, 256, (5, cfg.n_in)),
                      DENSITY_PX[density]).astype(np.uint8)

    want = _engine_results(
        SNNStreamEngine(params_q, dataclasses.replace(cfg, sparse_skip=False),
                        batch_size=2, chunk_steps=3, patience=2, seed=seed,
                        backend="reference"),
        imgs)
    for sparse_skip in (False, True):
        cfg_v = dataclasses.replace(cfg, sparse_skip=sparse_skip)
        got = _engine_results(
            SNNStreamEngine(params_q, cfg_v, batch_size=2, chunk_steps=3,
                            patience=2, seed=seed, backend="fused"), imgs)
        assert got == want, f"single-device sparse_skip={sparse_skip}"
        n_dev = len(jax.devices())
        sharded = _engine_results(
            ShardedSNNStreamEngine(params_q, cfg_v,
                                   lanes_per_device=2, chunk_steps=3,
                                   patience=2, seed=seed, backend="fused"),
            imgs)
        assert sharded == want, \
            f"sharded({n_dev} dev) sparse_skip={sparse_skip}"


def test_streamed_gated_engine_matches_reference(rng):
    """fused_streamed through the streaming engine (gate in-kernel,
    double-buffered weights) == reference engine, incl. early exit."""
    cfg = dataclasses.replace(SNN_CONFIG_DEEP, num_steps=8)
    params_q = _net(rng, cfg.layer_sizes)
    imgs = rng.integers(0, 256, (5, cfg.n_in), dtype=np.uint8)
    want = _engine_results(
        SNNStreamEngine(params_q, cfg, batch_size=2, chunk_steps=3,
                        patience=2, seed=3, backend="reference"), imgs)
    got = _engine_results(
        SNNStreamEngine(params_q, cfg, batch_size=2, chunk_steps=3,
                        patience=2, seed=3, backend="fused_streamed"), imgs)
    assert got == want
    assert any(r[3] for r in want.values()), \
        "test should exercise early exit"


def test_streamed_oversized_single_launch(rng, monkeypatch):
    """With the VMEM budget shrunk so SNN_CONFIG_DEEP no longer fits
    resident, explicit fused raises, fused_streamed still runs the whole
    stack in ONE Pallas launch, bit-identical to the reference."""
    cfg = dataclasses.replace(SNN_CONFIG_DEEP, num_steps=4)
    params_q = _net(rng, cfg.layer_sizes)
    assert snn.fused_unsupported_reason(cfg, 3, cfg.layer_sizes) is None
    monkeypatch.setattr(fused_snn, "VMEM_BUDGET_BYTES", 400_000)
    assert snn.fused_unsupported_reason(cfg, 3, cfg.layer_sizes) is not None
    assert snn.fused_unsupported_reason(cfg, 3, cfg.layer_sizes,
                                        streamed=True) is None
    px = jnp.asarray(rng.integers(0, 256, (3, cfg.n_in), dtype=np.uint8))
    state = prng.seed_state(17, px.shape)
    with pytest.raises(ValueError, match="fused_streamed"):
        snn.snn_apply_int(params_q, px, state, cfg, backend="fused")
    out_s = snn.snn_apply_int(params_q, px, state, cfg,
                              backend="fused_streamed")
    out_r = snn.snn_apply_int(params_q, px, state, cfg, backend="reference")
    for key in ("pred", "spike_counts", "v_trace", "first_spike_t",
                "active_adds", "prng_state"):
        np.testing.assert_array_equal(np.asarray(out_s[key]),
                                      np.asarray(out_r[key]), err_msg=key)
    jaxpr = str(jax.make_jaxpr(
        lambda p, a, b: snn.snn_apply_int(p, a, b, cfg,
                                          backend="fused_streamed")
        ["spike_counts"])(params_q, px, state))
    assert jaxpr.count("pallas_call") == 1


def test_resolve_backend_streamed_chain(monkeypatch):
    """On TPU, ``auto`` walks fused → fused_streamed → staged by VMEM
    feasibility; explicit requests raise exactly when their realisation
    cannot run."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    # fits resident
    assert snn.resolve_backend(SNN_CONFIG, "auto", 1) == "fused"
    # over the residency budget, streaming working set fits
    wide = SNN_CONFIG_WIDE.layer_sizes
    assert snn.resolve_backend(SNN_CONFIG_WIDE, "auto", 3,
                               layer_sizes=wide) == "fused_streamed"
    assert snn.resolve_backend(SNN_CONFIG_WIDE, "fused_streamed", 3,
                               layer_sizes=wide) == "fused_streamed"
    with pytest.raises(ValueError, match="fused_streamed"):
        snn.resolve_backend(SNN_CONFIG_WIDE, "fused", 3, layer_sizes=wide)
    # so wide even the 2-slot slab scratch blows the budget → staged
    huge = (784, 1 << 16, 10)
    cfg_huge = dataclasses.replace(SNN_CONFIG, layer_sizes=huge)
    assert snn.resolve_backend(cfg_huge, "auto", 2,
                               layer_sizes=huge) == "staged"
    with pytest.raises(ValueError, match="staged"):
        snn.resolve_backend(cfg_huge, "fused_streamed", 2, layer_sizes=huge)


@settings(max_examples=8, deadline=None)
@given(seed=st.integers(0, 2**31), b=st.integers(1, 9),
       n_in=st.integers(1, 300), n_out=st.integers(1, 140),
       density=st.sampled_from([0.0, 0.1, 0.5, 1.0]))
def test_spike_dot_matches_oracle(seed, b, n_in, n_out, density):
    """Property: spike_dot over the packed planes == the int32 oracle
    across densities and shapes, incl. all-zero spike tiles."""
    rng = np.random.default_rng(seed)
    spikes = jnp.asarray(
        (rng.random((b, n_in)) < density).astype(np.uint8))
    w = jnp.asarray(rng.integers(-256, 256, (n_in, n_out)), jnp.int16)
    want = np.asarray(ref.spike_matmul_ref(spikes, w))
    got = np.asarray(fused_snn.spike_dot(spikes, fused_snn.pack_weights(w)))
    np.testing.assert_array_equal(got, want)
