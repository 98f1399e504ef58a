"""Conv-pool layers: the streaming engine against a plain numpy reference
of the layer equations (``conv_snn_reference``, which imports nothing of
the program), on the ``reference`` backend and on ``fused`` in Pallas
interpret mode; chunked against one-shot; the conv stage's tile counter
against its jnp mirror and the kernel oracle; and the descriptors carried
through fingerprints, the wire and rollouts, and refused with a reason
where a path cannot run them."""

import numpy as np
import pytest

import jax.numpy as jnp

import conv_snn_reference as plain
from repro.configs.snn_mnist import SNN_CONFIG_CONV
from repro.core import prng
from repro.core.layers import ConvPool, Dense, weight_shapes
from repro.core.lif import LIFConfig
from repro.core.snn import (SNNConfig, fused_unsupported_reason,
                            resolve_backend, snn_apply_int,
                            snn_window_chunk, snn_window_init)
from repro.core.telemetry import layer_tile_skips, tiles_total
from repro.kernels import fused_snn, ops
from repro.kernels import ref as kref
from repro.serve import SNNStreamEngine
from repro.serve.wire import snn_cfg_from_wire, snn_cfg_to_wire
from repro.tune.fingerprint import config_fingerprint

LIF = LIFConfig(decay_shift=4, v_threshold=128, v_rest=0)
# 14x14 pixels, 3x3 convs to 4 and 8 channels, each 2x2-pooled, FC 32->10
SMALL = (ConvPool(4, (1, 14, 14), 3, 2), ConvPool(8, (4, 6, 6), 3, 2),
         Dense(10))


def _plain_layers(layers):
    return [("conv", d.c_out, d.in_shape, d.kernel, d.pool)
            if isinstance(d, ConvPool) else ("dense", d.n) for d in layers]


def _weights(layers, n_in, seed=0, gain=256.0):
    rng = np.random.default_rng(seed)
    out = []
    for s in weight_shapes(n_in, layers):
        fan_in = np.prod(s[1:]) if len(s) == 4 else s[0]
        w = np.round(rng.normal(size=s) * gain / np.sqrt(fan_in))
        out.append(np.clip(w, -256, 255).astype(np.int16))
    return out


def _cfg(layers, **kw):
    return SNNConfig(layers=layers, lif=LIF, qat=False, **kw)


def _serve(cfg, ws, pixels, backend, *, seed=5, lanes=8, chunk=4):
    eng = SNNStreamEngine({"layers": [{"w_q": jnp.asarray(w)} for w in ws]},
                          cfg, batch_size=lanes, chunk_steps=chunk,
                          seed=seed, backend=backend, dispatch_cache=False)
    for i, p in enumerate(pixels):
        eng.submit(p, request_id=i)
    res = eng.run()
    n = len(pixels)
    return {"pred": np.array([res[i].pred for i in range(n)]),
            "steps": np.array([res[i].steps for i in range(n)]),
            "adds": np.array([res[i].adds for i in range(n)]),
            "counts": np.stack([res[i].spike_counts for i in range(n)])}


def _assert_served_equal(got, want):
    for k in ("pred", "steps", "counts", "adds"):
        np.testing.assert_array_equal(got[k], want[k], err_msg=k)


@pytest.mark.parametrize("backend", ["reference", "fused"])
def test_engine_equals_plain_reference_small(backend):
    ws = _weights(SMALL, 196)
    px = np.random.default_rng(1).integers(0, 256, (20, 196), np.uint8)
    got = _serve(_cfg(SMALL), ws, px, backend)
    want = plain.serve(_plain_layers(SMALL), ws, px, 5 + np.arange(20))
    _assert_served_equal(got, want)
    assert (want["steps"] < 20).any()          # the gate retired some early


def test_engine_equals_plain_reference_published_widths():
    cfg = SNN_CONFIG_CONV
    assert cfg.layer_sizes == (784, 1728, 1024, 10)
    ws = _weights(cfg.layers, 784, seed=3)
    px = np.random.default_rng(4).integers(0, 256, (6, 784), np.uint8)
    got = _serve(cfg, ws, px, "reference", seed=11, lanes=4)
    want = plain.serve(_plain_layers(cfg.layers), ws, px, 11 + np.arange(6))
    _assert_served_equal(got, want)


@pytest.mark.parametrize("backend", ["reference", "fused"])
def test_chunked_equals_one_shot(backend):
    cfg = _cfg(SMALL, active_pruning=True, readout="first_spike")
    params = {"layers": [{"w_q": jnp.asarray(w)}
                         for w in _weights(SMALL, 196, seed=2, gain=300.0)]}
    px = jnp.asarray(np.random.default_rng(5).integers(0, 256, (9, 196),
                                                       np.uint8))
    rng = prng.seed_state(7, px.shape)
    whole = snn_apply_int(params, px, rng, cfg, backend=backend)
    st = snn_window_init(params, rng, cfg)
    chunks = []
    for n in (3, 8, 9):
        st, c = snn_window_chunk(params, px, st, cfg, chunk_steps=n,
                                 backend=backend)
        chunks.append(c)
    np.testing.assert_array_equal(st.counts, whole["spike_counts"])
    np.testing.assert_array_equal(st.first, whole["first_spike_t"])
    np.testing.assert_array_equal(st.rng, whole["prng_state"])
    np.testing.assert_array_equal(
        np.concatenate([c["active_adds"] for c in chunks]),
        whole["active_adds"])
    np.testing.assert_array_equal(
        np.concatenate([c["telemetry"].tiles_skipped for c in chunks]),
        whole["telemetry"].tiles_skipped)


@pytest.mark.parametrize("pruning", [False, True])
def test_conv_tile_counter_mirror_and_oracle(pruning):
    """The kernel's per-layer skipped tile pairs of a conv network equal
    core.telemetry's mirror (the reference backend) and the kernel
    oracle's independent count, and the totals are the geometry's."""
    ws = tuple(jnp.asarray(w) for w in _weights(SMALL, 196, seed=6,
                                                gain=300.0))
    px = jnp.asarray(np.random.default_rng(8).integers(0, 256, (11, 196),
                                                       np.uint8))
    rng = prng.seed_state(9, px.shape)
    kw = dict(num_steps=20, decay_shift=4, v_threshold=128,
              active_pruning=pruning, sparse_skip=True)
    kern = ops.fused_snn_stack_op(px, rng, ws, layers=SMALL, **kw)
    oracle = kref.fused_snn_stack_ref(px, rng, ws, layers=SMALL, **kw)
    cfg = _cfg(SMALL, active_pruning=pruning, sparse_skip=True)
    mirror = snn_apply_int({"layers": [{"w_q": w} for w in ws]}, px, rng,
                           cfg, backend="reference")["telemetry"]
    got = np.asarray(kern["telemetry"].tiles_skipped)
    np.testing.assert_array_equal(got, oracle["telemetry"].tiles_skipped)
    np.testing.assert_array_equal(got, mirror.tiles_skipped)
    for f in ("n_spk", "n_en"):
        np.testing.assert_array_equal(getattr(kern["telemetry"], f),
                                      getattr(mirror, f))
    np.testing.assert_array_equal(kern["active_adds"],
                                  oracle["active_adds"])
    if pruning:
        assert got[:, :2].sum() > 0            # conv tiles were skipped
    total = tiles_total(cfg.layer_sizes, SMALL)
    assert total == (2 * 2 * 3 * 2, 1 * 2 * 3 * 2, 2)
    assert (got <= np.array(total)[None, :, None]).all()
    # the mirror alone, one layer and one step, against the kernel's count
    geoms = fused_snn.stack_geometry(196, SMALL)
    x = jnp.asarray(np.random.default_rng(3).random((11, 196)) < 0.02)
    en = jnp.asarray(np.random.default_rng(4).random((11, 144)) < 0.05)
    one = layer_tile_skips(x, en, sparse_skip=True, geom=geoms[0])
    want = kref._conv_tile_skips_ref(x, en, SMALL[0],
                                     kref._conv_splits(SMALL)[0],
                                     sparse_skip=True)
    np.testing.assert_array_equal(one, want)


def test_descriptors_build_the_config():
    assert SNN_CONFIG_CONV.layer_sizes == (784, 1728, 1024, 10)
    assert SNN_CONFIG_CONV.layers[0].out_shape == (12, 12, 12)
    # an all-dense descriptor list is the layer_sizes config
    dense = SNNConfig(layer_sizes=(784, 128, 10),
                      layers=(Dense(128), Dense(10)))
    assert dense == SNNConfig(layer_sizes=(784, 128, 10))
    assert dense.layers is None
    with pytest.raises(ValueError, match="does not match"):
        SNNConfig(layers=(ConvPool(4, (1, 14, 14), 3, 2),
                          ConvPool(8, (4, 8, 8), 3, 2), Dense(10)))
    with pytest.raises(ValueError, match="last layer must be dense"):
        SNNConfig(layers=(ConvPool(4, (1, 14, 14), 3, 2),))
    with pytest.raises(ValueError, match="pools"):
        ConvPool(4, (1, 13, 13), 3, 2)


def test_fingerprint_keeps_conv_and_dense_apart():
    dense = SNNConfig(layer_sizes=(784, 1728, 1024, 10),
                      lif=SNN_CONFIG_CONV.lif, qat=False)
    assert dense.layer_sizes == SNN_CONFIG_CONV.layer_sizes
    assert config_fingerprint(dense) != config_fingerprint(SNN_CONFIG_CONV)
    other = SNNConfig(layers=(ConvPool(12, (1, 28, 28), 5, 2),
                              ConvPool(64, (12, 12, 12), 5, 2), Dense(10)),
                      lif=SNN_CONFIG_CONV.lif, qat=False)
    assert config_fingerprint(other) == config_fingerprint(SNN_CONFIG_CONV)


def test_wire_round_trips_the_descriptors():
    import json
    d = json.loads(json.dumps(snn_cfg_to_wire(SNN_CONFIG_CONV)))
    back = snn_cfg_from_wire(d)
    assert back == SNN_CONFIG_CONV and back.layers == SNN_CONFIG_CONV.layers
    bad = dict(d, layers=[dict(e, kind="pool") for e in d["layers"]])
    with pytest.raises(ValueError, match="unknown layer kind"):
        snn_cfg_from_wire(bad)
    plain_cfg = SNNConfig(layer_sizes=(784, 10))
    assert snn_cfg_from_wire(json.loads(json.dumps(
        snn_cfg_to_wire(plain_cfg)))) == plain_cfg


def test_rollout_refuses_other_descriptors():
    """The engine's descriptors are fixed: a rollout whose weights do not
    have the shapes they give is refused, whether it would serve the same
    flat widths densely or other conv kernels."""
    ws = _weights(SMALL, 196)
    eng = SNNStreamEngine({"layers": [{"w_q": jnp.asarray(w)} for w in ws]},
                          _cfg(SMALL), batch_size=8, backend="reference",
                          dispatch_cache=False)
    dense = tuple(Dense(n) for n in (144, 32, 10))   # the same flat widths
    other_k = (ConvPool(4, (1, 14, 14), 5, 2), ConvPool(8, (4, 5, 5), 2, 2),
             Dense(10))                               # 5x5 and 2x2 kernels
    for other in (dense, other_k):
        with pytest.raises(ValueError, match="cannot change the topology"):
            eng.begin_rollout({"layers": [{"w_q": jnp.asarray(w)}
                                          for w in _weights(other, 196)]})
    assert eng.begin_rollout(
        {"layers": [{"w_q": jnp.asarray(w)} for w in ws]}) == 1


@pytest.mark.parametrize("path", ["fused_streamed", "staged", "model_mesh",
                                  "data_mesh"])
def test_paths_without_conv_refuse_with_a_reason(path):
    cfg = SNN_CONFIG_CONV
    if path == "fused_streamed":
        reason = fused_unsupported_reason(cfg, 3, streamed=True)
        assert reason and "conv-pool" in reason
        with pytest.raises(ValueError, match="conv-pool"):
            resolve_backend(cfg, "fused_streamed", 3)
    elif path == "staged":
        with pytest.raises(ValueError, match="conv-pool"):
            resolve_backend(cfg, "staged", 3)
    else:
        if path == "model_mesh":
            reason = fused_unsupported_reason(cfg, 3, model_shards=4)
            assert reason and "conv-pool" in reason
        from types import SimpleNamespace

        from repro.serve import ShardedSNNStreamEngine
        # a 1 x 4 (data x model) or 4-way data mesh, described: the
        # sharded engine refuses conv-pool layers before it places anything
        shape = ({"data": 1, "model": 4} if path == "model_mesh"
                 else {"data": 4})
        mesh = SimpleNamespace(shape=shape, axis_names=tuple(shape))
        ws = _weights(cfg.layers, 784)
        with pytest.raises(ValueError, match="conv-pool"):
            ShardedSNNStreamEngine(
                {"layers": [{"w_q": jnp.asarray(w)} for w in ws]}, cfg,
                mesh=mesh, dispatch_cache=False)
