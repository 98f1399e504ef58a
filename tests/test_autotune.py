"""Wall-clock autotuner + persisted dispatch cache (repro.tune).

Contracts under test:

  * the config fingerprint is stable across processes for the same
    serving identity, diverges on any identity-bearing field, and
    ignores training-only fields — tuned shapes can never leak across
    networks (a wrong-config lookup is a miss, not an adoption);
  * the versioned cache codec round-trips; corrupt, stale-codec and
    future-codec files are rejected with actionable messages and the
    engines FALL BACK to static defaults instead of crashing (mirroring
    the ``serve.wire`` codec pattern);
  * ``REPRO_DISPATCH_CACHE`` arms the single-device engine, the sharded
    engine (keyed by its 2-D mesh shape) and the serving tier, each
    recording a :class:`CacheDecision`;
  * a cache-armed engine is prediction-bit-identical to the static
    default engine — the cache may only change *when* work happens;
  * explicit constructor arguments beat tuned values knob by knob;
  * ``block_b`` plumbs through the fused stack op value-neutrally and
    invalid blocks are rejected;
  * the proportional controller shrink converges in one observation
    under heavy retirement, is exactly one step AT the trigger
    fraction, clamps at ``min_chunk_steps``, and remains a frozen-mode
    no-op (the PR 8 speculation-discard guard only needs *any* retune
    to land between dispatches — pinned in test_sharded_engine);
  * ``resolve_backend`` consults a cache hit under ``auto`` and ignores
    the cache for explicit backend requests;
  * the tuner itself: default measured first, winner never slower than
    the default, every candidate bit-identical to the baseline.
"""

import dataclasses
import json
import os
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.snn_mnist import SNN_CONFIG
from repro.core import snn
from repro.serve import (AdaptiveDispatchConfig, ShardedSNNStreamEngine,
                         SNNStreamEngine)
from repro.serve.router import SNNServingTier
from repro.serve.telemetry import ChunkSummary, make_controller
from repro.tune import (ArrivalSchedule, AutotuneConfig, CacheDecision,
                        DispatchCache, DispatchCacheError, TunedShapes,
                        autotune_engine, cache_key, config_fingerprint,
                        decide_dispatch, device_kind_now,
                        fingerprint_payload, measure, serve_schedule,
                        write_cache)
from repro.tune.cache import CACHE_CODEC_VERSION, ENV_DISPATCH_CACHE


def _net(rng, sizes):
    return {"layers": [
        {"w_q": jnp.asarray(rng.integers(-256, 256, (a, b)), jnp.int16),
         "scale": jnp.float32(1.0)}
        for a, b in zip(sizes[:-1], sizes[1:])]}


def _small_cfg(**kw):
    kw.setdefault("layer_sizes", (16, 10))
    kw.setdefault("num_steps", 8)
    return dataclasses.replace(SNN_CONFIG, **kw)


def _tuned(**kw):
    base = dict(chunk_steps=3, block_b=8, lanes_per_device=4,
                backend="reference")
    base.update(kw)
    return TunedShapes(**base)


def _write(tmp_path, cfg, tuned=None, mesh_shapes=((1,),),
           name="cache.json", backend="auto"):
    """Persist a cache armed for ``cfg`` on this host; returns the path."""
    tuned = tuned or _tuned()
    cache = DispatchCache()
    fp = config_fingerprint(cfg)
    for mesh in mesh_shapes:
        cache.put(cache_key(fp, device_kind_now(), mesh, backend), tuned)
    return cache.save(str(tmp_path / name))


def _v1_blob(cfg):
    """A codec-v1 cache file with an entry keyed for ``cfg`` on this host.

    v1 entries also carried the density-dispatch threshold that v2
    dropped; the version alone refuses the file, before any entry is
    read."""
    key = cache_key(config_fingerprint(cfg), device_kind_now(), (1,),
                    "auto")
    return {"codec_version": 1,
            "entries": {key: {"chunk_steps": 3, "block_b": 8,
                              "lanes_per_device": 4, "threshold": 0.2,
                              "backend": "reference"}}}


def _bits(results):
    return {int(rid): (int(r.pred), int(r.steps),
                       tuple(r.spike_counts.tolist()))
            for rid, r in results.items()}


# ---------------------------------------------------------------------------
# fingerprint
# ---------------------------------------------------------------------------

def test_fingerprint_stable_and_diverges():
    cfg = _small_cfg()
    assert config_fingerprint(cfg) == config_fingerprint(
        dataclasses.replace(cfg))
    # every identity-bearing axis moves the fingerprint
    for other in (dataclasses.replace(cfg, num_steps=9),
                  dataclasses.replace(cfg, layer_sizes=(16, 12, 10)),
                  dataclasses.replace(cfg, readout="first_spike"),
                  dataclasses.replace(cfg, sparse_skip=False)):
        assert config_fingerprint(other) != config_fingerprint(cfg)
    # training-only fields do not (two configs that SERVE identically
    # share tuned shapes even if trained differently)
    assert config_fingerprint(dataclasses.replace(cfg, qat=not cfg.qat)) \
        == config_fingerprint(cfg)
    payload = fingerprint_payload(cfg)
    assert "qat" not in payload and payload["num_steps"] == 8


# ---------------------------------------------------------------------------
# cache codec: roundtrip + rejection ladder
# ---------------------------------------------------------------------------

def test_cache_roundtrip(tmp_path):
    cfg = _small_cfg()
    path = _write(tmp_path, cfg, mesh_shapes=((1,), (2, 1)))
    loaded = DispatchCache.load(path)
    d = loaded.lookup(fingerprint=config_fingerprint(cfg),
                      device_kind=device_kind_now(), mesh_shape=(1,),
                      backend=None)       # None normalizes to "auto"
    assert d.hit and d.tuned == _tuned() and d.source == path
    miss = loaded.lookup(fingerprint=config_fingerprint(cfg),
                         device_kind=device_kind_now(), mesh_shape=(4, 1),
                         backend="auto")
    assert not miss.hit and "static defaults" in miss.reason


def test_cache_rejects_corrupt_stale_future(tmp_path):
    corrupt = tmp_path / "corrupt.json"
    corrupt.write_text("{nope")
    with pytest.raises(DispatchCacheError, match="not valid JSON"):
        DispatchCache.load(str(corrupt))

    future = tmp_path / "future.json"
    future.write_text(json.dumps(
        {"codec_version": CACHE_CODEC_VERSION + 1, "entries": {}}))
    with pytest.raises(DispatchCacheError, match="newer build"):
        DispatchCache.load(str(future))

    stale = tmp_path / "stale.json"
    stale.write_text(json.dumps(_v1_blob(_small_cfg())))
    with pytest.raises(DispatchCacheError, match="stale codec v1"):
        DispatchCache.load(str(stale))

    noversion = tmp_path / "nover.json"
    noversion.write_text(json.dumps({"entries": {}}))
    with pytest.raises(DispatchCacheError, match="codec_version"):
        DispatchCache.load(str(noversion))

    badentry = tmp_path / "badentry.json"
    badentry.write_text(json.dumps({
        "codec_version": CACHE_CODEC_VERSION,
        "entries": {"k": {"chunk_steps": 0, "block_b": 8,
                          "lanes_per_device": 4,
                          "backend": "reference"}}}))
    with pytest.raises(DispatchCacheError, match="chunk_steps"):
        DispatchCache.load(str(badentry))
    badblock = tmp_path / "badblock.json"
    badblock.write_text(json.dumps({
        "codec_version": CACHE_CODEC_VERSION,
        "entries": {"k": {"chunk_steps": 2, "block_b": 12,
                          "lanes_per_device": 4,
                          "backend": "reference"}}}))
    with pytest.raises(DispatchCacheError, match="multiple of"):
        DispatchCache.load(str(badblock))


def test_engine_falls_back_on_bad_cache_never_crashes(tmp_path, rng):
    """Every rejected-cache shape constructs a working engine on static
    defaults, with one UserWarning and the reason recorded — including a
    v1 file holding an entry keyed for this very config."""
    cfg = _small_cfg()
    params_q = _net(rng, cfg.layer_sizes)
    for blob in ("{nope",
                 json.dumps({"codec_version": CACHE_CODEC_VERSION + 1,
                             "entries": {}}),
                 json.dumps({"codec_version": 0, "entries": {}}),
                 json.dumps(_v1_blob(cfg))):
        p = tmp_path / "bad.json"
        p.write_text(blob)
        with warnings.catch_warnings(record=True) as caught:
            warnings.simplefilter("always")
            eng = SNNStreamEngine(params_q, cfg, patience=10_000, seed=0,
                                  dispatch_cache=str(p))
        assert not eng.cache_decision.hit
        assert "static defaults" in eng.cache_decision.reason
        assert any(issubclass(w.category, UserWarning) for w in caught)
        assert eng.batch_size == 8 and eng.chunk_steps == 4
        eng.submit(np.full(cfg.n_in, 40, np.uint8))
        res = eng.run()
        assert res[0].steps == cfg.num_steps
    # a missing file likewise degrades, never raises
    eng = SNNStreamEngine(params_q, cfg, patience=2, seed=0,
                          dispatch_cache=str(tmp_path / "absent.json"))
    assert not eng.cache_decision.hit


def test_no_fingerprint_cross_leak(tmp_path, rng):
    """Shapes tuned for one network must never arm a different one."""
    cfg_a = _small_cfg()
    cfg_b = _small_cfg(num_steps=6)
    path = _write(tmp_path, cfg_a)
    params_b = _net(rng, cfg_b.layer_sizes)
    eng = SNNStreamEngine(params_b, cfg_b, patience=2, seed=0,
                          dispatch_cache=path)
    assert not eng.cache_decision.hit
    assert config_fingerprint(cfg_b) in eng.cache_decision.key
    # the same file is a hit for the config it was tuned for
    params_a = _net(rng, cfg_a.layer_sizes)
    assert SNNStreamEngine(params_a, cfg_a, patience=2, seed=0,
                           dispatch_cache=path).cache_decision.hit


# ---------------------------------------------------------------------------
# env resolution through the engines and the tier
# ---------------------------------------------------------------------------

def test_env_resolution_single_sharded_tier(tmp_path, rng, monkeypatch):
    cfg = _small_cfg()
    params_q = _net(rng, cfg.layer_sizes)
    n_dev = len(jax.devices())
    path = _write(tmp_path, cfg, mesh_shapes=((1,), (n_dev, 1)))
    monkeypatch.setenv(ENV_DISPATCH_CACHE, path)

    eng = SNNStreamEngine(params_q, cfg, patience=2, seed=0)
    assert eng.cache_decision.hit and eng.cache_decision.source == path
    assert eng.batch_size == _tuned().lanes_per_device
    assert eng.controller.chunk_steps == _tuned().chunk_steps

    sh = ShardedSNNStreamEngine(params_q, cfg, patience=2, seed=0)
    assert sh.cache_decision.hit
    assert f"mesh={n_dev}x1" in sh.cache_decision.key
    assert sh.batch_size == _tuned().lanes_per_device * n_dev

    tier = SNNServingTier(params_q, cfg, num_engines=2)
    assert len(tier.cache_decisions) == 2
    assert all(d.hit for d in tier.cache_decisions)

    # empty env = no cache, decision recorded as a miss
    monkeypatch.setenv(ENV_DISPATCH_CACHE, "")
    eng2 = SNNStreamEngine(params_q, cfg, patience=2, seed=0)
    assert not eng2.cache_decision.hit
    assert "no dispatch cache" in eng2.cache_decision.reason
    # False disables even an armed env (the tuner's own measurement mode)
    monkeypatch.setenv(ENV_DISPATCH_CACHE, path)
    eng3 = SNNStreamEngine(params_q, cfg, patience=2, seed=0,
                           dispatch_cache=False)
    assert not eng3.cache_decision.hit
    assert "explicitly disabled" in eng3.cache_decision.reason


def test_explicit_args_beat_tuned_knob_by_knob(tmp_path, rng):
    cfg = _small_cfg()
    params_q = _net(rng, cfg.layer_sizes)
    path = _write(tmp_path, cfg)
    eng = SNNStreamEngine(params_q, cfg, patience=2, seed=0,
                          chunk_steps=5, dispatch_cache=path)
    assert eng.cache_decision.hit
    assert eng.controller.chunk_steps == 5          # explicit wins
    assert eng.batch_size == _tuned().lanes_per_device  # tuned fills rest
    eng = SNNStreamEngine(params_q, cfg, patience=2, seed=0,
                          batch_size=6, dispatch_cache=path)
    assert eng.batch_size == 6
    assert eng.controller.chunk_steps == _tuned().chunk_steps


def test_cache_armed_engine_bit_identical(tmp_path, rng):
    cfg = _small_cfg()
    params_q = _net(rng, cfg.layer_sizes)
    path = _write(tmp_path, cfg)
    sched = ArrivalSchedule(n_requests=10, per_round=3, seed=5)
    pixels = sched.pixels(cfg.n_in)
    plain = SNNStreamEngine(params_q, cfg, patience=2, seed=0,
                            dispatch_cache=False)
    armed = SNNStreamEngine(params_q, cfg, patience=2, seed=0,
                            dispatch_cache=path)
    assert armed.cache_decision.hit
    assert _bits(serve_schedule(plain, sched, pixels)) \
        == _bits(serve_schedule(armed, sched, pixels))


# ---------------------------------------------------------------------------
# block_b plumb
# ---------------------------------------------------------------------------

def test_block_b_value_neutral_and_validated(rng):
    from repro.core import prng
    from repro.kernels import ops
    cfg = _small_cfg()
    params_q = _net(rng, cfg.layer_sizes)
    weights = tuple(l["w_q"] for l in params_q["layers"])
    px = jnp.asarray(rng.integers(0, 256, (8, cfg.n_in), dtype=np.uint8))
    st = prng.seed_state(3, px.shape)
    base = ops.fused_snn_stack_op(
        px, st, weights, num_steps=cfg.num_steps,
        decay_shift=cfg.lif.decay_shift, v_threshold=cfg.lif.v_threshold)
    for bb in (8, 16):
        out = ops.fused_snn_stack_op(
            px, st, weights, num_steps=cfg.num_steps,
            decay_shift=cfg.lif.decay_shift,
            v_threshold=cfg.lif.v_threshold, block_b=bb)
        np.testing.assert_array_equal(np.asarray(base["spike_counts"]),
                                      np.asarray(out["spike_counts"]))
        np.testing.assert_array_equal(np.asarray(base["active_adds"]),
                                      np.asarray(out["active_adds"]))
    for bad in (4, 12, 0):
        with pytest.raises(ValueError, match="block_b"):
            ops.fused_snn_stack_op(
                px, st, weights, num_steps=cfg.num_steps,
                decay_shift=cfg.lif.decay_shift,
                v_threshold=cfg.lif.v_threshold, block_b=bad)


def test_block_b_engine_bit_identical(rng):
    cfg = _small_cfg()
    params_q = _net(rng, cfg.layer_sizes)
    sched = ArrivalSchedule(n_requests=6, per_round=2, seed=9)
    pixels = sched.pixels(cfg.n_in)
    base = SNNStreamEngine(params_q, cfg, batch_size=4, patience=2, seed=0,
                           backend="fused", dispatch_cache=False)
    alt = SNNStreamEngine(params_q, cfg, batch_size=4, patience=2, seed=0,
                          backend="fused", block_b=16,
                          dispatch_cache=False)
    assert _bits(serve_schedule(base, sched, pixels)) \
        == _bits(serve_schedule(alt, sched, pixels))


# ---------------------------------------------------------------------------
# proportional controller shrink
# ---------------------------------------------------------------------------

def _summary(retired, active, chunk):
    return ChunkSummary(density_in=0.1, layer_densities=(0.1,),
                        executed_adds=0, tiles_skipped=0,
                        lanes_retired=retired, lanes_active=active,
                        active_lane_steps=active * chunk)


def test_proportional_shrink():
    cfg = AdaptiveDispatchConfig(adaptive=True, min_chunk_steps=2,
                                 max_chunk_steps=16,
                                 shrink_retire_frac=0.25)
    ctl = make_controller(cfg, chunk_steps=12, num_steps=20)
    # exactly AT the trigger fraction: one step, as before this PR
    ctl.observe(_summary(retired=2, active=8, chunk=12))
    assert ctl.chunk_steps == 11
    # every lane retired (frac 1.0 = 3 trigger-widths over): 4 steps
    ctl.observe(_summary(retired=8, active=8, chunk=11))
    assert ctl.chunk_steps == 7
    # half retired (frac 0.5 = 1 width over): 2 steps
    ctl.observe(_summary(retired=4, active=8, chunk=7))
    assert ctl.chunk_steps == 5
    # clamps at min_chunk_steps however heavy the overshoot
    ctl.observe(_summary(retired=8, active=8, chunk=5))
    ctl.observe(_summary(retired=8, active=8, chunk=2))
    assert ctl.chunk_steps == cfg.min_chunk_steps == 2


def test_shrink_frozen_noop():
    ctl = make_controller(AdaptiveDispatchConfig(adaptive=False),
                          chunk_steps=12, num_steps=20)
    ctl.observe(_summary(retired=8, active=8, chunk=12))
    assert ctl.chunk_steps == 12 and ctl.history == []


def test_controller_from_cache(tmp_path, rng, monkeypatch):
    """A cache hit starts the engine's controller at the tuned chunk
    length: frozen by default, adaptive walking its law from there."""
    monkeypatch.delenv("REPRO_ADAPTIVE_DISPATCH", raising=False)
    cfg = _small_cfg()
    params_q = _net(rng, cfg.layer_sizes)
    path = _write(tmp_path, cfg, tuned=_tuned(chunk_steps=6))
    ctl = SNNStreamEngine(params_q, cfg, patience=2, seed=0,
                          dispatch_cache=path).controller
    assert ctl.frozen                       # env default stays frozen
    assert ctl.chunk_steps == 6 and ctl.static_chunk_steps == 6
    adaptive = SNNStreamEngine(
        params_q, cfg, patience=2, seed=0, dispatch_cache=path,
        adaptive=AdaptiveDispatchConfig(adaptive=True)).controller
    assert not adaptive.frozen and adaptive.chunk_steps == 6


# ---------------------------------------------------------------------------
# resolve_backend cache consult
# ---------------------------------------------------------------------------

def test_resolve_backend_consults_cache():
    cfg = _small_cfg()
    cache = DispatchCache()
    key = cache_key(config_fingerprint(cfg), device_kind_now(), (1,),
                    "auto")
    cache.put(key, _tuned(backend="staged"))
    kw = dict(layer_sizes=cfg.layer_sizes, trace_steps=None)
    # auto + hit: the cached non-fused backend is adopted directly
    assert snn.resolve_backend(cfg, "auto", 1, dispatch_cache=cache,
                               **kw) == "staged"
    # a fused cached backend off-TPU fails its gate → normal chain
    cache.put(key, _tuned(backend="fused"))
    expect = "fused" if jax.default_backend() == "tpu" else "reference"
    assert snn.resolve_backend(cfg, "auto", 1, dispatch_cache=cache,
                               **kw) == expect
    # explicit requests ignore the cache entirely
    cache.put(key, _tuned(backend="staged"))
    assert snn.resolve_backend(cfg, "reference", 1, dispatch_cache=cache,
                               **kw) == "reference"
    # no entry for another mesh shape → normal chain
    assert snn.resolve_backend(cfg, "auto", 1, dispatch_cache=cache,
                               mesh_shape=(4, 1), **kw) \
        in ("reference", "fused", "fused_streamed", "staged")


def test_decide_dispatch_records_miss_reason(tmp_path):
    cfg = _small_cfg()
    d = decide_dispatch(None, cfg=cfg, backend=None, mesh_shape=(1,))
    assert isinstance(d, CacheDecision)
    if not os.environ.get(ENV_DISPATCH_CACHE):
        assert not d.hit and "no dispatch cache" in d.reason


# ---------------------------------------------------------------------------
# timing harness
# ---------------------------------------------------------------------------

def test_measure_contract():
    calls = []
    rec = measure(lambda: calls.append(1), repeats=3, warmup=2)
    assert len(calls) == 5                  # warmup + repeats, all called
    assert rec.repeats == 3 and rec.warmup == 2
    assert len(rec.samples_s) == 3
    assert rec.median_s == sorted(rec.samples_s)[1]
    assert rec.device_kind == device_kind_now()
    assert rec.interpret is False
    assert rec.to_json()["interpret"] is False
    assert rec.us == pytest.approx(rec.median_s * 1e6)
    with pytest.raises(ValueError):
        measure(lambda: None, repeats=0)


# ---------------------------------------------------------------------------
# the tuner end to end (tiny grid)
# ---------------------------------------------------------------------------

def test_autotune_engine_and_write_cache(tmp_path, rng):
    cfg = _small_cfg()
    params_q = _net(rng, cfg.layer_sizes)
    tc = AutotuneConfig(
        chunk_steps_grid=(2, 4), block_b_grid=(8,), lanes_grid=(4,),
        schedule=ArrivalSchedule(n_requests=6, per_round=2, seed=3),
        repeats=2, warmup=1, max_candidates=4)
    result = autotune_engine(params_q, cfg, tune_cfg=tc, patience=2,
                             seed=0)
    assert result.bit_identical
    assert result.records[0]["candidate"] == result.default.to_json()
    assert result.tuned.seconds_per_retired_request \
        <= result.baseline_spr * (1 + 1e-9)
    assert result.fingerprint == config_fingerprint(cfg)
    # persist + arm an engine from the file the tuner wrote
    path = str(tmp_path / "tuned.json")
    write_cache(result, path, mesh_shapes=((1,),))
    eng = SNNStreamEngine(params_q, cfg, patience=2, seed=0,
                          dispatch_cache=path)
    assert eng.cache_decision.hit
    assert eng.controller.chunk_steps == result.tuned.chunk_steps
