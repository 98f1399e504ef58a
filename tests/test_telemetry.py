"""Telemetry side channel + adaptive chunk-length controller.

Contracts under test:

  * telemetry executed adds ARE the energy counters: summed over layers
    they equal ``active_adds`` on every backend, and through the gated
    streaming chunk they equal the frozen per-lane add deltas;
  * the telemetry record is bit-identical across the
    fused / fused_streamed / staged / reference backends and across
    random window chunk splits (concatenation == one-shot) — the side
    channel is cross-checkable exactly like the datapath;
  * the tile-skip mirror (``core.telemetry.layer_tile_skips``) agrees
    with the independently-derived ``kernels.ref.tile_skips_ref`` oracle
    (double-entry bookkeeping for the launch geometry);
  * the controller in frozen mode reproduces the static choices exactly
    (and never syncs), while adaptive mode — property-tested over random
    traffic — changes ONLY the chunk length: engine results are
    bit-identical with adaptivity on and off.
"""

import dataclasses
import pickle

import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.configs.snn_mnist import SNN_CONFIG, SNN_CONFIG_DEEP
from repro.core import prng, snn
from repro.core.telemetry import (concat_telemetry, layer_tile_skips,
                                  tiles_total)
from repro.kernels import ref
from repro.serve import (AdaptiveDispatchConfig, SNNStreamEngine,
                         TelemetryController, summarize_chunk)
from repro.serve.telemetry import make_controller

_TEL_FIELDS = ("n_spk", "n_en", "tiles_skipped")


def _net(rng, sizes):
    return {"layers": [
        {"w_q": jnp.asarray(rng.integers(-256, 256, (a, b)), jnp.int16),
         "scale": jnp.float32(1.0)}
        for a, b in zip(sizes[:-1], sizes[1:])]}


# ---------------------------------------------------------------------------
# telemetry invariants
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("prune", [False, True])
def test_telemetry_adds_equal_energy_counters(rng, prune):
    """Σ_layers telemetry adds == the frozen active_adds channel, every
    backend — the invariant that keeps the side channel honest instead of
    being a second, separately-buggy accounting."""
    cfg = dataclasses.replace(SNN_CONFIG_DEEP, num_steps=6,
                              active_pruning=prune)
    params_q = _net(rng, cfg.layer_sizes)
    px = jnp.asarray(rng.integers(0, 256, (5, cfg.n_in), dtype=np.uint8))
    state = prng.seed_state(13, px.shape)
    for backend in ("reference", "staged", "fused"):
        out = snn.snn_apply_int(params_q, px, state, cfg, backend=backend)
        tel = out["telemetry"]
        np.testing.assert_array_equal(
            np.asarray(tel.adds).sum(axis=1),
            np.asarray(out["active_adds"]), err_msg=backend)


@pytest.mark.parametrize("sparse_skip", [False, True])
def test_telemetry_bit_identical_across_backends(rng, sparse_skip):
    """fused == fused(streamed init path) == staged == reference on every
    telemetry leaf — including nonzero tile-skip counts (sparse input)."""
    cfg = dataclasses.replace(SNN_CONFIG_DEEP, num_steps=7,
                              active_pruning=True, sparse_skip=sparse_skip)
    params_q = _net(rng, cfg.layer_sizes)
    # very sparse pixels → zero-spike K-tiles actually occur
    px = jnp.asarray(np.minimum(rng.integers(0, 256, (4, cfg.n_in)), 3)
                     .astype(np.uint8))
    state = prng.seed_state(29, px.shape)
    outs = {b: snn.snn_apply_int(params_q, px, state, cfg, backend=b)
            for b in ("reference", "staged", "fused")}
    for f in _TEL_FIELDS:
        a = np.asarray(getattr(outs["reference"]["telemetry"], f))
        for b in ("staged", "fused"):
            np.testing.assert_array_equal(
                a, np.asarray(getattr(outs[b]["telemetry"], f)),
                err_msg=f"{f} on {b}")
    for lx in range(len(cfg.layer_sizes) - 1):
        a = np.asarray(outs["reference"]["v_peak"][lx])
        for b in ("staged", "fused"):
            np.testing.assert_array_equal(
                a, np.asarray(outs[b]["v_peak"][lx]),
                err_msg=f"v_peak[{lx}] on {b}")
    skipped = int(np.asarray(outs["fused"]["telemetry"].tiles_skipped).sum())
    if sparse_skip:
        assert skipped > 0, "sparse input should skip some tiles"
    else:
        assert skipped == 0, "dense mode must report zero skipped tiles"


@settings(max_examples=6, deadline=None)
@given(seed=st.integers(1, 2**31), n_chunks=st.integers(1, 4),
       backend=st.sampled_from(["fused", "fused_streamed", "reference"]))
def test_telemetry_chunk_split_property(seed, n_chunks, backend):
    """Property: telemetry concatenated over any random split of the
    window == the one-shot record, and == the reference record — on the
    resident AND weight-streamed kernels and the jnp scan."""
    rng = np.random.default_rng(seed % (2**31))
    cfg = dataclasses.replace(SNN_CONFIG_DEEP, num_steps=8,
                              sparse_skip=True)
    params_q = _net(rng, cfg.layer_sizes)
    px = jnp.asarray(np.minimum(rng.integers(0, 256, (4, cfg.n_in)), 20)
                     .astype(np.uint8))
    state0 = prng.seed_state(seed, px.shape)
    T = cfg.num_steps
    cuts = sorted(rng.choice(np.arange(1, T), size=min(n_chunks - 1, T - 1),
                             replace=False).tolist()) if n_chunks > 1 else []
    bounds = [0] + cuts + [T]

    def run(be, splits):
        st_ = snn.snn_window_init(params_q, state0, cfg)
        tels = []
        for lo, hi in zip(splits[:-1], splits[1:]):
            st_, out = snn.snn_window_chunk(params_q, px, st_, cfg,
                                            chunk_steps=hi - lo, backend=be)
            tels.append(out["telemetry"])
        return st_, concat_telemetry(tels)

    _, one_shot = run(backend, [0, T])
    chunk_state, chunked = run(backend, bounds)
    _, ref_tel = run("reference", [0, T])
    for f in _TEL_FIELDS:
        np.testing.assert_array_equal(
            np.asarray(getattr(chunked, f)), np.asarray(getattr(one_shot, f)),
            err_msg=f"{f} split={bounds} backend={backend}")
        np.testing.assert_array_equal(
            np.asarray(getattr(chunked, f)), np.asarray(getattr(ref_tel, f)),
            err_msg=f"{f} vs reference backend={backend}")


def test_tile_skip_mirror_matches_ref_oracle(rng):
    """core.telemetry.layer_tile_skips (kernel-geometry mirror) and
    kernels.ref.tile_skips_ref (independently re-derived oracle) agree —
    the double-entry check that a silent geometry change cannot pass."""
    for b, n_in, n_out in ((1, 32, 10), (5, 300, 140), (9, 784, 10),
                           (16, 256, 256)):
        x = rng.random((b, n_in)) < 0.02
        en = rng.random((b, n_out)) < 0.5
        en[:, : min(128, n_out)] = False        # a fully-pruned tile
        for ss in (False, True):
            got = np.asarray(layer_tile_skips(
                jnp.asarray(x), jnp.asarray(en), sparse_skip=ss))
            want = np.asarray(ref.tile_skips_ref(
                jnp.asarray(x), jnp.asarray(en), sparse_skip=ss))
            np.testing.assert_array_equal(got, want,
                                          err_msg=f"{b}x{n_in}x{n_out}")
    # sanity: totals are bounded by the static tile grid
    tt = tiles_total((300, 140, 10))
    assert tt == (3 * 2, 2 * 1)


# ---------------------------------------------------------------------------
# adaptive controller
# ---------------------------------------------------------------------------

def test_frozen_controller_reproduces_static_choices(monkeypatch):
    """Frozen mode IS today's behavior: the static chunk length comes
    back verbatim and observations are no-ops."""
    monkeypatch.delenv("REPRO_ADAPTIVE_DISPATCH", raising=False)
    ctl = make_controller(None, chunk_steps=4, num_steps=20)
    assert ctl.frozen
    assert ctl.chunk_steps == 4 and ctl.min_chunk_steps == 4
    ctl.observe(None)           # frozen observe never touches the summary
    assert ctl.history == []
    ctl2 = make_controller(None, chunk_steps=6, num_steps=20)
    assert ctl2.chunk_steps == 6


def test_adaptive_controller_retunes_chunk_length():
    """Deterministic control law: the chunk length shrinks under
    retirement pressure and grows in quiet steady state."""
    cfg = AdaptiveDispatchConfig(adaptive=True, min_chunk_steps=2,
                                 max_chunk_steps=8, grow_patience=2)
    ctl = TelemetryController(cfg=cfg, static_chunk_steps=4, num_steps=20)

    def summary(density, retired, active):
        from repro.serve import ChunkSummary
        return ChunkSummary(density_in=density, layer_densities=(density,),
                            executed_adds=0, tiles_skipped=0,
                            lanes_retired=retired, lanes_active=active,
                            active_lane_steps=max(1, active) * 4)

    for _ in range(8):
        ctl.observe(summary(0.04, retired=4, active=8))
    assert ctl.chunk_steps == cfg.min_chunk_steps   # retirement pressure
    for _ in range(10):
        ctl.observe(summary(0.04, retired=0, active=8))
    assert ctl.chunk_steps > cfg.min_chunk_steps    # quiet → grow
    assert len(ctl.history) == 18
    # trajectory is replayable: same observations → same decisions
    ctl2 = TelemetryController(cfg=cfg, static_chunk_steps=4, num_steps=20)
    for _ in range(8):
        ctl2.observe(summary(0.04, retired=4, active=8))
    for _ in range(10):
        ctl2.observe(summary(0.04, retired=0, active=8))
    assert [h["chunk_steps"] for h in ctl2.history] == \
        [h["chunk_steps"] for h in ctl.history]


def test_summarize_chunk_measures_known_density(rng):
    """Constant-level pixels: the summary's density estimate must land on
    the analytic px/256 Poisson rate (occupancy-weighted)."""
    level = 128
    cfg = dataclasses.replace(SNN_CONFIG, num_steps=16)
    params_q = _net(rng, cfg.layer_sizes)
    px = jnp.full((4, cfg.n_in), level, jnp.uint8)
    state = prng.seed_state(3, px.shape)
    out = snn.snn_apply_int(params_q, px, state, cfg, backend="reference")
    steps = np.full((4,), cfg.num_steps, np.int32)
    s = summarize_chunk(out["telemetry"], cfg.layer_sizes,
                        steps_before=np.zeros((4,), np.int32),
                        steps_after=steps,
                        active_before=np.ones((4,), bool),
                        active_after=np.zeros((4,), bool))
    assert abs(s.density_in - level / 256) < 0.03
    assert s.lanes_retired == 4 and s.active_lane_steps == 4 * cfg.num_steps
    assert s.executed_adds == int(np.asarray(out["active_adds"]).sum())


@settings(max_examples=4, deadline=None)
@given(seed=st.integers(1, 2**31),
       level=st.sampled_from([0, 33, 128, 255]),
       patience=st.sampled_from([1, 2, 10_000]))
def test_adaptive_never_changes_predictions(seed, level, patience):
    """THE acceptance property: with the controller adaptive (live chunk
    lengths) every request's prediction, retirement step,
    spike registers and frozen add counter are bit-identical to frozen
    mode — adaptivity moves wall-clock only."""
    rng = np.random.default_rng(seed % (2**31))
    cfg = dataclasses.replace(SNN_CONFIG, num_steps=10)
    params_q = _net(rng, cfg.layer_sizes)
    imgs = np.minimum(rng.integers(0, 256, (6, cfg.n_in)),
                      level).astype(np.uint8)

    def run(adaptive):
        eng = SNNStreamEngine(params_q, cfg, batch_size=2, chunk_steps=4,
                              patience=patience, seed=seed,
                              backend="reference", adaptive=adaptive)
        ids = [eng.submit(im) for im in imgs]
        res = eng.run()
        return {i: (res[i].pred, res[i].steps, res[i].adds,
                    res[i].early_exit, tuple(res[i].spike_counts.tolist()))
                for i in ids}, eng

    frozen, _ = run(AdaptiveDispatchConfig(adaptive=False))
    adaptive, eng = run(AdaptiveDispatchConfig(adaptive=True,
                                               min_chunk_steps=2,
                                               max_chunk_steps=7,
                                               grow_patience=1))
    assert adaptive == frozen
    assert not eng.controller.frozen
    assert len(eng.controller.history) > 0


# ---------------------------------------------------------------------------
# controller edges: zero-signal chunks, clamp bounds, pickle determinism
# ---------------------------------------------------------------------------

def _summary(density, retired, active, lane_steps=None):
    from repro.serve import ChunkSummary
    return ChunkSummary(
        density_in=density, layer_densities=(density,), executed_adds=0,
        tiles_skipped=0, lanes_retired=retired, lanes_active=active,
        active_lane_steps=(active * 4 if lane_steps is None else lane_steps))


def test_summarize_chunk_all_frozen_lanes_no_blowup():
    """A chunk dispatched with every lane already frozen consumes zero
    lane-steps — densities must come back exactly 0.0 (finite, no
    division blow-up), not NaN/inf from a 0/0."""
    from repro.core.telemetry import ChunkTelemetry
    chunk, L, B = 3, 2, 4
    tel = ChunkTelemetry(
        n_spk=jnp.zeros((chunk, L, B), jnp.int32),
        n_en=jnp.zeros((chunk, L, B), jnp.int32),
        tiles_skipped=jnp.zeros((chunk, L, 1), jnp.int32))
    s = summarize_chunk(tel, (784, 128, 10),
                        steps_before=np.full((B,), 5, np.int32),
                        steps_after=np.full((B,), 5, np.int32),
                        active_before=np.zeros((B,), bool),
                        active_after=np.zeros((B,), bool))
    assert s.active_lane_steps == 0 and s.lanes_active == 0
    assert s.density_in == 0.0 and all(np.isfinite(s.layer_densities))
    assert s.executed_adds == 0 and s.lanes_retired == 0


def test_zero_signal_chunks_leave_estimator_untouched():
    """Zero-lane-step / zero-active observations carry no information:
    the chunk length must not move (in particular the retirement
    fraction 0/0 must not be computed)."""
    cfg = AdaptiveDispatchConfig(adaptive=True)
    ctl = TelemetryController(cfg=cfg, static_chunk_steps=4, num_steps=20)
    ctl.observe(_summary(0.1, retired=0, active=8))
    chunk, quiet = ctl.chunk_steps, ctl._quiet
    for _ in range(5):
        ctl.observe(_summary(0.0, retired=0, active=0, lane_steps=0))
    assert ctl.chunk_steps == chunk
    assert ctl._quiet == quiet      # empty chunks are not "quiet traffic"


def test_chunk_length_clamps_at_bounds():
    """Sustained pressure can never walk the chunk length past its
    configured bounds, and the dispatched length is additionally capped
    by the window itself (num_steps)."""
    cfg = AdaptiveDispatchConfig(adaptive=True, min_chunk_steps=2,
                                 max_chunk_steps=12, grow_patience=1)
    ctl = TelemetryController(cfg=cfg, static_chunk_steps=4, num_steps=8)
    for _ in range(50):             # retirement storm, far past the clamp
        ctl.observe(_summary(0.1, retired=8, active=8))
    assert ctl._chunk == cfg.min_chunk_steps
    assert ctl.chunk_steps == cfg.min_chunk_steps
    for _ in range(50):             # quiet steady state, far past the clamp
        ctl.observe(_summary(0.1, retired=0, active=8))
    assert ctl._chunk == cfg.max_chunk_steps
    assert ctl.chunk_steps == min(cfg.max_chunk_steps, ctl.num_steps) == 8


def test_controller_pickle_restore_determinism():
    """A controller pickled mid-trajectory and restored continues the
    exact decision sequence of the uninterrupted original — frozen mode
    stays static across the round-trip, adaptive mode replays."""
    def drive(ctl, summaries):
        for s in summaries:
            ctl.observe(s)
        return [h["chunk_steps"] for h in ctl.history]

    traffic = ([_summary(0.05, retired=2, active=8)] * 6
               + [_summary(0.3, retired=0, active=8)] * 6)
    cfg = AdaptiveDispatchConfig(adaptive=True, min_chunk_steps=2,
                                 max_chunk_steps=8, grow_patience=2)
    a = TelemetryController(cfg=cfg, static_chunk_steps=4, num_steps=20)
    full = drive(a, traffic)
    b = TelemetryController(cfg=cfg, static_chunk_steps=4, num_steps=20)
    drive(b, traffic[:5])
    b2 = pickle.loads(pickle.dumps(b))
    assert (b2._chunk, b2._quiet) == (b._chunk, b._quiet)
    resumed = drive(b2, traffic[5:])
    assert resumed == full
    # frozen controller: the round-trip preserves the static choices and
    # observe stays a no-op
    f = make_controller(AdaptiveDispatchConfig(adaptive=False),
                        chunk_steps=6, num_steps=20)
    f2 = pickle.loads(pickle.dumps(f))
    assert f2.frozen
    assert f2.chunk_steps == 6 and f2.min_chunk_steps == 6
    f2.observe(None)
    assert f2.history == []
