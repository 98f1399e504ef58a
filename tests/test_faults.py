"""Fault tolerance: deterministic injection, failover, degradation ladder.

Contracts under test:
  * **failover == no-fault run** (the PR's acceptance property): under a
    seeded FaultPlan killing an engine mid-window, evacuated lanes resume
    on healthy engines and finish prediction-for-prediction bit-identical
    to the never-faulted tier (chunked==one-shot makes the LaneState row
    a perfect checkpoint) — on the jnp reference backend AND the fused
    megakernel;
  * **never-silent accounting** — ``results ∪ shed ∪ faulted`` exactly
    partitions the submitted ids under arbitrary fault plans, and a
    replayed (plan, schedule) pair reproduces every routing/shed/fault
    decision exactly;
  * **degradation ladder** — persistent fused launch faults demote the
    engine down the resumable backend chain, the demotion is recorded in
    the telemetry controller's history, served results stay bit-identical
    (cross-backend identity), and clean chunks re-promote;
  * **retry/backoff/watchdog** — transient faults retry and back off
    deterministically; persistent faults escalate to EngineFailure; a
    hung engine trips the chunk-deadline watchdog with lane state intact;
  * **poison quarantine** — a request that faults everywhere is
    quarantined with its replay seed after K faults, not retried forever;
  * **rollout × faults** — a dead engine's draining versions abort; an
    adopting engine restores garbage-collected versions from the tier's
    host planes (WeightBank.ensure), so a rollout never completes while
    an evacuated old-version lane is still draining;
  * **satellite regressions** — tier.submit validates before any state
    mutation; WeightBank.begin stacks by default and raises the typed
    RolloutInProgressError under exclusive=True.
"""

import dataclasses

import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.configs.snn_mnist import SNN_CONFIG, SNNServingTierConfig
from repro.core.telemetry import EngineLoad, estimate_eta_steps, load_score
from repro.serve import (EngineFailure, FaultEvent, FaultInjector, FaultPlan,
                         FaultPlanSpecError, FaultToleranceConfig,
                         RolloutInProgressError, SNNServingTier,
                         SNNStreamEngine, WeightBank)


def small_net(rng, sizes):
    return {"layers": [
        {"w_q": jnp.asarray(rng.integers(-256, 256, (a, b)), jnp.int16),
         "scale": jnp.float32(1.0)}
        for a, b in zip(sizes[:-1], sizes[1:])]}


def as_tuple(r):
    return (r.pred, r.steps, r.adds, r.early_exit, r.spike_counts.tolist())


def _cfg(sizes=(12, 6), T=8):
    return dataclasses.replace(SNN_CONFIG, layer_sizes=sizes, num_steps=T)


def _partition_ok(tier, submitted):
    """results ∪ shed ∪ faulted partitions the submitted ids exactly."""
    res, shed, faulted = set(tier.results), set(tier.shed), set(tier.faulted)
    assert res | shed | faulted == set(submitted)
    assert not (res & shed) and not (res & faulted) and not (shed & faulted)


# ---- failover contract ----------------------------------------------------

@pytest.mark.parametrize("backend", ["reference", "fused"])
def test_failover_evacuation_bit_identical(backend):
    """Kill engine 1 mid-window: its lanes evacuate to engine 0 and every
    request finishes bit-identical to the never-faulted tier."""
    rng = np.random.default_rng(6)
    cfg = _cfg(sizes=(16, 8), T=8)
    params_q = small_net(rng, cfg.layer_sizes)
    imgs = rng.integers(0, 256, (6, 16), dtype=np.uint8)
    plan = FaultPlan(events=(
        FaultEvent(kind="device_loss", engine=1, first_chunk=2),))

    def serve(fault_plan):
        tier = SNNServingTier(params_q, cfg, num_engines=2,
                              lanes_per_engine=2, chunk_steps=3,
                              patience=10_000, seed=11, backend=backend,
                              shedding=False, fault_plan=fault_plan)
        rids = [tier.submit(im) for im in imgs]
        return tier, rids, tier.run()

    tier, rids, res = serve(plan)
    base, _, ref = serve(None)
    assert tier.stats["engines_failed"] == 1
    assert tier.stats["evacuated"] >= 1      # mid-window lanes moved
    assert tier.faulted == {}                # nothing was unrecoverable
    _partition_ok(tier, rids)
    assert set(res) == set(ref) == set(rids)
    for rid in rids:
        assert as_tuple(res[rid]) == as_tuple(ref[rid]), rid
    assert not tier.load_report()[1].alive
    assert tier.load_report()[0].alive


def test_hang_watchdog_failover_and_requeue():
    """A hung engine makes no chunk progress; the watchdog declares it
    failed after ``watchdog_chunks`` stalls with its lane state intact,
    and both its lanes and its host queue land on the healthy engine."""
    rng = np.random.default_rng(7)
    cfg = _cfg()
    params_q = small_net(rng, cfg.layer_sizes)
    imgs = rng.integers(0, 256, (6, 12), dtype=np.uint8)
    plan = FaultPlan(events=(
        FaultEvent(kind="hang", engine=1, first_chunk=1),))
    ft = FaultToleranceConfig(watchdog_chunks=2)

    def serve(fault_plan):
        tier = SNNServingTier(params_q, cfg, num_engines=2,
                              lanes_per_engine=2, chunk_steps=2,
                              patience=10_000, seed=3, backend="reference",
                              shedding=False, fault_plan=fault_plan,
                              fault_cfg=ft)
        rids = [tier.submit(im) for im in imgs]
        return tier, rids, tier.run()

    tier, rids, res = serve(plan)
    _, _, ref = serve(None)
    assert set(res) == set(rids)
    for rid in rids:
        assert as_tuple(res[rid]) == as_tuple(ref[rid]), rid
    e1 = tier.engines[1]
    fail = [e for e in e1.health.events if e.get("event") == "engine_failure"]
    assert fail and fail[0]["reason"] == "hang"
    assert tier.stats["evacuated"] >= 1
    assert tier.stats["requeued"] >= 1       # e1's host queue re-routed
    # the armed healthy engine reports its full watchdog margin
    assert tier.load_report()[0].watchdog_margin == ft.watchdog_chunks


def test_state_lost_windows_are_recorded_not_silent():
    """Device loss WITH lane state: the in-flight windows cannot be
    evacuated — each gets a FaultRecord; everything else still serves
    bit-identically and the partition invariant holds."""
    rng = np.random.default_rng(8)
    cfg = _cfg()
    params_q = small_net(rng, cfg.layer_sizes)
    imgs = rng.integers(0, 256, (6, 12), dtype=np.uint8)
    plan = FaultPlan(events=(FaultEvent(
        kind="device_loss", engine=1, first_chunk=1, state_lost=True),))
    tier = SNNServingTier(params_q, cfg, num_engines=2, lanes_per_engine=2,
                          chunk_steps=2, patience=10_000, seed=5,
                          backend="reference", shedding=False,
                          fault_plan=plan)
    rids = [tier.submit(im) for im in imgs]
    res = tier.run()
    _partition_ok(tier, rids)
    lost = {rid for rid, rec in tier.faulted.items()
            if rec.reason == "state_lost"}
    assert lost                              # engine 1 held in-flight lanes
    base = SNNServingTier(params_q, cfg, num_engines=2, lanes_per_engine=2,
                          chunk_steps=2, patience=10_000, seed=5,
                          backend="reference", shedding=False)
    for im in imgs:
        base.submit(im)
    ref = base.run()
    for rid in res:
        assert as_tuple(res[rid]) == as_tuple(ref[rid]), rid
    for rec in tier.faulted.values():
        assert rec.replay_seed == 5 + rec.request_id


def test_all_engines_dead_no_capacity():
    """Fleet-wide loss: every window lands in ``faulted`` (never silent)
    and a post-mortem submit is recorded as ``no_capacity``."""
    rng = np.random.default_rng(9)
    cfg = _cfg()
    params_q = small_net(rng, cfg.layer_sizes)
    imgs = rng.integers(0, 256, (3, 12), dtype=np.uint8)
    plan = FaultPlan(events=(FaultEvent(kind="device_loss", first_chunk=0),))
    tier = SNNServingTier(params_q, cfg, num_engines=2, lanes_per_engine=2,
                          chunk_steps=2, patience=10_000, seed=1,
                          backend="reference", shedding=False,
                          fault_plan=plan)
    rids = [tier.submit(im) for im in imgs[:2]]
    res = tier.run()
    assert res == {} and len(tier._dead) == 2
    rids.append(tier.submit(imgs[2]))
    assert tier.faulted[rids[-1]].reason == "no_capacity"
    _partition_ok(tier, rids)


# ---- degradation ladder ---------------------------------------------------

def test_degradation_ladder_demotes_serves_and_repromotes():
    """Persistent fused launch faults: the engine steps down the ladder,
    serves bit-identical results on the demoted rung, records the
    demotion in the telemetry history, and re-promotes after clean
    chunks once the faults stop."""
    rng = np.random.default_rng(10)
    cfg = _cfg(sizes=(16, 8), T=8)
    params_q = small_net(rng, cfg.layer_sizes)
    imgs = rng.integers(0, 256, (6, 16), dtype=np.uint8)
    plan = FaultPlan(events=(FaultEvent(
        kind="dispatch", first_chunk=0, last_chunk=4, backends=("fused",)),))
    ft = FaultToleranceConfig(demote_after=2, promote_after=3)
    eng = SNNStreamEngine(params_q, cfg, batch_size=2, chunk_steps=2,
                          patience=10_000, seed=4, backend="fused",
                          injector=FaultInjector(plan, 0), fault_cfg=ft)
    assert eng._ladder[0] == "fused" and eng._ladder[-1] == "reference"
    rids = [eng.submit(im) for im in imgs]
    res = eng.run()

    ref = SNNStreamEngine(params_q, cfg, batch_size=2, chunk_steps=2,
                          patience=10_000, seed=4, backend="fused")
    for im in imgs:
        ref.submit(im)
    refres = ref.run()
    assert set(res) == set(rids)
    for rid in rids:
        assert as_tuple(res[rid]) == as_tuple(refres[rid]), rid
    demotes = [e for e in eng.controller.history
               if isinstance(e, dict) and e.get("event") == "demote"]
    promotes = [e for e in eng.controller.history
                if isinstance(e, dict) and e.get("event") == "promote"]
    assert demotes and demotes[0]["from"] == "fused"
    assert promotes and promotes[-1]["to"] == "fused"
    assert eng.health.demotion_level == 0    # back on the top rung
    assert eng.backend_effective == "fused"
    assert eng.health.alive


def test_transient_faults_retry_and_backoff_value_neutral():
    """A bounded transient burst: immediate retries + deterministic
    backoff ride it out with zero effect on served results."""
    rng = np.random.default_rng(11)
    cfg = _cfg()
    params_q = small_net(rng, cfg.layer_sizes)
    imgs = rng.integers(0, 256, (4, 12), dtype=np.uint8)
    plan = FaultPlan(events=(FaultEvent(
        kind="dispatch", first_chunk=0, last_chunk=3),))
    eng = SNNStreamEngine(params_q, cfg, batch_size=2, chunk_steps=2,
                          patience=10_000, seed=2, backend="reference",
                          injector=FaultInjector(plan, 0))
    rids = [eng.submit(im) for im in imgs]
    res = eng.run()
    ref = SNNStreamEngine(params_q, cfg, batch_size=2, chunk_steps=2,
                          patience=10_000, seed=2, backend="reference")
    for im in imgs:
        ref.submit(im)
    refres = ref.run()
    for rid in rids:
        assert as_tuple(res[rid]) == as_tuple(refres[rid]), rid
    assert eng.health.total_faults == 4      # consults 0..3 all faulted
    assert eng.health.alive and eng.health.consecutive_faults == 0


def test_persistent_faults_escalate_to_engine_failure():
    rng = np.random.default_rng(12)
    cfg = _cfg()
    eng = SNNStreamEngine(small_net(rng, cfg.layer_sizes), cfg,
                          batch_size=2, chunk_steps=2, patience=10_000,
                          seed=2, backend="reference",
                          injector=FaultInjector(
                              FaultPlan(events=(
                                  FaultEvent(kind="dispatch",
                                             first_chunk=0),)), 0))
    eng.submit(np.zeros(12, np.uint8))
    with pytest.raises(EngineFailure) as ei:
        eng.run()
    assert ei.value.reason == "dispatch_exhausted"
    assert not eng.health.alive
    assert not eng.load_summary().alive
    assert load_score(eng.load_summary()) == float("inf")


def test_corrupted_telemetry_detected_and_dropped():
    """A corrupted side-channel record fails host validation and is
    dropped (counted, never fed to the controller); the datapath result
    is untouched."""
    rng = np.random.default_rng(13)
    cfg = _cfg()
    params_q = small_net(rng, cfg.layer_sizes)
    imgs = rng.integers(0, 256, (2, 12), dtype=np.uint8)
    plan = FaultPlan(events=(FaultEvent(
        kind="telemetry", first_chunk=0, last_chunk=1),))
    eng = SNNStreamEngine(params_q, cfg, batch_size=2, chunk_steps=2,
                          patience=10_000, seed=6, backend="reference",
                          injector=FaultInjector(plan, 0))
    rids = [eng.submit(im) for im in imgs]
    res = eng.run()
    ref = SNNStreamEngine(params_q, cfg, batch_size=2, chunk_steps=2,
                          patience=10_000, seed=6, backend="reference")
    for im in imgs:
        ref.submit(im)
    refres = ref.run()
    for rid in rids:
        assert as_tuple(res[rid]) == as_tuple(refres[rid]), rid
    assert eng.health.telemetry_faults == 2
    assert eng.health.alive


# ---- poison quarantine ----------------------------------------------------

def test_poison_request_quarantined_with_replay_seed():
    """A request that faults on every engine is evicted, retried across
    engines, and quarantined with its replay seed after K faults; every
    other request still serves bit-identically."""
    rng = np.random.default_rng(14)
    cfg = _cfg()
    params_q = small_net(rng, cfg.layer_sizes)
    imgs = rng.integers(0, 256, (5, 12), dtype=np.uint8)
    plan = FaultPlan(events=(
        FaultEvent(kind="poison", request_id=2, first_chunk=0),))
    tier = SNNServingTier(params_q, cfg, num_engines=2, lanes_per_engine=2,
                          chunk_steps=2, patience=10_000, seed=21,
                          backend="reference", shedding=False,
                          fault_plan=plan,
                          fault_cfg=FaultToleranceConfig(quarantine_after=2))
    rids = [tier.submit(im) for im in imgs]
    res = tier.run()
    _partition_ok(tier, rids)
    assert set(tier.faulted) == {2}
    rec = tier.faulted[2]
    assert rec.reason == "quarantined"
    assert rec.faults == 2 and rec.replay_seed == 21 + 2
    assert tier.stats["poison_retries"] == 1
    base = SNNServingTier(params_q, cfg, num_engines=2, lanes_per_engine=2,
                          chunk_steps=2, patience=10_000, seed=21,
                          backend="reference", shedding=False)
    for im in imgs:
        base.submit(im)
    ref = base.run()
    for rid in res:
        assert as_tuple(res[rid]) == as_tuple(ref[rid]), rid


# ---- rollout × faults -----------------------------------------------------

def test_evacuation_restores_gcd_weight_version():
    """Adopting an old-version lane on an engine that finished the
    rollout re-installs the planes (bank.ensure), re-opens the rolling
    state until the lane retires, and resumes bit-exactly."""
    rng = np.random.default_rng(15)
    cfg = _cfg()
    old = small_net(rng, cfg.layer_sizes)
    new = small_net(np.random.default_rng(99), cfg.layer_sizes)
    img = rng.integers(0, 256, (12,), dtype=np.uint8)

    src = SNNStreamEngine(old, cfg, batch_size=2, chunk_steps=2,
                          patience=10_000, seed=30, backend="reference")
    src.submit(img, request_id=7)
    src.step()                               # rid 7 is mid-window on v0
    row = src.evict_lane(7)
    assert int(row.steps) > 0

    tgt = SNNStreamEngine(old, cfg, batch_size=2, chunk_steps=2,
                          patience=10_000, seed=30, backend="reference")
    tgt.begin_rollout(new)
    tgt.bank.gc({1})                         # rollout completed: v0 gone
    assert tgt.bank.versions == (1,)
    with pytest.raises(KeyError, match="version 0"):
        tgt.adopt(7, row)
    assert tgt.bank.ensure(
        0, tgt._place_weights(tuple(l["w_q"] for l in old["layers"])))
    assert tgt.bank.rolling                  # old version live again
    tgt.adopt(7, row)
    res = tgt.run()
    assert not tgt.bank.rolling              # adopted lane retired ⇒ done
    assert [e.kind for e in tgt.bank.history] == [
        "begin", "complete", "restore", "complete"]

    solo = SNNStreamEngine(old, cfg, batch_size=2, chunk_steps=2,
                           patience=10_000, seed=30, backend="reference")
    solo.submit(img, request_id=7)
    assert as_tuple(res[7]) == as_tuple(solo.run()[7])
    assert res[7].weight_version == 0


def test_engine_failure_mid_rollout_aborts_and_fleet_completes():
    """An engine dying mid-rollout: its draining versions abort, the
    evacuated old-version lanes keep tier.rollout_active True on the
    survivors, and every window still finishes on its admission-time
    weights bit-for-bit."""
    rng = np.random.default_rng(16)
    cfg = _cfg()
    old = small_net(rng, cfg.layer_sizes)
    new = small_net(np.random.default_rng(98), cfg.layer_sizes)
    imgs = rng.integers(0, 256, (4, 12), dtype=np.uint8)
    plan = FaultPlan(events=(
        FaultEvent(kind="device_loss", engine=1, first_chunk=2),))
    tier = SNNServingTier(old, cfg, num_engines=2, lanes_per_engine=2,
                          chunk_steps=2, patience=10_000, seed=40,
                          backend="reference", shedding=False,
                          fault_plan=plan)
    pre = [tier.submit(im) for im in imgs[:2]]   # one per engine
    tier.step()                                  # both mid-window on v0
    assert tier.begin_rollout(new) == 1
    post = [tier.submit(im) for im in imgs[2:]]
    tier.step()                                  # post pair admitted on v1
    tier.step()                                  # engine 1 dies here
    assert 1 in tier._dead
    assert tier.engines[1].bank.history[-1].kind == "abort"
    assert not tier.engines[1].bank.rolling
    assert tier.rollout_active                   # old lanes drain elsewhere
    res = tier.run()
    assert not tier.rollout_active
    _partition_ok(tier, pre + post)
    for rid, im, params, v in [(pre[0], imgs[0], old, 0),
                               (pre[1], imgs[1], old, 0),
                               (post[0], imgs[2], new, 1),
                               (post[1], imgs[3], new, 1)]:
        solo = SNNStreamEngine(params, cfg, batch_size=2, chunk_steps=2,
                               patience=10_000, seed=40,
                               backend="reference")
        solo.submit(im, request_id=rid)
        assert as_tuple(solo.run()[rid]) == as_tuple(res[rid]), rid
        assert res[rid].weight_version == v


# ---- satellite: WeightBank begin/abort/exclusive --------------------------

def test_weight_bank_exclusive_begin_and_abort():
    bank = WeightBank(("w0",))
    bank.begin(("w1",))
    with pytest.raises(RolloutInProgressError) as ei:
        bank.begin(("w2",), exclusive=True)
    assert ei.value.versions == (0, 1)
    assert bank.begin(("w2",)) == 2          # stacking stays the default
    assert bank.versions == (0, 1, 2)
    assert bank.abort() == (0, 1)            # dead-engine cleanup
    assert not bank.rolling and bank.versions == (2,)
    assert [e.kind for e in bank.history] == ["begin", "begin", "abort"]
    assert bank.abort() == ()                # idempotent when clean


def test_weight_bank_ensure_contract():
    bank = WeightBank(("w0",))
    bank.begin(("w1",))
    bank.gc({1})
    assert bank.ensure(0, ("w0",)) is True   # restore retired version
    assert bank.rolling
    assert bank.ensure(0, ("w0",)) is False  # already live: no-op
    with pytest.raises(ValueError, match="newer than current"):
        bank.ensure(5, ("w5",))
    assert bank.gc({1}) == (0,)
    assert [e.kind for e in bank.history] == [
        "begin", "complete", "restore", "complete"]


# ---- satellite: submit validates before mutation --------------------------

def test_submit_validation_leaves_tier_untouched():
    """Regression: a rejected submit must consume no id and write no
    bookkeeping — the id counter used to advance before the priority
    check could throw."""
    rng = np.random.default_rng(17)
    cfg = _cfg()
    tier = SNNServingTier(small_net(rng, cfg.layer_sizes), cfg,
                          num_engines=2, lanes_per_engine=2,
                          backend="reference", shedding=False)
    img = np.zeros(12, np.uint8)
    with pytest.raises(ValueError, match="unknown priority"):
        tier.submit(img, priority="platinum")
    assert tier._next_id == 0 and tier._meta == {}
    assert tier.shed == {} and tier.faulted == {}
    assert all(e.pending == 0 for e in tier.engines)
    assert tier.submit(img) == 0             # the id was never burned
    with pytest.raises(ValueError, match="already in use"):
        tier.submit(img, request_id=0)
    assert tier._next_id == 1 and len(tier._meta) == 1
    res = tier.run()
    assert set(res) == {0}
    _partition_ok(tier, [0])


# ---- properties (hypothesis; satellite) -----------------------------------

@settings(max_examples=40, deadline=None)
@given(lanes=st.integers(1, 64), busy=st.integers(0, 64),
       q=st.integers(0, 128), mean=st.floats(0.5, 200.0),
       dq=st.integers(1, 32), dm=st.floats(0.1, 50.0))
def test_eta_monotone_and_nonnegative(lanes, busy, q, mean, dq, dm):
    busy = min(busy, lanes)
    base = EngineLoad(lanes, busy, q, mean, 0)
    deeper = EngineLoad(lanes, busy, q + dq, mean, 0)
    longer = EngineLoad(lanes, busy, q, mean + dm, 0)
    assert 0 <= estimate_eta_steps(base)
    assert estimate_eta_steps(base) <= estimate_eta_steps(deeper)
    assert estimate_eta_steps(base) <= estimate_eta_steps(longer)
    assert load_score(base) >= 0


@settings(max_examples=40, deadline=None)
@given(lanes=st.integers(1, 64), busy=st.integers(0, 64),
       q=st.integers(0, 128), mean=st.floats(0.5, 200.0),
       faults=st.integers(0, 8), level=st.integers(0, 2))
def test_load_score_health_penalty(lanes, busy, q, mean, faults, level):
    """Healthy == the historical undegraded score; degradation only ever
    raises the bid; dead is never routable."""
    busy = min(busy, lanes)
    healthy = EngineLoad(lanes, busy, q, mean, 0)
    legacy = (0.5 * busy + q) * mean / max(1, lanes)
    assert load_score(healthy) == pytest.approx(legacy)
    degraded = EngineLoad(lanes, busy, q, mean, 0,
                          consecutive_faults=faults, demotion_level=level)
    assert load_score(degraded) >= load_score(healthy)
    if faults or level:
        assert load_score(degraded) > load_score(healthy)
    dead = EngineLoad(lanes, busy, q, mean, 0, alive=False)
    assert load_score(dead) == float("inf")


@settings(max_examples=40, deadline=None)
@given(lanes=st.integers(1, 64), busy=st.integers(0, 64),
       q=st.integers(0, 128),
       mean=st.sampled_from([0.0, -3.5, float("nan"),
                             float("inf"), float("-inf")]))
def test_eta_and_score_cold_engine_edges(lanes, busy, q, mean):
    """Regression: a cold engine (retired_total == 0, service EWMA still
    empty — serialized as 0 / NaN / inf by external coordinators) must
    yield a finite ETA ≥ 1 and a finite non-negative score.  Pre-fix,
    mean_service_steps=0 collapsed the score to 0 regardless of queue
    depth, so a cold engine with a 100-deep queue spuriously beat every
    warmed healthy engine; NaN poisoned both estimators outright."""
    import math
    busy = min(busy, lanes)
    cold = EngineLoad(lanes, busy, q, mean, 0)
    eta = estimate_eta_steps(cold)
    assert math.isfinite(eta) and eta >= 1.0
    score = load_score(cold)
    assert math.isfinite(score) and score >= 0.0
    if busy or q:
        # outstanding work still counts: the cold engine must not tie a
        # warmed, completely idle engine (score 0) in a least-loaded pick
        warmed_idle = EngineLoad(lanes, 0, 0, 20.0, 100)
        assert load_score(warmed_idle) == 0.0
        assert score > load_score(warmed_idle)


def test_cold_engine_does_not_beat_warmed_busy_engine():
    """The routing comparison the bug corrupted, pinned directly: a cold
    engine drowning in queued work must score WORSE than a warmed healthy
    engine with a couple of free lanes — not 0 or NaN."""
    cold_drowning = EngineLoad(8, 8, 100, 0.0, 0)
    warmed_light = EngineLoad(8, 6, 0, 20.0, 50)
    assert load_score(cold_drowning) > load_score(warmed_light) > 0
    # and the admission gate sees a usable wait bound from both
    for load in (cold_drowning, warmed_light):
        eta = estimate_eta_steps(load)
        assert eta == eta and 1.0 <= eta < float("inf")


@settings(max_examples=4, deadline=None)
@given(seed=st.integers(0, 2**16), kill=st.integers(0, 1),
       kchunk=st.integers(1, 5), rate=st.floats(0.0, 0.15),
       state_lost=st.sampled_from([False, True]))
def test_partition_and_replay_under_random_fault_plans(
        seed, kill, kchunk, rate, state_lost):
    """For any (plan, schedule): the partition invariant holds, and a
    replay reproduces every result, shed and fault record exactly."""
    rng = np.random.default_rng(seed)
    cfg = _cfg()
    params_q = small_net(rng, cfg.layer_sizes)
    n = int(rng.integers(4, 10))
    imgs = rng.integers(0, 256, (n, 12), dtype=np.uint8)
    plan = FaultPlan(
        events=(FaultEvent(kind="device_loss", engine=kill,
                           first_chunk=kchunk, state_lost=state_lost),),
        seed=seed, dispatch_rate=rate)

    def run_once():
        tier = SNNServingTier(params_q, cfg, num_engines=2,
                              lanes_per_engine=2, chunk_steps=2, patience=1,
                              seed=seed, backend="reference",
                              default_deadline_steps=40, queue_limit=3,
                              fault_plan=plan)
        rids = [tier.submit(im) for im in imgs]
        res = tier.run()
        _partition_ok(tier, rids)
        return ({r: as_tuple(v) for r, v in res.items()},
                dict(tier.shed), dict(tier.faulted), tier.stats)

    assert run_once() == run_once()


# ---- env-armed chaos ------------------------------------------------------

def test_env_plan_arms_engine_and_stays_value_neutral(monkeypatch):
    """REPRO_FAULT_PLAN arms every engine built without an injector; the
    injected transient faults are absorbed with bit-identical results —
    the property the chaos CI lane leans on suite-wide."""
    rng = np.random.default_rng(18)
    cfg = _cfg()
    params_q = small_net(rng, cfg.layer_sizes)
    imgs = rng.integers(0, 256, (4, 12), dtype=np.uint8)
    monkeypatch.delenv("REPRO_FAULT_PLAN", raising=False)
    ref = SNNStreamEngine(params_q, cfg, batch_size=2, chunk_steps=2,
                          patience=10_000, seed=9, backend="reference")
    assert ref.injector is None              # env cleared ⇒ unarmed
    for im in imgs:
        ref.submit(im)
    refres = ref.run()
    monkeypatch.setenv("REPRO_FAULT_PLAN", "seed=3,dispatch=0.2")
    eng = SNNStreamEngine(params_q, cfg, batch_size=2, chunk_steps=2,
                          patience=10_000, seed=9, backend="reference")
    assert eng.injector is not None
    assert eng.injector.plan.dispatch_rate == 0.2
    for im in imgs:
        eng.submit(im)
    res = eng.run()
    for rid in refres:
        assert as_tuple(res[rid]) == as_tuple(refres[rid]), rid
    with pytest.raises(ValueError, match="unknown"):
        FaultPlan.from_spec("seed=3,bogus=1")


# ---- spec grammar (strict parsing is the chaos lane's safety net) ---------

def test_from_spec_parses_process_faults():
    plan = FaultPlan.from_spec(
        "seed=7,dispatch=0.1,worker_kill=1@3,worker_hang=0@2,"
        "coordinator_kill=5,worker_kill=0@9")
    assert plan.seed == 7 and plan.dispatch_rate == 0.1
    assert plan.worker_kill(1, 3) is not None
    assert plan.worker_kill(1, 2) is None      # windowed [r, r], not >= r
    assert plan.worker_kill(0, 9) is not None  # repeated keys accumulate
    assert plan.worker_hang(0, 2) and not plan.worker_hang(1, 2)
    assert plan.coordinator_kill(5) and not plan.coordinator_kill(4)
    assert plan.engine_relevant(0) and plan.engine_relevant(1)


def test_from_spec_typo_fails_loudly_not_silently():
    """Regression: a typo'd key must never parse to an inert no-op plan —
    a chaos lane that silently tests nothing is worse than none."""
    with pytest.raises(FaultPlanSpecError) as ei:
        FaultPlan.from_spec("seed=11,dipsatch=0.03")
    assert ei.value.key == "dipsatch=0.03"
    msg = str(ei.value)
    assert "dipsatch" in msg and "accepted grammar" in msg
    assert "dispatch" in msg          # known keys listed for the human


@pytest.mark.parametrize("spec, detail", [
    ("worker_kill=1", "'<worker>@<round>'"),
    ("worker_kill=a@3", "'<worker>@<round>'"),
    ("worker_hang=0@", "'<worker>@<round>'"),
    ("worker_kill=-1@3", ">= 0"),
    ("coordinator_kill=x", "integer round"),
    ("coordinator_kill=-2", ">= 0"),
    ("dispatch=1.5", "outside"),
    ("seed=abc", "integer"),
    ("seed", "missing '=<value>'"),
])
def test_from_spec_malformed_values_raise(spec, detail):
    with pytest.raises(FaultPlanSpecError) as ei:
        FaultPlan.from_spec(spec)
    assert detail in str(ei.value)
    assert "accepted grammar" in str(ei.value)


def test_from_env_rejects_bad_spec(monkeypatch):
    monkeypatch.setenv("REPRO_FAULT_PLAN", "seed=11,dipsatch=0.03")
    with pytest.raises(FaultPlanSpecError):
        FaultPlan.from_env()
    monkeypatch.delenv("REPRO_FAULT_PLAN")
    assert FaultPlan.from_env() is None


# ---- recovery knob validation ---------------------------------------------

@pytest.mark.parametrize("bad", [
    dict(heartbeat_interval_s=0.0),
    dict(heartbeat_deadline_s=0.05, heartbeat_interval_s=0.1),
    dict(max_respawns=-1),
    dict(watchdog_chunks=0),
    dict(max_retries=-1),
])
def test_fault_tolerance_config_validates(bad):
    with pytest.raises(ValueError):
        FaultToleranceConfig(**bad)


def test_tier_knobs_resolve_into_fault_cfg():
    knobs = SNNServingTierConfig(max_respawns=3, heartbeat_interval_s=0.01,
                                 heartbeat_deadline_s=2.0)
    eff = knobs.resolve_fault_cfg()
    assert eff.max_respawns == 3 and eff.heartbeat_deadline_s == 2.0
    assert eff.watchdog_chunks == FaultToleranceConfig().watchdog_chunks
    assert SNNServingTierConfig().resolve_fault_cfg() is None
    with pytest.raises(ValueError, match="one source of truth"):
        SNNServingTierConfig(fault_cfg=FaultToleranceConfig(),
                             max_respawns=2)
    # invalid knob combinations fail at config construction, not at the
    # first worker death hours into a run
    with pytest.raises(ValueError, match="heartbeat"):
        SNNServingTierConfig(heartbeat_interval_s=1.0,
                             heartbeat_deadline_s=0.5)
