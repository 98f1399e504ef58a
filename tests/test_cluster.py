"""Process-level failover: heartbeat coordinator, wire checkpoints, ledger.

Contracts under test (serve.cluster / serve.wire / serve.ledger):
  * **process failover == no-fault run** — with a seeded plan killing a
    worker process mid-window and the coordinator once, the cluster run
    equals the no-fault single-engine run prediction-for-prediction
    (reference AND fused backends), every surviving id bit-identical;
  * **wire codec** — a ``LaneState`` checkpoint roundtrips through
    ``lane_to_wire``/``lane_from_wire`` (via real JSON) bit-identically,
    and rows stamped with a future codec version are rejected with an
    actionable message instead of being misinterpreted;
  * **crash-proof accounting** — the write-ahead JSONL ledger restores
    ``results ∪ shed ∪ faulted`` as an exact partition after the
    coordinator dies (including mid-evacuation), tolerates a torn final
    line, and raises on any other corruption;
  * **restart-and-readopt** — a killed worker is respawned, re-probed
    and re-enters routing; with the respawn budget exhausted the
    survivors absorb its lanes instead;
  * **never-silent loss** — ``state_lost`` kills surface as
    ``FaultRecord("state_lost")``, and replaying the same plan
    reproduces every record exactly;
  * **config threading** — the recovery knobs on ``SNNServingTierConfig``
    resolve into one validated ``FaultToleranceConfig`` shared by the
    in-process tier and the cluster path.
"""

import dataclasses
import json

import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.snn_mnist import (SNN_CONFIG, SNNClusterConfig,
                                     SNNServingTierConfig, make_cluster,
                                     make_serving_tier)
from repro.core.telemetry import (EngineLoad, engine_load_from_wire,
                                  engine_load_to_wire)
from repro.serve import (ClusterCoordinator, CoordinatorCrash, FaultEvent,
                         FaultPlan, FaultToleranceConfig, Ledger,
                         LedgerCorruptError, SNNStreamEngine,
                         WIRE_CODEC_VERSION, WireError, lane_from_wire,
                         lane_to_wire, read_ledger, recover_accounting)


def small_net(rng, sizes):
    return {"layers": [
        {"w_q": jnp.asarray(rng.integers(-256, 256, (a, b)), jnp.int16),
         "scale": jnp.float32(1.0)}
        for a, b in zip(sizes[:-1], sizes[1:])]}


def as_tuple(r):
    return (r.pred, r.steps, r.adds, r.early_exit, r.spike_counts.tolist())


_RNG = np.random.default_rng(17)
CFG = dataclasses.replace(SNN_CONFIG, layer_sizes=(12, 6), num_steps=8)
PARAMS = small_net(_RNG, CFG.layer_sizes)
IMGS = _RNG.integers(0, 256, (10, 12), dtype=np.uint8)
KW = dict(num_workers=2, lanes_per_worker=2, chunk_steps=2,
          patience=10_000, seed=0)

_BASELINE: dict = {}


@pytest.fixture(autouse=True)
def _no_env_plan(monkeypatch):
    # a suite-wide REPRO_FAULT_PLAN (the chaos CI lane) must not arm the
    # in-process baseline engines these tests compare against
    monkeypatch.delenv("REPRO_FAULT_PLAN", raising=False)


def baseline(backend):
    """No-fault single-engine signatures (the bit-identity reference)."""
    if backend not in _BASELINE:
        eng = SNNStreamEngine(PARAMS, CFG, batch_size=2, chunk_steps=2,
                              patience=10_000, seed=0, backend=backend)
        for i, im in enumerate(IMGS):
            eng.submit(im, request_id=i)
        _BASELINE[backend] = {r: as_tuple(v) for r, v in eng.run().items()}
    return _BASELINE[backend]


def make_co(ledger_dir, backend="reference", plan=None, fault_cfg=None):
    return ClusterCoordinator(PARAMS, CFG, backend=backend, fault_plan=plan,
                              fault_cfg=fault_cfg, ledger_dir=str(ledger_dir),
                              **KW)


def _partition_ok(co, submitted):
    res, shed, faulted = set(co.results), set(co.shed), set(co.faulted)
    assert res | shed | faulted == set(submitted)
    assert not (res & shed) and not (res & faulted) and not (shed & faulted)


def _assert_matches_baseline(co, backend):
    base = baseline(backend)
    assert set(co.results) == set(base) - set(co.faulted) - set(co.shed)
    for rid, r in co.results.items():
        assert as_tuple(r) == base[rid], rid


# ---- wire codec -----------------------------------------------------------

def _lane_rows():
    eng = SNNStreamEngine(PARAMS, CFG, batch_size=2, chunk_steps=2,
                          patience=10_000, seed=0, backend="reference")
    for i in range(4):
        eng.submit(IMGS[i], request_id=i)
    eng.step()
    eng.step()
    return eng, eng.checkpoint_lanes()


def test_lane_wire_roundtrip_bit_identical():
    """checkpoint → wire → JSON text → wire → LaneState: every leaf keeps
    its dtype, shape and bytes exactly."""
    _, rows = _lane_rows()
    assert rows, "mid-window checkpoint should have active lanes"
    for rid, row in rows:
        back = lane_from_wire(json.loads(json.dumps(lane_to_wire(row))))
        for f in row._fields:
            a, b = getattr(row, f), getattr(back, f)
            if isinstance(a, tuple):
                for x, y in zip(a, b):
                    assert np.asarray(x).dtype == np.asarray(y).dtype
                    assert np.array_equal(x, y), (rid, f)
            else:
                assert np.asarray(a).dtype == np.asarray(b).dtype
                assert np.array_equal(a, b), (rid, f)


def test_checkpoint_lanes_is_non_destructive():
    """Shipping checkpoints every round must not perturb the engine."""
    eng, _ = _lane_rows()
    res = eng.run()
    base = baseline("reference")
    for rid in res:
        assert as_tuple(res[rid]) == base[rid]


def test_lane_wire_rejects_future_codec_version():
    _, rows = _lane_rows()
    w = lane_to_wire(rows[0][1])
    w["codec"] = WIRE_CODEC_VERSION + 1
    with pytest.raises(WireError, match="upgrade this coordinator/worker"):
        lane_from_wire(w)


def test_lane_wire_rejects_malformed_rows():
    with pytest.raises(WireError, match="codec"):
        lane_from_wire({"leaves": {}})           # no version stamp
    with pytest.raises(WireError, match="invalid codec version"):
        lane_from_wire({"codec": 0, "leaves": {}})
    with pytest.raises(WireError, match="missing"):
        lane_from_wire({"codec": WIRE_CODEC_VERSION, "leaves": {}})


def test_write_msg_times_out_on_full_pipe():
    """The heartbeat deadline covers the write side too: a hung worker
    that stops draining its pipe fills the kernel buffer, and a large
    frame must raise TimeoutError instead of blocking the coordinator
    inside os.write forever (which would defeat the watchdog)."""
    import os
    import time

    from repro.serve.wire import write_msg
    r, w = os.pipe()
    try:
        os.set_blocking(w, False)
        try:
            while True:
                os.write(w, b"\0" * 65536)
        except BlockingIOError:
            pass   # pipe buffer is now full
        os.set_blocking(w, True)
        t0 = time.monotonic()
        with pytest.raises(TimeoutError, match="frame write"):
            write_msg(w, {"px": "y" * 4096}, timeout_s=0.1)
        assert time.monotonic() - t0 < 5.0
    finally:
        os.close(r)
        os.close(w)


def test_write_msg_with_deadline_roundtrips():
    """A live peer: the deadline path must still deliver the frame
    byte-exactly (chunked writes included)."""
    import os

    from repro.serve.wire import read_msg, write_msg
    r, w = os.pipe()
    try:
        obj = {"op": "submit", "px": list(range(100))}
        write_msg(w, obj, timeout_s=5.0)
        assert read_msg(r, 5.0) == obj
    finally:
        os.close(r)
        os.close(w)


def test_engine_load_wire_roundtrip():
    load = EngineLoad(lanes_total=8, lanes_busy=3, queue_depth=2,
                      mean_service_steps=5.5, retired_total=7,
                      consecutive_faults=1,
                      demotion_level=2, watchdog_margin=None, alive=False)
    back = engine_load_from_wire(json.loads(json.dumps(
        engine_load_to_wire(load))))
    assert back == load


# ---- ledger ---------------------------------------------------------------

def test_ledger_drops_torn_final_line(tmp_path):
    p = str(tmp_path / "l.jsonl")
    led = Ledger(p)
    led.append({"kind": "submit", "rid": 0})
    led.append({"kind": "result", "rid": 0})
    led.close()
    with open(p, "a", encoding="utf-8") as f:
        f.write('{"kind": "fault", "rid": 1, "rea')   # crash mid-append
    recs = read_ledger(p)
    assert [r["kind"] for r in recs] == ["submit", "result"]


def test_ledger_reopen_repairs_torn_tail(tmp_path):
    """A recovered process reopening a ledger whose last append was torn
    must truncate the partial line first — appending straight onto it
    would weld two records into one corrupt mid-file line, silently
    dropping the new record (if last) or poisoning the whole ledger with
    LedgerCorruptError (if not)."""
    p = str(tmp_path / "l.jsonl")
    led = Ledger(p)
    led.append({"kind": "submit", "rid": 0})
    led.close()
    with open(p, "a", encoding="utf-8") as f:
        f.write('{"kind": "result", "rid": 0, "pre')   # crash mid-append
    led2 = Ledger(p)   # the respawned incarnation reopens the same file
    led2.append({"kind": "result", "rid": 0})
    led2.append({"kind": "fault", "rid": 1, "reason": "state_lost"})
    led2.close()
    recs = read_ledger(p)
    assert [(r["kind"], r["rid"]) for r in recs] == [
        ("submit", 0), ("result", 0), ("fault", 1)]
    acc = recover_accounting([p])
    assert set(acc["results"]) == {0} and set(acc["faulted"]) == {1}


def test_ledger_reopen_keeps_clean_file_intact(tmp_path):
    p = str(tmp_path / "l.jsonl")
    led = Ledger(p)
    led.append({"kind": "submit", "rid": 0})
    led.close()
    led2 = Ledger(p)
    led2.append({"kind": "result", "rid": 0})
    led2.close()
    assert [r["kind"] for r in read_ledger(p)] == ["submit", "result"]


def test_ledger_raises_on_mid_file_corruption(tmp_path):
    p = str(tmp_path / "l.jsonl")
    with open(p, "w", encoding="utf-8") as f:
        f.write('{"kind": "submit", "rid": 0}\n')
        f.write('garbage{\n')
        f.write('{"kind": "result", "rid": 0}\n')
    with pytest.raises(LedgerCorruptError, match=r"l\.jsonl:2"):
        read_ledger(p)


def test_recover_accounting_result_beats_fault(tmp_path):
    """A worker-replicated result must win over the coordinator's fault
    record for the same id — the computed answer is the truth."""
    cp, wp = str(tmp_path / "c.jsonl"), str(tmp_path / "w.jsonl")
    c = Ledger(cp)
    for rid in (0, 1, 2):
        c.append({"kind": "submit", "rid": rid, "px": "x"})
    c.append({"kind": "fault", "rid": 1, "reason": "state_lost"})
    c.append({"kind": "shed", "rid": 2, "reason": "deadline"})
    c.close()
    w = Ledger(wp)
    w.append({"kind": "result", "rid": 1, "pred": 3})
    w.close()
    acc = recover_accounting([cp, wp])
    assert set(acc["results"]) == {1}
    assert set(acc["shed"]) == {2}
    assert acc["faulted"] == {}
    assert acc["outstanding"] == [0]
    assert [rid for rid, _ in acc["submitted"]] == [0, 1, 2]


# ---- cluster: no-fault ----------------------------------------------------

def test_cluster_matches_single_engine(tmp_path):
    with make_co(tmp_path) as co:
        for i, im in enumerate(IMGS):
            co.submit(im, request_id=i)
        res = co.run()
        assert {r: as_tuple(v) for r, v in res.items()} == baseline(
            "reference")
        _partition_ok(co, range(len(IMGS)))
        assert not co.faulted and not co.shed
    # write-ahead + replication: the coordinator logged every submit
    # before routing it, and each worker replicated its own results
    recs = read_ledger(str(tmp_path / "coordinator.jsonl"))
    assert {r["rid"] for r in recs if r["kind"] == "submit"} == set(
        range(len(IMGS)))
    assert all("deadline_steps" in r for r in recs if r["kind"] == "submit")
    wrecs = [r for i in range(KW["num_workers"])
             for r in read_ledger(str(tmp_path / f"worker-{i}.jsonl"))]
    assert {r["rid"] for r in wrecs if r["kind"] == "result"} == set(
        range(len(IMGS)))


# ---- cluster: the process-failover contract -------------------------------

CONTRACT_PLAN = "seed=0,worker_kill=1@2,coordinator_kill=4"


@pytest.mark.parametrize("backend", ["reference", "fused"])
def test_process_failover_contract(tmp_path, backend):
    """Worker 1 killed mid-window at round 2, coordinator killed at round
    4; ledger recovery re-runs the outstanding ids — final accounting is
    a lossless, bit-identical match of the no-fault run."""
    co = make_co(tmp_path, backend, plan=CONTRACT_PLAN)
    try:
        for i, im in enumerate(IMGS):
            co.submit(im, request_id=i)
        with pytest.raises(CoordinatorCrash):
            co.run()
        assert co.stats["workers_failed"] >= 1
        assert co.stats["evacuated"] >= 1
        # the submit lines were write-ahead: all durable before the crash
        recs = read_ledger(str(tmp_path / "coordinator.jsonl"))
        assert {r["rid"] for r in recs if r["kind"] == "submit"} == set(
            range(len(IMGS)))
        with ClusterCoordinator.recover(
                PARAMS, CFG, ledger_dir=str(tmp_path), backend=backend,
                fault_plan=CONTRACT_PLAN, **KW) as co2:
            co2.run()
            _partition_ok(co2, range(len(IMGS)))
            assert not co2.faulted and not co2.shed   # lossless schedule
            _assert_matches_baseline(co2, backend)
    finally:
        co.close()


def test_worker_hang_detected_by_heartbeat(tmp_path):
    """A worker that stops responding mid-round trips the heartbeat
    deadline, is killed and respawned, and its lanes resume losslessly
    from the shipped checkpoints."""
    cfg = FaultToleranceConfig(heartbeat_interval_s=0.02,
                               heartbeat_deadline_s=1.5)
    with make_co(tmp_path, plan="seed=0,worker_hang=0@2",
                 fault_cfg=cfg) as co:
        for i, im in enumerate(IMGS):
            co.submit(im, request_id=i)
        co.run()
        assert co.stats["workers_failed"] == 1
        assert co.stats["respawned"] == 1
        _partition_ok(co, range(len(IMGS)))
        assert not co.faulted
        _assert_matches_baseline(co, "reference")


def test_respawn_budget_exhausted_survivors_absorb(tmp_path):
    cfg = FaultToleranceConfig(max_respawns=0)
    with make_co(tmp_path, plan="seed=0,worker_kill=1@2",
                 fault_cfg=cfg) as co:
        for i, im in enumerate(IMGS):
            co.submit(im, request_id=i)
        co.run()
        assert co.stats["respawned"] == 0
        assert [i for i, h in enumerate(co.workers) if h.alive] == [0]
        _partition_ok(co, range(len(IMGS)))
        assert not co.faulted
        _assert_matches_baseline(co, "reference")


def test_coordinator_crash_mid_evacuation_exactly_once(tmp_path):
    """The coordinator dies after landing ONE evacuated lane — recovery
    must account every id exactly once (results or faulted, never both,
    never neither)."""
    co = make_co(tmp_path, plan="seed=0,worker_kill=1@2")
    co._crash_after_evacuations = 1
    try:
        for i, im in enumerate(IMGS):
            co.submit(im, request_id=i)
        with pytest.raises(CoordinatorCrash):
            co.run()
        with ClusterCoordinator.recover(
                PARAMS, CFG, ledger_dir=str(tmp_path),
                backend="reference", fault_plan="seed=0,worker_kill=1@2",
                **KW) as co2:
            co2.run()
            _partition_ok(co2, range(len(IMGS)))
            _assert_matches_baseline(co2, "reference")
    finally:
        co.close()


def test_rollout_survives_coordinator_crash(tmp_path):
    """Weight rollouts are ledgered and replayed on recovery: with four
    requests outstanding at the crash and a rollout that preceded it,
    the recovered coordinator must re-run them against the pre-crash
    fleet version, not version 0 of the caller-supplied params."""
    params2 = small_net(np.random.default_rng(99), CFG.layer_sizes)
    co = make_co(tmp_path)
    try:
        for i, im in enumerate(IMGS[:4]):
            co.submit(im, request_id=i)
        assert co.begin_rollout(params2) == 1
        with pytest.raises(CoordinatorCrash):
            co._crash(co.round)
    finally:
        co.close()
    recs = read_ledger(str(tmp_path / "coordinator.jsonl"))
    assert [r["version"] for r in recs if r["kind"] == "rollout"] == [1]
    with ClusterCoordinator.recover(
            PARAMS, CFG, ledger_dir=str(tmp_path), backend="reference",
            **KW) as co2:
        assert co2._current_version == 1
        res = co2.run()
        assert set(res) == set(range(4))
        assert all(r.weight_version == 1 for r in res.values())
    # the replay itself must not re-append the rollout record — a second
    # recovery would otherwise replay it twice and land at version 2
    recs = read_ledger(str(tmp_path / "coordinator.jsonl"))
    assert [r["version"] for r in recs if r["kind"] == "rollout"] == [1]


def _dead_slot(self, idx, incarnation=0):
    from repro.serve.cluster import WorkerHandle
    return WorkerHandle(proc=None, rfd=-1, wfd=-1, alive=False)


def test_begin_rollout_requires_live_workers(tmp_path, monkeypatch):
    """With zero live workers the rollout must fail loudly (a typed
    RuntimeError) — not KeyError off an empty version set, and not an
    assert that python -O strips."""
    monkeypatch.setattr(ClusterCoordinator, "_spawn", _dead_slot)
    co = ClusterCoordinator(PARAMS, CFG, ledger_dir=str(tmp_path), **KW)
    with pytest.raises(RuntimeError, match="no live worker"):
        co.begin_rollout(PARAMS)
    co.close()


def test_recover_redispatch_preserves_deadline(tmp_path, monkeypatch):
    """deadline_steps rides the write-ahead submit record: recovery must
    re-dispatch an outstanding SLO-bounded request with its original
    deadline, not silently upgrade it to unbounded."""
    from repro.serve.wire import array_to_wire
    led = Ledger(str(tmp_path / "coordinator.jsonl"))
    led.append({"kind": "submit", "rid": 0, "px": array_to_wire(IMGS[0]),
                "deadline_steps": 7})
    led.append({"kind": "submit", "rid": 1, "px": array_to_wire(IMGS[1]),
                "deadline_steps": None})
    led.close()
    captured = {}

    def fake_dispatch(self, rid, px, *, deadline_steps=None, **kw):
        captured[rid] = deadline_steps

    monkeypatch.setattr(ClusterCoordinator, "_spawn", _dead_slot)
    monkeypatch.setattr(ClusterCoordinator, "_dispatch", fake_dispatch)
    co = ClusterCoordinator.recover(PARAMS, CFG, ledger_dir=str(tmp_path),
                                    **KW)
    co.close()
    assert captured == {0: 7, 1: None}


STATE_LOST_PLAN = FaultPlan(events=(
    FaultEvent(kind="worker_kill", engine=1, first_chunk=2, last_chunk=2,
               state_lost=True),))


def test_state_lost_kill_records_fault_records(tmp_path):
    """A kill that also destroys the replica checkpoint surfaces every
    lost window as FaultRecord("state_lost") — never a silent drop."""
    with make_co(tmp_path, plan=STATE_LOST_PLAN) as co:
        for i, im in enumerate(IMGS):
            co.submit(im, request_id=i)
        co.run()
        _partition_ok(co, range(len(IMGS)))
        assert co.faulted, "worker 1 had in-flight lanes at round 2"
        assert all(f.reason == "state_lost" and f.replay_seed == rid
                   for rid, f in co.faulted.items())
        _assert_matches_baseline(co, "reference")


def test_replay_reproduces_every_record_exactly(tmp_path):
    """Same plan, same submissions, fresh cluster: identical results,
    identical FaultRecords, identical routing stats."""
    runs = []
    for sub in ("a", "b"):
        d = tmp_path / sub
        d.mkdir()
        with make_co(d, plan=STATE_LOST_PLAN) as co:
            for i, im in enumerate(IMGS):
                co.submit(im, request_id=i)
            co.run()
            runs.append(({r: as_tuple(v) for r, v in co.results.items()},
                         dict(co.faulted), dict(co.shed), co.stats))
    assert runs[0] == runs[1]


# ---- config threading -----------------------------------------------------

def test_tier_config_recovery_knob_validation():
    with pytest.raises(ValueError, match="heartbeat_deadline_s"):
        SNNServingTierConfig(heartbeat_interval_s=0.5,
                             heartbeat_deadline_s=0.1)
    with pytest.raises(ValueError, match="watchdog_chunks"):
        SNNServingTierConfig(watchdog_chunks=0)
    with pytest.raises(ValueError, match="one source of truth"):
        SNNServingTierConfig(fault_cfg=FaultToleranceConfig(),
                             demote_after=2)
    knobs = SNNServingTierConfig(watchdog_chunks=5, demote_after=2,
                                 heartbeat_interval_s=0.01,
                                 heartbeat_deadline_s=3.0)
    eff = knobs.resolve_fault_cfg()
    assert eff.watchdog_chunks == 5 and eff.demote_after == 2
    assert eff.heartbeat_deadline_s == 3.0
    # unset knobs keep the FaultToleranceConfig defaults
    assert eff.max_retries == FaultToleranceConfig().max_retries


def test_tier_config_threads_fault_cfg_to_engines():
    knobs = SNNServingTierConfig(num_engines=1, lanes_per_engine=2,
                                 chunk_steps=2, shedding=False,
                                 watchdog_chunks=7)
    tier = make_serving_tier(PARAMS, CFG, knobs, patience=10_000, seed=0,
                             backend="reference")
    assert tier.fault_cfg.watchdog_chunks == 7
    assert all(e.fault_cfg.watchdog_chunks == 7 for e in tier.engines)


def test_cluster_config_validation_and_factory(tmp_path):
    with pytest.raises(ValueError, match="num_workers"):
        SNNClusterConfig(num_workers=0)
    with pytest.raises(ValueError, match="ledger_dir"):
        make_cluster(PARAMS, CFG, SNNClusterConfig(num_workers=1))
    knobs = SNNClusterConfig(num_workers=1, lanes_per_worker=2,
                             chunk_steps=2, backend="reference",
                             ledger_dir=str(tmp_path))
    tier_knobs = SNNServingTierConfig(heartbeat_interval_s=0.01,
                                      heartbeat_deadline_s=5.0)
    with make_cluster(PARAMS, CFG, knobs, tier_knobs,
                      patience=10_000, seed=0) as co:
        assert co.fault_cfg.heartbeat_deadline_s == 5.0
        co.submit(IMGS[0], request_id=0)
        res = co.run()
        assert as_tuple(res[0]) == baseline("reference")[0]


def test_coordinator_holding_accelerator_fails_fast(tmp_path, monkeypatch):
    """A coordinator that already holds the chip would leave its workers
    failing or blocked in init for the whole init timeout: it must refuse
    at once, before any worker process is spawned."""
    import time

    from repro.serve import cluster

    def no_spawn(*a, **kw):
        raise AssertionError("a worker was spawned")

    monkeypatch.setattr(cluster, "_held_accelerator", lambda: "tpu")
    monkeypatch.setattr(cluster.subprocess, "Popen", no_spawn)
    t0 = time.monotonic()
    with pytest.raises(RuntimeError,
                       match="already holds the tpu.*JAX_PLATFORMS=cpu"):
        make_co(tmp_path)
    assert time.monotonic() - t0 < 5.0


def test_held_accelerator_probe_on_host_cpu():
    """The real probe: a process that has only run JAX on the host CPU
    holds no chip, so the guard never fires there."""
    import jax.numpy as jnp

    from repro.serve import cluster

    jnp.ones(2).block_until_ready()
    assert cluster._held_accelerator() is None
