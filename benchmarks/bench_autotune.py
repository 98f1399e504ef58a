"""Wall-clock autotuner + persisted dispatch cache (§ROADMAP "wall-clock
autotuning of the dispatch shapes").

Runs the telemetry-seeded search of ``repro.tune`` against a
deterministic open-loop arrival schedule, persists the winner to the
versioned JSON dispatch cache under ``results/tune/``, then proves the
three contract claims the CI gate diffs (benchmarks/check_tracked.py):

  * ``tuned_bit_identical`` — the tuned shapes reproduce the default
    engine's predictions and retirement steps exactly, on the reference
    AND fused backends, single-device AND sharded.  The cache may only
    change *when* work happens, never *what* is computed.
  * ``tuned_not_slower`` — median tuned seconds-per-retired-request is
    within 5% of the default shapes measured in the same session.  The
    default is always a candidate and the winner is the argmin over all
    candidates including it, so this holds by construction; the field
    records that the invariant actually survived measurement noise.
  * ``cache_roundtrip_ok`` — the persisted file reloads to a hit on the
    same key, arms engines (single-device, sharded, and the serving
    tier) whose startup decisions record the hit, and a corrupted copy
    is rejected with a warning while the engine falls back to static
    defaults instead of crashing.

Wall-clock numbers are measurement provenance, tagged with
``{device_kind, interpret}`` — never contract fields.
"""

from __future__ import annotations

import dataclasses
import json
import os
import warnings

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.snn_mnist import SNN_CONFIG
from repro.tune import (ArrivalSchedule, AutotuneConfig, DispatchCache,
                        autotune_engine, config_fingerprint,
                        serve_schedule, write_cache)

from .common import emit, results_path, save_json


def _sizes():
    if os.environ.get("REPRO_BENCH_TINY"):
        return dict(T=6, n_requests=8, per_round=2, repeats=2,
                    chunk_grid=(2, 3), lanes_grid=(4, 8),
                    max_candidates=5, check_requests=6)
    return dict(T=10, n_requests=24, per_round=2, repeats=3,
                chunk_grid=(2, 3, 4, 6), lanes_grid=(4, 8, 16),
                max_candidates=10, check_requests=10)


def _net(rng, cfg):
    n_in, n_out = cfg.layer_sizes[0], cfg.layer_sizes[-1]
    w = jnp.asarray(rng.integers(-256, 256, (n_in, n_out)), jnp.int16)
    return {"layers": [{"w_q": w, "scale": jnp.float32(1.0)}]}


def _bits(results: dict) -> dict:
    return {int(rid): (int(r.pred), int(r.steps))
            for rid, r in results.items()}


def _serve(engine, sched, pixels):
    return _bits(serve_schedule(engine, sched, pixels))


def run():
    from repro.serve import ShardedSNNStreamEngine, SNNStreamEngine
    s = _sizes()
    rng = np.random.default_rng(17)
    cfg = dataclasses.replace(SNN_CONFIG, num_steps=s["T"],
                              sparse_skip=True)
    params_q = _net(rng, cfg)
    sched = ArrivalSchedule(n_requests=s["n_requests"],
                            per_round=s["per_round"], seed=97)
    tc = AutotuneConfig(chunk_steps_grid=s["chunk_grid"],
                        lanes_grid=s["lanes_grid"], schedule=sched,
                        repeats=s["repeats"],
                        max_candidates=s["max_candidates"])

    # ---- the measured search (auto backend: reference on CPU hosts) -----
    result = autotune_engine(params_q, cfg, tune_cfg=tc, patience=2,
                             seed=3)
    tuned = result.tuned
    ratio = (tuned.seconds_per_retired_request
             / max(result.baseline_spr, 1e-12))
    tuned_not_slower = ratio <= 1.05
    emit("autotune.search", None,
         f"candidates={len(result.records)} "
         f"pruned={result.pruned} probe_service_steps="
         f"{result.probe['service_steps_ewma']:.4f}")
    emit("autotune.winner", tuned.seconds_per_retired_request * 1e6,
         f"chunk={tuned.chunk_steps} block_b={tuned.block_b} "
         f"lanes={tuned.lanes_per_device} "
         f"backend={tuned.backend} "
         f"s_per_req_vs_default={ratio:.3f}x")
    assert result.bit_identical, \
        "a measured candidate changed predictions — dispatch knobs must " \
        "be value-neutral"
    assert tuned_not_slower, \
        f"winner slower than the default it was measured against " \
        f"({ratio:.3f}x)"

    # ---- persist: single-device key + this host's sharded mesh key ------
    n_dev = len(jax.devices())
    path = results_path("tune", "dispatch_cache.json")
    write_cache(result, path, backend_request="auto",
                mesh_shapes=((1,), (n_dev, 1)))
    emit("autotune.cache_written", None,
         f"path=results/tune/dispatch_cache.json "
         f"fingerprint={result.fingerprint} meshes=1,{n_dev}x1")

    # ---- tuned shapes are value-neutral per backend, per topology -------
    check_sched = ArrivalSchedule(n_requests=s["check_requests"],
                                  per_round=2, seed=53)
    pixels = check_sched.pixels(cfg.layer_sizes[0])
    identity = {}
    for backend in ("reference", "fused"):
        base = SNNStreamEngine(params_q, cfg, backend=backend, patience=2,
                               seed=3, dispatch_cache=False)
        tuned_eng = SNNStreamEngine(
            params_q, cfg, batch_size=tuned.lanes_per_device,
            chunk_steps=tuned.chunk_steps, block_b=tuned.block_b,
            backend=backend, patience=2, seed=3, dispatch_cache=False)
        identity[f"single.{backend}"] = (
            _serve(base, check_sched, pixels)
            == _serve(tuned_eng, check_sched, pixels))
        base_sh = ShardedSNNStreamEngine(
            params_q, cfg, backend=backend, patience=2, seed=3,
            dispatch_cache=False)
        tuned_sh = ShardedSNNStreamEngine(
            params_q, cfg, lanes_per_device=tuned.lanes_per_device,
            chunk_steps=tuned.chunk_steps, block_b=tuned.block_b,
            backend=backend, patience=2, seed=3, dispatch_cache=False)
        identity[f"sharded.{backend}"] = (
            _serve(base_sh, check_sched, pixels)
            == _serve(tuned_sh, check_sched, pixels))
    tuned_bit_identical = result.bit_identical and all(identity.values())
    for k, ok in identity.items():
        emit(f"autotune.identity.{k}", None, f"tuned==default={ok}")
    assert tuned_bit_identical, f"tuned shapes changed results: {identity}"

    # ---- the persisted cache arms engines and records the hit -----------
    loaded = DispatchCache.load(path)
    decision = loaded.lookup(
        fingerprint=result.fingerprint, device_kind=result.device_kind,
        mesh_shape=(1,), backend="auto")
    armed = SNNStreamEngine(params_q, cfg, patience=2, seed=3,
                            dispatch_cache=path)
    armed_sh = ShardedSNNStreamEngine(params_q, cfg, patience=2, seed=3,
                                      dispatch_cache=path)
    plain = SNNStreamEngine(params_q, cfg, patience=2, seed=3,
                            dispatch_cache=False)
    armed_hits = (decision.hit and armed.cache_decision.hit
                  and armed_sh.cache_decision.hit)
    armed_identical = (_serve(plain, check_sched, pixels)
                       == _serve(armed, check_sched, pixels))
    emit("autotune.cache_armed", None,
         f"lookup_hit={decision.hit} engine_hit={armed.cache_decision.hit} "
         f"sharded_hit={armed_sh.cache_decision.hit} "
         f"armed==static={armed_identical} "
         f"armed_chunk={armed.controller.chunk_steps}")
    assert armed_identical, "cache-armed engine changed predictions"

    # ---- corrupt copies are rejected loudly, never crash startup --------
    corrupt = results_path("tune", "dispatch_cache_corrupt.json")
    with open(corrupt, "w") as f:
        f.write("{not json")
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        fallback = SNNStreamEngine(params_q, cfg, patience=2, seed=3,
                                   dispatch_cache=corrupt)
    rejects_corrupt = (not fallback.cache_decision.hit
                       and len(caught) >= 1)
    emit("autotune.corrupt_fallback", None,
         f"hit={fallback.cache_decision.hit} warned={len(caught) >= 1} "
         f"reason={fallback.cache_decision.reason[:60]!r}")
    assert rejects_corrupt, "corrupt cache must warn and fall back"

    cache_roundtrip_ok = bool(armed_hits and armed_identical
                              and rejects_corrupt)
    assert cache_roundtrip_ok

    with open(path) as f:
        persisted = json.load(f)
    save_json({
        "sizes": {k: v for k, v in s.items()},
        "fingerprint": result.fingerprint,
        "device_kind": result.device_kind,
        "fingerprint_matches": config_fingerprint(cfg) == result.fingerprint,
        "tuned": tuned.to_json(),
        "default": result.default.to_json(),
        "baseline_seconds_per_retired_request": result.baseline_spr,
        "tuned_vs_default_ratio": ratio,
        "tuned_bit_identical": bool(tuned_bit_identical),
        "tuned_not_slower": bool(tuned_not_slower),
        "cache_roundtrip_ok": cache_roundtrip_ok,
        "identity_matrix": {k: bool(v) for k, v in identity.items()},
        "candidates": result.records,
        "probe": result.probe,
        "pruned": result.pruned,
        "cache_codec_version": persisted.get("codec_version"),
        "cache_entries": sorted(persisted.get("entries", {})),
        "backend_platform": jax.default_backend(),
    }, "bench", "BENCH_autotune.json")
    return result


if __name__ == "__main__":
    run()
