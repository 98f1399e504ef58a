"""Model-axis sharding benchmark: the 2-D (data × model) mesh contract,
re-verified where the numbers are produced.

Spawns a 4-device forced-host subprocess (the same trick as the CI
multidevice lane) and reports

  * **bit-identity across mesh shapes** — 2×2 (data × model) and 1×4
    (pure model) engines reproduce the single-device engine
    prediction-for-prediction for both chunk backends,
  * **telemetry-for-telemetry** — per-lane spike/enable counts from the
    model-sharded step match the unsharded step bit-for-bit, and on
    128-aligned shard widths the per-shard skipped-tile counts sum to
    exactly the unsharded layer count,
  * **failover placement-independence** — lanes snapshot from a
    model-sharded engine adopt onto a plain single-device engine and
    finish bit-identical (the PR-7 contract, extended),
  * **WIDE feasibility** — SNN_CONFIG_WIDE (784-2048-2048-10) exceeds
    the VMEM budget single-device but each 4-way model shard fits:
    per-device resident weight bytes ≤ budget, and backend resolution
    lands on the resident ``fused`` megakernel instead of
    ``fused_streamed``.

Saves results/bench/BENCH_model_sharded.json (contract fields diffed
against the committed copy by benchmarks.check_tracked).
REPRO_BENCH_TINY=1 shrinks the mesh workload for the smoke lane.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import textwrap
import time

from .common import emit, save_json

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_SUB = """
    import dataclasses, json, time
    import jax, jax.numpy as jnp, numpy as np
    from jax.sharding import PartitionSpec as P
    from repro.configs.snn_mnist import (SNN_CONFIG, SNNStreamMeshConfig,
                                         make_stream_engine)
    from repro.core import prng, snn
    from repro.core.lif import LIFStateInt
    from repro.distributed.sharding import make_2d_device_mesh
    from repro.kernels.fused_snn import layer_shard_ways
    from repro.serve import SNNStreamEngine

    assert len(jax.devices()) == 4, jax.devices()
    tiny = TINY
    sizes = (24, 16, 10) if tiny else (784, 256, 128, 10)
    T = 8 if tiny else 12
    n_imgs = 12 if tiny else 20

    def small_net(rng, sizes):
        return {"layers": [
            {"w_q": jnp.asarray(rng.integers(-256, 256, (a, b)), jnp.int16),
             "scale": jnp.float32(1.0)}
            for a, b in zip(sizes[:-1], sizes[1:])]}

    def sig(r):
        return (r.pred, r.steps, r.adds, r.early_exit,
                tuple(r.spike_counts.tolist()))

    rng = np.random.default_rng(0)
    cfg = dataclasses.replace(SNN_CONFIG, layer_sizes=sizes, num_steps=T)
    params_q = small_net(rng, sizes)
    imgs = rng.integers(0, 256, (n_imgs, sizes[0]), dtype=np.uint8)

    # ---- bit-identity across mesh shapes vs single-device --------------
    identical, t_mesh = True, None
    for backend in ("reference", "fused"):
        ref = SNNStreamEngine(params_q, cfg, batch_size=8, chunk_steps=3,
                              patience=1, seed=11, backend=backend)
        for im in imgs:
            ref.submit(im)
        r1 = ref.run()
        for nd, md, lpd in ((2, 2, 4), (1, 4, 8)):
            knobs = SNNStreamMeshConfig(num_devices=nd, model_devices=md,
                                        lanes_per_device=lpd, chunk_steps=3)
            eng = make_stream_engine(params_q, cfg, knobs, patience=1,
                                     seed=11, backend=backend)
            for im in imgs:
                eng.submit(im)
            t0 = time.perf_counter()
            r2 = eng.run()
            dt = time.perf_counter() - t0
            if backend == "reference" and (nd, md) == (2, 2):
                t_mesh = dt
            identical &= (set(r1) == set(r2) and
                          all(sig(r1[k]) == sig(r2[k]) for k in r1))

    # ---- telemetry bit-identity (128-aligned shard widths) -------------
    tsz = (784, 512, 512, 10)
    tw = {"layers": [
        {"w_q": jnp.asarray(rng.integers(-256, 256, (a, b)), jnp.int16),
         "scale": jnp.float32(1.0)}
        for a, b in zip(tsz[:-1], tsz[1:])]}
    weights = tuple(jnp.asarray(l["w_q"], jnp.int32) for l in tw["layers"])
    B = 8
    pixels = jnp.asarray(rng.integers(0, 256, (B, tsz[0]), np.uint8))
    rng_state = prng.seed_state(3, (B, tsz[0]))
    states = tuple(LIFStateInt(v=jnp.zeros((B, n), jnp.int32),
                               enable=jnp.ones((B, n), bool))
                   for n in tsz[1:])
    _, st1, x1, adds1, tel1 = snn.snn_int_stack_step(
        rng_state, pixels, states, weights, cfg.lif, active_pruning=True)
    mesh = make_2d_device_mesh(1, 4)
    ways = layer_shard_ways(tsz, 4)

    def body(rng_state, pixels, states, weights):
        return snn.snn_int_stack_step_sharded(
            rng_state, pixels, states, weights, cfg.lif,
            model_axis="model", ways=ways, active_pruning=True,
            contraction="jnp")

    rep = P()
    w_specs = tuple(P(None, "model") if w > 1 else P() for w in ways)
    st_specs = tuple(LIFStateInt(v=rep, enable=rep) for _ in states)
    tel_spec = {"n_spk": rep, "n_en": rep,
                "tiles": P(None, ("data", "model"))}
    f = jax.shard_map(body, mesh=mesh, check_vma=False,
                      in_specs=(rep, rep, st_specs, w_specs),
                      out_specs=(rep, st_specs, rep, rep, tel_spec))
    _, st2, x2, adds2, tel2 = f(rng_state, pixels, states, weights)
    t1t = np.asarray(tel1["tiles"])
    t2t = np.asarray(tel2["tiles"])
    nb = t1t.shape[1]
    per_shard = t2t.reshape(t1t.shape[0], 4, nb)
    tiles_ok = all(
        (per_shard[l].sum(axis=0) == t1t[l]).all() if w > 1
        else (per_shard[l] == t1t[l][None, :]).all()
        for l, w in enumerate(ways))
    tel_identical = bool(
        (np.asarray(x1) == np.asarray(x2)).all()
        and (np.asarray(adds1) == np.asarray(adds2)).all()
        and (np.asarray(tel1["n_spk"]) == np.asarray(tel2["n_spk"])).all()
        and (np.asarray(tel1["n_en"]) == np.asarray(tel2["n_en"])).all()
        and tiles_ok)

    # ---- failover: model-sharded snapshot → single-device adopt --------
    base = SNNStreamEngine(params_q, cfg, batch_size=8, chunk_steps=3,
                           patience=10_000, seed=9, backend="reference")
    for im in imgs[:8]:
        base.submit(im)
    want = base.run()
    knobs = SNNStreamMeshConfig(num_devices=2, model_devices=2,
                                lanes_per_device=4, chunk_steps=3)
    src = make_stream_engine(params_q, cfg, knobs, patience=10_000,
                             seed=9, backend="reference")
    for im in imgs[:8]:
        src.submit(im)
    src.run(max_chunks=2)
    rows = src.snapshot_lanes()
    dst = SNNStreamEngine(params_q, cfg, batch_size=8, chunk_steps=3,
                          patience=10_000, seed=9, backend="reference")
    for rid, row in rows:
        dst.adopt(rid, row)
    got = dst.run()
    failover_identical = (set(got) == set(want) and
                          all(sig(got[k]) == sig(want[k]) for k in want))

    print("RESULT " + json.dumps({
        "model_sharded_bit_identical": identical,
        "telemetry_bit_identical_model": tel_identical,
        "failover_bit_identical": failover_identical,
        "mesh_seconds_2x2": t_mesh,
        "n_imgs": n_imgs,
        "layer_sizes": list(sizes),
    }))
"""


def _wide_feasibility() -> dict:
    """Host-side VMEM math + backend resolution for SNN_CONFIG_WIDE on a
    4-way model axis (no devices needed — the estimate is pure)."""
    import jax

    from repro.configs.snn_mnist import SNN_CONFIG_WIDE
    from repro.core.snn import resolve_backend
    from repro.kernels.fused_snn import (VMEM_BUDGET_BYTES, _pad128,
                                         layer_shard_ways,
                                         stack_vmem_bytes)
    sizes = SNN_CONFIG_WIDE.layer_sizes
    n_layers = len(sizes) - 1
    ways = layer_shard_ways(sizes, 4)
    shard_weight_bytes = sum(
        _pad128(a) * _pad128(b // w) * 2
        for a, b, w in zip(sizes[:-1], sizes[1:], ways))
    full = stack_vmem_bytes(sizes, num_steps=4)
    shard = stack_vmem_bytes(sizes, num_steps=4, model_shards=4)
    orig = jax.default_backend
    jax.default_backend = lambda: "tpu"       # resolution is host math
    try:
        kw = dict(layer_sizes=sizes, trace_steps=4, local_batch=256)
        single = resolve_backend(SNN_CONFIG_WIDE, "auto", n_layers, **kw)
        sharded = resolve_backend(SNN_CONFIG_WIDE, "auto", n_layers,
                                  model_shards=4, **kw)
    finally:
        jax.default_backend = orig
    return {
        "layer_shard_ways": list(ways),
        "per_device_resident_weight_bytes": shard_weight_bytes,
        "vmem_budget_bytes": VMEM_BUDGET_BYTES,
        "stack_vmem_bytes_full": full,
        "stack_vmem_bytes_4way_shard": shard,
        "single_device_backend": single,
        "model_sharded_backend": sharded,
        "wide_fused_resident": (single == "fused_streamed"
                                and sharded == "fused"),
        "wide_shard_fits_vmem": (full > VMEM_BUDGET_BYTES
                                 and shard <= VMEM_BUDGET_BYTES
                                 and shard_weight_bytes
                                 <= VMEM_BUDGET_BYTES),
    }


def run():
    tiny = bool(os.environ.get("REPRO_BENCH_TINY"))
    wide = _wide_feasibility()
    emit("model_sharded.wide_feasibility", None,
         f"shard_weight_bytes={wide['per_device_resident_weight_bytes']} "
         f"budget={wide['vmem_budget_bytes']} "
         f"single={wide['single_device_backend']} "
         f"4way={wide['model_sharded_backend']}")

    env = dict(os.environ,
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               JAX_PLATFORMS="cpu",
               PYTHONPATH=os.path.join(REPO_ROOT, "src"))
    code = textwrap.dedent(_SUB).replace("TINY", repr(tiny))
    t0 = time.perf_counter()
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=900)
    dt = time.perf_counter() - t0
    if out.returncode != 0:
        raise RuntimeError(f"mesh subprocess failed:\n{out.stderr[-3000:]}")
    line = [ln for ln in out.stdout.splitlines()
            if ln.startswith("RESULT ")][-1]
    mesh = json.loads(line[len("RESULT "):])
    emit("model_sharded.mesh_identity",
         dt * 1e6 / mesh["n_imgs"],
         f"bit_identical={mesh['model_sharded_bit_identical']} "
         f"telemetry={mesh['telemetry_bit_identical_model']} "
         f"failover={mesh['failover_bit_identical']}")

    save_json({
        "mesh_shape": [2, 2],
        "devices": 4,
        "layer_sizes": mesh["layer_sizes"],
        "wide": wide,
        "model_sharded_bit_identical": mesh["model_sharded_bit_identical"],
        "telemetry_bit_identical_model":
            mesh["telemetry_bit_identical_model"],
        "failover_bit_identical": mesh["failover_bit_identical"],
        "wide_fused_resident": wide["wide_fused_resident"],
        "wide_shard_fits_vmem": wide["wide_shard_fits_vmem"],
        "mesh_seconds_2x2": mesh["mesh_seconds_2x2"],
    }, "bench", "BENCH_model_sharded.json")
    assert mesh["model_sharded_bit_identical"]
    assert mesh["telemetry_bit_identical_model"]
    assert mesh["failover_bit_identical"]
    assert wide["wide_fused_resident"] and wide["wide_shard_fits_vmem"]
    return mesh


if __name__ == "__main__":
    run()
