"""Staged vs fused encode→LIF: throughput and HBM bytes moved (§V-B).

The staged path launches the Poisson-encoder kernel and the LIF kernel
separately, materialising the full (T, B, N_in) uint8 spike tensor in HBM
between them — written once by the encoder, read once by the LIF layer.
The fused megakernel (kernels/fused_snn.py) keeps the spike stream in
VMEM/registers for the whole window, so the encoder→layer-1 hop moves
ZERO HBM bytes; only pixels, PRNG state and the small per-neuron outputs
cross the memory boundary.  That is the paper's "no external memory
access" property, and the acceptance bar here: the spike tensor the staged
path moves is ≥ T× the pixel stream itself.

Runs on random weights (no training needed) so it doubles as the CI
kernel-regression smoke: REPRO_BENCH_TINY=1 shrinks sizes.  Emits CSV
lines and saves results/bench/BENCH_fused.json (uploaded as a CI
artifact).
"""

from __future__ import annotations

import dataclasses
import os

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.snn_mnist import (SNN_CONFIG, SNN_CONFIG_DEEP,
                                     SNN_CONFIG_WIDE)
from repro.core import prng, snn
from repro.kernels import fused_snn, ops

from .common import emit, save_json, time_record


def _interp(backend: str) -> bool:
    """True when this backend's timing ran Pallas interpret mode (CPU)."""
    return backend.startswith("fused") or backend == "staged" \
        if jax.default_backend() != "tpu" else False


def _resident_weight_bytes(weights):
    """Per-program resident weight bytes, packed vs the pre-packing layout.

    MEASURED, not assumed: the packed figure is the actual ``nbytes`` of
    the plane arrays ``kernels.fused_snn.pack_weights`` emits for the
    128-padded shapes the kernel allocates — if packing ever regresses to
    a wider dtype or an extra plane, this number (and the CI gate on it)
    moves.  Legacy is the pre-PR layout over the same padded shapes:
    int16 storage plus the whole-matrix int32 cast the first kernel
    revision held live for the entire launch (6 B/weight).
    """
    pad = fused_snn._pad128
    packed_bytes = legacy_bytes = 0
    for w in weights:
        wp = jnp.pad(w, [(0, pad(w.shape[0]) - w.shape[0]),
                         (0, pad(w.shape[1]) - w.shape[1])])
        packed = fused_snn.pack_weights(wp)
        packed_bytes += packed.size * packed.dtype.itemsize
        legacy_bytes += wp.size * (2 + 4)       # int16 + resident i32 cast
    return {"packed_int8": int(packed_bytes),
            "legacy_int16_cast": int(legacy_bytes),
            "reduction": round(legacy_bytes / packed_bytes, 3)}


def _sizes():
    if os.environ.get("REPRO_BENCH_TINY"):
        return dict(batch=16, T=5, n_in=784, n_out=10, repeats=2)
    return dict(batch=128, T=20, n_in=784, n_out=10, repeats=3)


def _sizes_multilayer():
    if os.environ.get("REPRO_BENCH_TINY"):
        return dict(batch=8, T=4, layer_sizes=(784, 64, 32, 10), repeats=2)
    return dict(batch=64, T=20, layer_sizes=SNN_CONFIG_DEEP.layer_sizes,
                repeats=3)


def run():
    s = _sizes()
    batch, T, n_in, n_out = s["batch"], s["T"], s["n_in"], s["n_out"]
    rng = np.random.default_rng(0)
    w = jnp.asarray(rng.integers(-256, 256, (n_in, n_out)), jnp.int16)
    params_q = {"layers": [{"w_q": w, "scale": jnp.float32(1.0)}]}
    px = jnp.asarray(rng.integers(0, 256, (batch, n_in), dtype=np.uint8))
    st = prng.seed_state(11, px.shape)
    cfg = dataclasses.replace(SNN_CONFIG, num_steps=T)

    # --- bit-exactness across backends (same PRNG seeds) -----------------
    outs = {}
    times = {}
    recs = {}
    for backend in ("reference", "staged", "fused"):
        fn = jax.jit(lambda p, a, b, bk=backend:
                     snn.snn_apply_int(p, a, b, cfg, backend=bk)
                     ["spike_counts"])
        recs[backend] = time_record(fn, params_q, px, st,
                                    repeats=s["repeats"],
                                    interpret=_interp(backend))
        times[backend] = recs[backend].us
        outs[backend] = np.asarray(fn(params_q, px, st))
        emit(f"fused.{backend}", times[backend] / batch,
             f"batch={batch} T={T} "
             f"imgs_per_s={batch / (times[backend] * 1e-6):.0f}"
             + ("" if jax.default_backend() == "tpu"
                else " (Pallas interpret on CPU)" if backend != "reference"
                else ""))
    exact = (np.array_equal(outs["staged"], outs["fused"])
             and np.array_equal(outs["reference"], outs["fused"]))
    emit("fused.bit_identical", None, f"staged==fused==reference={exact}")
    assert exact, "backends disagree on spike counts"

    # --- HBM bytes moved for the encoder→layer-1 hop ---------------------
    # Staged: the (T, B, N_in) uint8 spike tensor is written by the encoder
    # launch and read back by the LIF launch.
    staged_hop = 2 * T * batch * n_in
    # Fused: the spike stream never leaves the core.
    fused_hop = 0
    # Common traffic both paths pay (pixels in, PRNG state in+out):
    stream = batch * n_in * (1 + 4 + 4)
    ratio_vs_pixels = staged_hop / (batch * n_in)
    emit("fused.hop_bytes_staged", None, f"{staged_hop}")
    emit("fused.hop_bytes_fused", None, f"{fused_hop}")
    emit("fused.hop_reduction", None,
         f"spike_tensor_vs_pixel_stream={ratio_vs_pixels:.0f}x "
         f"(>=T={T}x required) total_encoder_traffic="
         f"{(stream + staged_hop) / stream:.1f}x_less_when_fused")
    assert fused_hop == 0, "fused path must not materialise spikes"
    assert staged_hop >= T * batch * n_in, "hop accounting inconsistent"

    # --- resident weight bytes: int8-packed planes vs int16+cast ---------
    resident = _resident_weight_bytes((w,))
    emit("fused.resident_weight_bytes", None,
         f"packed_int8={resident['packed_int8']} "
         f"legacy_int16_cast={resident['legacy_int16_cast']} "
         f"reduction={resident['reduction']:.1f}x")
    assert resident["legacy_int16_cast"] >= 2 * resident["packed_int8"], \
        "packing must at least halve resident weight bytes"

    sparse = run_sparse(params_q, cfg, batch)

    save_json({
        "sizes": {k: v for k, v in s.items() if k != "repeats"},
        "us_per_image": {k: v / batch for k, v in times.items()},
        "bit_identical": bool(exact),
        "hop_bytes": {"staged": staged_hop, "fused": fused_hop},
        "hop_reduction_vs_pixels": ratio_vs_pixels,
        "resident_weight_bytes": resident,
        "sparse": sparse,
        "timing": {k: r.to_json() for k, r in recs.items()},
        "backend_platform": jax.default_backend(),
    }, "bench", "BENCH_fused.json")

    run_multilayer()
    run_streamed()
    run_telemetry()
    return times


def run_sparse(params_q, cfg, batch):
    """Executed-adds vs spike density under event-driven tile skipping.

    The kernel's energy counter counts ``input spikes × enabled outputs``
    — on the sparse path a skipped tile pair carries zero of either, so
    the counter measures exactly the adds the event-driven datapath
    executes, and must scale linearly with the Poisson density px/256
    (the analytic (1 − sparsity) law the paper's Table II argues from).
    """
    n_in, n_out = cfg.layer_sizes[0], cfg.layer_sizes[-1]
    T = cfg.num_steps
    weights = tuple(l["w_q"] for l in params_q["layers"])
    st = prng.seed_state(29, (batch, n_in))
    levels = [0, 33, 128, 255]
    dense_cap = T * batch * n_in * n_out        # every line spiking
    adds, fracs = [], []
    for px_level in levels:
        px = jnp.full((batch, n_in), px_level, jnp.uint8)
        out = ops.fused_snn_stack_op(
            px, st, weights, num_steps=T,
            decay_shift=cfg.lif.decay_shift,
            v_threshold=cfg.lif.v_threshold, sparse_skip=True)
        total = int(np.asarray(out["active_adds"]).sum())
        adds.append(total)
        fracs.append(total / dense_cap)
        emit(f"fused.sparse_adds@{px_level}", None,
             f"density={px_level / 256:.3f} executed_adds={total} "
             f"fraction_of_dense={total / dense_cap:.3f}")
    # executed adds must track density: fraction ≈ px/256 per level
    scaling_ok = all(abs(f - lv / 256) < 0.05
                     for f, lv in zip(fracs, levels))
    emit("fused.sparse_scaling", None,
         f"adds_track_density={scaling_ok} "
         f"(fractions={[round(f, 3) for f in fracs]})")
    assert adds[0] == 0, "zero-density input must execute zero adds"
    assert scaling_ok, "executed adds do not scale with spike density"
    return {"px_levels": levels,
            "densities": [lv / 256 for lv in levels],
            "executed_adds": adds,
            "fraction_of_dense": fracs,
            "scaling_ok": bool(scaling_ok)}


def run_multilayer():
    """Hidden-layer stacks: per-hop HBM spike bytes, staged vs fused.

    Bouvier et al. (arXiv:2005.01467) identify inter-layer spike traffic
    as the dominant cost of multi-layer SNN hardware.  The staged path
    materialises every hop — the encoder output AND each hidden
    activation train — as a (T, B, N) tensor written+read through HBM
    (2·T·B·N bytes per hop); the multi-layer megakernel carries all of it
    in VMEM scratch across the static layer loop, so every hop moves ZERO
    HBM bytes.  Acceptance bar: fused per-hop bytes are exactly 0 on a
    ≥2-hidden-layer stack while the backends stay bit-identical.
    """
    s = _sizes_multilayer()
    batch, T, sizes = s["batch"], s["T"], tuple(s["layer_sizes"])
    rng = np.random.default_rng(1)
    params_q = {"layers": [
        {"w_q": jnp.asarray(rng.integers(-256, 256, (n_in, n_out)),
                            jnp.int16),
         "scale": jnp.float32(1.0)}
        for n_in, n_out in zip(sizes[:-1], sizes[1:])]}
    px = jnp.asarray(rng.integers(0, 256, (batch, sizes[0]), dtype=np.uint8))
    st = prng.seed_state(23, px.shape)
    cfg = dataclasses.replace(SNN_CONFIG_DEEP, layer_sizes=sizes,
                              num_steps=T)

    outs, adds, times, recs = {}, {}, {}, {}
    for backend in ("reference", "staged", "fused"):
        fn = jax.jit(lambda p, a, b, bk=backend:
                     snn.snn_apply_int(p, a, b, cfg, backend=bk))
        recs[backend] = time_record(
            lambda p, a, b: fn(p, a, b)["spike_counts"], params_q, px, st,
            repeats=s["repeats"], interpret=_interp(backend))
        times[backend] = recs[backend].us
        out = fn(params_q, px, st)
        outs[backend] = np.asarray(out["spike_counts"])
        adds[backend] = np.asarray(out["active_adds"])
        emit(f"fused_ml.{backend}", times[backend] / batch,
             f"layers={len(sizes) - 1} batch={batch} T={T}"
             + ("" if jax.default_backend() == "tpu"
                else " (Pallas interpret on CPU)"
                if backend != "reference" else ""))
    exact = all(np.array_equal(outs["reference"], outs[b])
                and np.array_equal(adds["reference"], adds[b])
                for b in ("staged", "fused"))
    emit("fused_ml.bit_identical", None,
         f"counts+adds staged==fused==reference={exact}")
    assert exact, "multi-layer backends disagree"

    # Per-hop HBM spike bytes: hop 0 is encoder→layer1, hop l is
    # layer l→layer l+1.  Staged writes then reads each (T, B, N) uint8
    # spike train.  The fused path's zero is OBSERVED, not assumed: the
    # whole stack must lower to exactly one pallas_call (no inter-launch
    # tensor to round-trip) and must never materialise an input spike
    # train — if a regression reintroduces staged launches under the
    # fused backend, this gate (and the CI assert on the JSON) goes red.
    fused_jaxpr = str(jax.make_jaxpr(
        lambda p, a, b: snn.snn_apply_int(p, a, b, cfg, backend="fused")
        ["spike_counts"])(params_q, px, st))
    n_launches = fused_jaxpr.count("pallas_call")
    fused_out = snn.snn_apply_int(params_q, px, st, cfg, backend="fused")
    fused_is_one_launch = (n_launches == 1
                           and fused_out["input_spikes"] is None)
    emit("fused_ml.launches", None,
         f"fused_pallas_calls={n_launches} input_spikes_materialised="
         f"{fused_out['input_spikes'] is not None}")
    assert fused_is_one_launch, \
        f"fused path no longer a single launch ({n_launches} pallas_calls)"
    staged_hops = [2 * T * batch * n for n in sizes[:-1]]
    fused_hops = [0 if fused_is_one_launch else h for h in staged_hops]
    for i, (sh, fh) in enumerate(zip(staged_hops, fused_hops)):
        emit(f"fused_ml.hop{i}_bytes", None, f"staged={sh} fused={fh}")
    emit("fused_ml.hop_bytes_total", None,
         f"staged={sum(staged_hops)} fused={sum(fused_hops)} "
         f"({sum(staged_hops) / (batch * sizes[0]):.0f}x the pixel stream)")
    assert sum(fused_hops) == 0, "fused path must not materialise spikes"
    assert len(staged_hops) >= 3, "need >=2 hidden layers for this bench"

    resident = _resident_weight_bytes(
        tuple(l["w_q"] for l in params_q["layers"]))
    emit("fused_ml.resident_weight_bytes", None,
         f"packed_int8={resident['packed_int8']} "
         f"legacy_int16_cast={resident['legacy_int16_cast']} "
         f"reduction={resident['reduction']:.1f}x")

    save_json({
        "sizes": {"batch": batch, "T": T, "layer_sizes": list(sizes)},
        "us_per_image": {k: v / batch for k, v in times.items()},
        "bit_identical": bool(exact),
        "hop_bytes": {"staged": staged_hops, "fused": fused_hops,
                      "staged_total": sum(staged_hops),
                      "fused_total": sum(fused_hops)},
        "fused_single_launch": bool(fused_is_one_launch),
        "resident_weight_bytes": resident,
        "timing": {k: r.to_json() for k, r in recs.items()},
        "backend_platform": jax.default_backend(),
    }, "bench", "BENCH_fused_multilayer.json")
    return times


def _sizes_streamed():
    if os.environ.get("REPRO_BENCH_TINY"):
        return dict(batch=8, T=2, repeats=1)
    return dict(batch=16, T=8, repeats=2)


def run_streamed():
    """VMEM-oversized stack through the ``fused_streamed`` backend.

    ``SNN_CONFIG_WIDE``'s packed resident footprint (~13.5 MiB padded)
    exceeds the 12 MiB residency budget, so an explicit ``fused`` request
    must raise — and ``fused_streamed`` must run the whole stack in ONE
    Pallas launch anyway (packed weights double-buffered out of HBM),
    bit-identical to the reference scan.  Interpret mode on CPU; the
    wall-clock win is a TPU measurement (ROADMAP's on-TPU item).
    """
    s = _sizes_streamed()
    batch, T = s["batch"], s["T"]
    cfg = dataclasses.replace(SNN_CONFIG_WIDE, num_steps=T)
    sizes = cfg.layer_sizes
    rng = np.random.default_rng(5)
    params_q = {"layers": [
        {"w_q": jnp.asarray(rng.integers(-256, 256, (a, b)), jnp.int16),
         "scale": jnp.float32(1.0)}
        for a, b in zip(sizes[:-1], sizes[1:])]}
    px = jnp.asarray(rng.integers(0, 256, (batch, sizes[0]),
                                  dtype=np.uint8))
    st = prng.seed_state(31, px.shape)

    resident_mib = fused_snn.stack_vmem_bytes(sizes, 8, T) / 2**20
    streamed_mib = fused_snn.stack_vmem_bytes(sizes, 8, T,
                                              streamed=True) / 2**20
    budget_mib = fused_snn.VMEM_BUDGET_BYTES / 2**20
    emit("fused_streamed.vmem", None,
         f"resident={resident_mib:.1f}MiB streamed={streamed_mib:.1f}MiB "
         f"budget={budget_mib:.0f}MiB")
    assert resident_mib > budget_mib, \
        "streamed bench stack must exceed the residency budget"
    assert streamed_mib <= budget_mib, \
        "streamed working set must fit the budget"

    fused_raises = False
    try:
        snn.snn_apply_int(params_q, px, st, cfg, backend="fused")
    except ValueError:
        fused_raises = True
    emit("fused_streamed.fused_raises", None,
         f"explicit_fused_raises={fused_raises}")
    assert fused_raises, "oversized stack must reject backend='fused'"

    outs, times, recs = {}, {}, {}
    for backend in ("reference", "fused_streamed"):
        fn = jax.jit(lambda p, a, b, bk=backend:
                     snn.snn_apply_int(p, a, b, cfg, backend=bk))
        recs[backend] = time_record(
            lambda p, a, b: fn(p, a, b)["spike_counts"], params_q, px, st,
            repeats=s["repeats"], interpret=_interp(backend))
        times[backend] = recs[backend].us
        out = fn(params_q, px, st)
        outs[backend] = (np.asarray(out["spike_counts"]),
                         np.asarray(out["active_adds"]))
        emit(f"fused_streamed.{backend}", times[backend] / batch,
             f"layer_sizes={sizes} batch={batch} T={T}"
             + ("" if jax.default_backend() == "tpu"
                else " (Pallas interpret on CPU)"
                if backend != "reference" else ""))
    exact = all(np.array_equal(a, b) for a, b in
                zip(outs["reference"], outs["fused_streamed"]))
    emit("fused_streamed.bit_identical", None,
         f"counts+adds reference==fused_streamed={exact}")
    assert exact, "streamed backend disagrees with reference"

    jaxpr = str(jax.make_jaxpr(
        lambda p, a, b: snn.snn_apply_int(p, a, b, cfg,
                                          backend="fused_streamed")
        ["spike_counts"])(params_q, px, st))
    n_launches = jaxpr.count("pallas_call")
    emit("fused_streamed.launches", None, f"pallas_calls={n_launches}")
    assert n_launches == 1, "streamed stack must stay a single launch"

    save_json({
        "sizes": {"batch": batch, "T": T, "layer_sizes": list(sizes)},
        "us_per_image": {k: v / batch for k, v in times.items()},
        "bit_identical": bool(exact),
        "single_launch": n_launches == 1,
        "explicit_fused_raises": bool(fused_raises),
        "vmem_mib": {"resident": resident_mib, "streamed": streamed_mib,
                     "budget": budget_mib},
        "timing": {k: r.to_json() for k, r in recs.items()},
        "backend_platform": jax.default_backend(),
    }, "bench", "BENCH_fused_streamed.json")
    return times


def _sizes_telemetry():
    if os.environ.get("REPRO_BENCH_TINY"):
        return dict(batch=4, T=8, n_imgs=8, chunk=3)
    return dict(batch=8, T=20, n_imgs=24, chunk=4)


def run_telemetry():
    """Telemetry side channel + adaptive chunk-length controller.

    Two contract claims, all run-invariant and diffed by
    check_tracked / the CI gate:

      * ``telemetry_bit_identical`` — the ChunkTelemetry record
        (per-step/layer spike counts, prune occupancy, skipped MXU tile
        pairs) is bit-identical across the reference / staged / fused
        backends, and its adds equal the frozen energy counters
        (``adds_match``);
      * ``adaptive_matches_frozen`` — the same request stream served with
        the controller adaptive (live chunk lengths) returns bit-identical
        results to frozen mode: adaptivity only moves wall-clock.  The
        chunk trajectory is recorded so the tuning behavior itself is
        reviewable across PRs.
    """
    from repro.serve import AdaptiveDispatchConfig, SNNStreamEngine

    s = _sizes_telemetry()
    batch, T = s["batch"], s["T"]
    rng = np.random.default_rng(7)
    cfg = dataclasses.replace(SNN_CONFIG, num_steps=T, sparse_skip=True)
    n_in, n_out = cfg.layer_sizes[0], cfg.layer_sizes[-1]
    w = jnp.asarray(rng.integers(-256, 256, (n_in, n_out)), jnp.int16)
    params_q = {"layers": [{"w_q": w, "scale": jnp.float32(1.0)}]}

    # --- cross-backend bit-identity of the side channel ------------------
    px = jnp.asarray(np.minimum(rng.integers(0, 256, (batch, n_in)), 5)
                     .astype(np.uint8))                # sparse → tiles skip
    st = prng.seed_state(19, px.shape)
    outs = {b: snn.snn_apply_int(params_q, px, st, cfg, backend=b)
            for b in ("reference", "staged", "fused")}
    tel_identical = all(
        np.array_equal(np.asarray(getattr(outs["reference"]["telemetry"], f)),
                       np.asarray(getattr(outs[b]["telemetry"], f)))
        for b in ("staged", "fused") for f in ("n_spk", "n_en",
                                               "tiles_skipped"))
    adds_match = all(
        np.array_equal(np.asarray(outs[b]["telemetry"].adds).sum(axis=1),
                       np.asarray(outs[b]["active_adds"]))
        for b in outs)
    skipped = int(np.asarray(outs["fused"]["telemetry"].tiles_skipped).sum())
    obs_density = float(np.asarray(
        outs["fused"]["telemetry"].densities(cfg.layer_sizes))[:, 0].mean())
    emit("telemetry.bit_identical", None,
         f"staged==fused==reference={tel_identical} adds_match={adds_match} "
         f"tiles_skipped={skipped} layer0_density={obs_density:.4f}")
    assert tel_identical, "telemetry diverges across backends"
    assert adds_match, "telemetry adds != energy counters"
    assert skipped > 0, "sparse input must skip tiles"

    # --- adaptivity is value-neutral + trajectory record -----------------
    imgs = rng.integers(0, 256, (s["n_imgs"], n_in), dtype=np.uint8)

    def serve(adaptive):
        eng = SNNStreamEngine(params_q, cfg, batch_size=batch,
                              chunk_steps=s["chunk"], patience=2, seed=3,
                              backend="reference", adaptive=adaptive)
        ids = [eng.submit(im) for im in imgs]
        res = eng.run()
        return {i: (res[i].pred, res[i].steps, res[i].adds,
                    tuple(res[i].spike_counts.tolist())) for i in ids}, eng

    frozen_res, frozen_eng = serve(AdaptiveDispatchConfig(adaptive=False))
    adaptive_res, adaptive_eng = serve(AdaptiveDispatchConfig(
        adaptive=True, min_chunk_steps=2, max_chunk_steps=8))
    matches = frozen_res == adaptive_res
    chunk_traj = [h["chunk_steps"] for h in adaptive_eng.controller.history]
    emit("telemetry.adaptive_matches_frozen", None,
         f"{matches} chunk_trajectory={chunk_traj[:8]}...")
    assert matches, "adaptive mode changed predictions"
    assert frozen_eng.controller.history == [], \
        "frozen controller must record nothing (no readbacks)"

    save_json({
        "sizes": {"batch": batch, "T": T, "n_imgs": s["n_imgs"]},
        "telemetry_bit_identical": bool(tel_identical),
        "adds_match": bool(adds_match),
        "tiles_skipped": skipped,
        "adaptive_matches_frozen": bool(matches),
        "chunk_trajectory": chunk_traj,
        "backend_platform": jax.default_backend(),
    }, "bench", "BENCH_telemetry.json")


if __name__ == "__main__":
    run()
