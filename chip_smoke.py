"""Chip smoke test: the served SNN path, end to end, on a TPU.

    python chip_smoke.py              # one chip: every phase below
    python chip_smoke.py --chips 4    # the four-chip mesh phase only

One process.  Weights come from a seed: on one chip the paper config
(784→10) is trained briefly on the procedural digits; DEEP, WIDE and the
four-chip phase quantize seeded random weights.  No weight file, dataset
path or dispatch cache is read.

Each single-chip phase serves a few hundred digit requests through
``SNNStreamEngine`` and then through ``SNNServingTier`` on a 64-lane tile
(eight 8-row kernel blocks, ``chunk_steps=4``), checks the backend
``auto`` resolved to, and requires predictions, retirement steps and
executed-add counters bit-identical to the same requests served with
``backend="reference"`` on the same chip.  One-shot ``snn_apply_int``
is compared the same way.  ``--chips 4`` serves the paper config on a
4×1 data mesh and WIDE on a 1×4 model mesh, each against the
single-device engine on device 0.

Any failed check raises, so the exit code is nonzero.  The last line of
standard output is the JSON result, printed only when every check held.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

# The smoke test runs the shipped defaults: no REPRO_* override (fault
# plans, tile-skip or dispatch settings) may steer it.
for _k in [k for k in os.environ if k.startswith("REPRO_")]:
    del os.environ[_k]

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax.experimental import pallas as pl  # noqa: E402

from repro.compile_cache import enable_compile_cache  # noqa: E402
from repro.configs.snn_mnist import (SNN_CONFIG, SNN_CONFIG_DEEP,  # noqa: E402
                                     SNN_CONFIG_PRUNED, SNN_CONFIG_WIDE)
from repro.core import prng, snn  # noqa: E402
from repro.core.train_snn import train_bptt  # noqa: E402
from repro.data import digits  # noqa: E402
from repro.distributed.sharding import make_2d_device_mesh  # noqa: E402
from repro.serve import (ShardedSNNStreamEngine, SNNServingTier,  # noqa: E402
                         SNNStreamEngine)

LANES = 64            # lane tile: eight 8-row kernel blocks
CHUNK = 4
N_REQUESTS = 256
TRAIN_STEPS = 400
SEED = 0


class SmokeFailure(RuntimeError):
    pass


def check(ok: bool, msg: str) -> None:
    if not ok:
        raise SmokeFailure(msg)


# ---- every Pallas launch traced in this process, with its interpret flag
_KERNELS: list[tuple[str, bool]] = []
_pallas_call = pl.pallas_call


def _recording_pallas_call(kernel, *args, **kwargs):
    name = getattr(getattr(kernel, "func", kernel), "__name__", str(kernel))
    _KERNELS.append((name, bool(kwargs.get("interpret", False))))
    return _pallas_call(kernel, *args, **kwargs)


pl.pallas_call = _recording_pallas_call


def log(msg: str) -> None:
    print(msg, flush=True)


def signature(r) -> tuple:
    return (r.pred, r.steps, r.adds, r.early_exit,
            tuple(np.asarray(r.spike_counts).tolist()))


def compare_results(got: dict, want: dict, what: str) -> None:
    check(sorted(got) == sorted(want),
          f"{what}: served ids differ ({len(got)} vs {len(want)})")
    bad = [rid for rid in want if signature(got[rid]) != signature(want[rid])]
    check(not bad, f"{what}: {len(bad)} of {len(want)} requests differ from "
                   f"reference, first id {bad[:1]}: "
                   f"{[signature(got[b]) for b in bad[:1]]} vs "
                   f"{[signature(want[b]) for b in bad[:1]]}")


def drive(server, imgs) -> tuple[dict, float, float]:
    """Submit ``imgs``, run to completion; returns (results, seconds of
    the first scheduling step, compilation included, total seconds)."""
    for i, im in enumerate(imgs):
        server.submit(im, request_id=i)
    t0 = time.perf_counter()
    server.step()
    first = time.perf_counter() - t0
    res = dict(server.run())
    return res, first, time.perf_counter() - t0


def serve_phase(name: str, params_q: dict, cfg, expect: str, imgs) -> dict:
    """Engine then tier, auto vs reference; returns the auto results."""
    def engine(backend):
        return SNNStreamEngine(params_q, cfg, batch_size=LANES,
                               chunk_steps=CHUNK, seed=SEED, backend=backend,
                               dispatch_cache=False)

    eng = engine(None)
    check(eng.backend == expect,
          f"{name}: auto resolved {eng.backend!r}, expected {expect!r}")
    got, first, total = drive(eng, imgs)
    want, _, _ = drive(engine("reference"), imgs)
    compare_results(got, want, f"{name} engine")
    steps = np.mean([r.steps for r in got.values()])
    log(f"phase {name} engine: backend={eng.backend} "
        f"first_step_incl_compile_s={first:.2f} total_s={total:.2f} "
        f"requests={len(got)} mean_steps={steps:.2f} "
        f"bit_identical_to_reference=True")

    def tier(backend):
        return SNNServingTier(params_q, cfg, num_engines=2,
                              lanes_per_engine=LANES, chunk_steps=CHUNK,
                              seed=SEED, backend=backend,
                              dispatch_cache=False)

    t = tier(None)
    backends = sorted({e.backend for e in t.engines})
    check(backends == [expect],
          f"{name}: tier engines resolved {backends}, expected {expect!r}")
    tgot, first, total = drive(t, imgs)
    tref = tier("reference")
    twant, _, _ = drive(tref, imgs)
    check(not t.shed and not t.faulted and not tref.shed and not tref.faulted,
          f"{name} tier: shed/faulted requests on a fault-free run")
    compare_results(tgot, twant, f"{name} tier")
    log(f"phase {name} tier: backend={expect} engines={len(t.engines)} "
        f"first_step_incl_compile_s={first:.2f} total_s={total:.2f} "
        f"requests={len(tgot)} "
        f"mean_steps={np.mean([r.steps for r in tgot.values()]):.2f} "
        f"bit_identical_to_reference=True")
    return got


def one_shot(name: str, params_q: dict, cfg, backend: str, imgs) -> None:
    px = jnp.asarray(imgs[:LANES])
    st = prng.seed_state(SEED, px.shape)
    got = snn.snn_apply_int(params_q, px, st, cfg, backend=backend)
    want = snn.snn_apply_int(params_q, px, st, cfg, backend="reference")
    for key in ("pred", "spike_counts", "first_spike_t", "v_final",
                "v_trace", "active_adds", "prng_state"):
        check(np.array_equal(np.asarray(got[key]), np.asarray(want[key])),
              f"{name} one-shot {backend}: {key} differs from reference")
    for field in ("n_spk", "n_en", "tiles_skipped"):
        check(np.array_equal(np.asarray(getattr(got["telemetry"], field)),
                             np.asarray(getattr(want["telemetry"], field))),
              f"{name} one-shot {backend}: telemetry {field} differs")
    log(f"phase {name} one-shot: snn_apply_int backend={backend} "
        f"batch={LANES} bit_identical_to_reference=True")


def quantized(cfg, params=None) -> dict:
    if params is None:
        params = snn.snn_init(jax.random.PRNGKey(SEED), cfg)
    return snn.quantize_params(params, cfg)


def single_chip(ds) -> None:
    imgs = (ds.x_test[:N_REQUESTS] * 255).astype(np.uint8)
    labels = ds.y_test[:N_REQUESTS]
    t0 = time.perf_counter()
    trained = train_bptt(SNN_CONFIG, ds, steps=TRAIN_STEPS, seed=SEED)
    log(f"setup: trained paper config {TRAIN_STEPS} BPTT steps in "
        f"{time.perf_counter() - t0:.2f}s")

    paper_q = quantized(SNN_CONFIG, trained)
    got = serve_phase("paper", paper_q, SNN_CONFIG, "fused", imgs)
    acc = float(np.mean([got[i].pred == labels[i] for i in range(len(labels))]))
    log(f"paper config accuracy on {len(labels)} served digits: {acc:.4f}")
    check(acc > 0.5, f"paper config accuracy {acc:.4f} is not above chance")
    one_shot("paper", paper_q, SNN_CONFIG, "fused", imgs)

    pruned_q = quantized(SNN_CONFIG_PRUNED, trained)
    serve_phase("pruned", pruned_q, SNN_CONFIG_PRUNED, "fused", imgs)
    one_shot("pruned", pruned_q, SNN_CONFIG_PRUNED, "fused", imgs)

    deep_q = quantized(SNN_CONFIG_DEEP)
    serve_phase("deep", deep_q, SNN_CONFIG_DEEP, "fused", imgs)
    one_shot("deep", deep_q, SNN_CONFIG_DEEP, "fused", imgs)

    wide_q = quantized(SNN_CONFIG_WIDE)
    serve_phase("wide", wide_q, SNN_CONFIG_WIDE, "fused_streamed", imgs)
    one_shot("wide", wide_q, SNN_CONFIG_WIDE, "fused_streamed", imgs)


def _devices_of(tree) -> set:
    return {s.device for leaf in jax.tree.leaves(tree)
            for s in leaf.addressable_shards}


def mesh_phase(name: str, params_q: dict, cfg, mesh, expect: str,
               imgs) -> None:
    devs = set(mesh.devices.flat)
    sh = ShardedSNNStreamEngine(params_q, cfg, mesh=mesh, batch_size=LANES,
                                chunk_steps=CHUNK, seed=SEED,
                                dispatch_cache=False)
    check(sh.backend == expect,
          f"{name}: auto resolved {sh.backend!r}, expected {expect!r}")
    got, first, total = drive(sh, imgs)
    check(_devices_of(sh.lanes) == devs,
          f"{name}: lane state lives on {len(_devices_of(sh.lanes))} "
          f"devices, not the mesh's {len(devs)}")
    check(_devices_of(sh.weights) == devs,
          f"{name}: weights live on {len(_devices_of(sh.weights))} devices")
    per_dev = {d.id: {tuple(s.data.shape) for s in sh.weights[0].addressable_shards
                      if s.device == d} for d in devs}
    one = SNNStreamEngine(params_q, cfg, batch_size=LANES, chunk_steps=CHUNK,
                          seed=SEED, dispatch_cache=False)
    check(_devices_of(one.weights) == {jax.devices()[0]},
          f"{name}: single-device engine is not on device 0")
    want, _, _ = drive(one, imgs)
    compare_results(got, want, f"{name} mesh vs single device")
    log(f"phase {name}: mesh={dict(mesh.shape)} backend={sh.backend} "
        f"single_device_backend={one.backend} "
        f"layer0_weight_shard_per_device={sorted(per_dev.items())} "
        f"first_step_incl_compile_s={first:.2f} total_s={total:.2f} "
        f"requests={len(got)} "
        f"mean_steps={np.mean([r.steps for r in got.values()]):.2f} "
        f"spec_used={sh.stats['spec_used']} "
        f"spec_wasted={sh.stats['spec_wasted']} "
        f"bit_identical_to_single_device=True")


def four_chips(ds) -> None:
    check(len(jax.devices()) >= 4,
          f"--chips 4 needs four devices, JAX sees {len(jax.devices())}")
    imgs = (ds.x_test[:N_REQUESTS] * 255).astype(np.uint8)
    mesh_phase("paper 4x1 data mesh", quantized(SNN_CONFIG),
               SNN_CONFIG, make_2d_device_mesh(4, 1), "fused", imgs)
    mesh_phase("wide 1x4 model mesh", quantized(SNN_CONFIG_WIDE),
               SNN_CONFIG_WIDE, make_2d_device_mesh(1, 4), "fused", imgs)
    check(any(name == "_partial_kernel" for name, _ in _KERNELS),
          "the model mesh never launched the partial-contraction kernel")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the four-chip mesh phase")
    args = ap.parse_args(argv)

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU (JAX's first device is {dev.platform}); "
              f"this script only runs on the chip", file=sys.stderr)
        return 2
    cache_dir = enable_compile_cache()
    log(f"device: {dev.device_kind} x{len(jax.devices())}, jax "
        f"{jax.__version__}, compile cache {cache_dir}")
    ds = digits.make_dataset(seed=0)

    if args.chips == 4:
        four_chips(ds)
    else:
        single_chip(ds)

    names = sorted({name for name, _ in _KERNELS})
    interp = sorted({name for name, i in _KERNELS if i})
    check(not interp, f"kernels ran in interpret mode on the chip: {interp}")
    check("_stack_kernel" in names or args.chips == 4,
          "no fused stack kernel was launched")
    log(f"pallas kernels launched: {names} (interpret mode: none)")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
