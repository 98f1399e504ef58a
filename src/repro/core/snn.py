"""The paper's SNN as a composable JAX module.

Network topology (paper §IV-A): Poisson encoder → fully-connected 784→10 LIF
layer → spike-register readout, over a T-timestep window.  The module
generalises to arbitrary layer stacks (hidden LIF layers) so the framework
can scale the idea, but the paper configuration is the single FC layer.

Three executables are exposed:

* :func:`snn_apply_float` — differentiable forward (surrogate gradients),
  used for BPTT training.  Optionally trains *through* fake-quantised weights
  (QAT) so the trained weights survive int8 conversion.
* :func:`snn_apply_int` — the bit-exact fixed-point inference engine
  (the actual reproduction target), including active pruning and the
  op-count/energy side channel.
* :func:`snn_loss` / :func:`snn_train_step` helpers for the training loop.

Weights layout: ``params = {"layers": [{"w": (n_in, n_out)}, ...]}``.
"""

from __future__ import annotations

import math
from dataclasses import dataclass, field
from typing import NamedTuple

import jax
import jax.numpy as jnp

from . import encoding, fixed_point, lif, pruning
from .layers import (ConvPool, conv_pool_adds, conv_pool_current,
                     dense_layers, has_conv, stack_sizes, validate_stack,
                     weight_shapes)
from .telemetry import (ChunkTelemetry, layer_tile_skips, resolve_sparse_skip)

__all__ = [
    "SNNConfig",
    "snn_init",
    "snn_apply_float",
    "snn_apply_int",
    "snn_loss",
    "quantize_params",
    "encode_lif_timestep",
    "snn_int_stack_step",
    "snn_int_stack_step_sharded",
    "resolve_backend",
    "fused_unsupported_reason",
    "readout_pred",
    "SNNWindowState",
    "snn_window_init",
    "snn_window_chunk",
]


@dataclass(frozen=True)
class SNNConfig:
    layer_sizes: tuple[int, ...] = (784, 10)   # paper: single FC 784→10
    num_steps: int = 20                        # simulation window (paper §IV-C)
    lif: lif.LIFConfig = field(default_factory=lif.LIFConfig)
    weight_bits: int = 8                       # paper: 8-bit codes (9 incl. sign ref)
    qat: bool = True                           # train through fake-quant
    surrogate_slope: float = 4.0
    readout: str = "count"                     # count|first_spike|membrane
    active_pruning: bool = False
    dot_impl: str = "int32"                    # int32 | f32 (bit-exact fast path)
    fuse_encoder: bool = False                 # PRNG+encode inside the LIF scan
    # Integer-engine backend: which realisation of the RTL datapath runs.
    #   fused          — one resumable Pallas launch for the whole
    #               encode→LIF window across the full layer stack, weights
    #               resident as int8-packed planes; neither the input nor
    #               any inter-layer spike tensor ever touches HBM (§V-B)
    #   fused_streamed — the same single launch for stacks OVER the VMEM
    #               residency budget: packed weights stay in HBM and a
    #               double-buffered DMA pipeline slabs them through a
    #               2-slot VMEM scratch, overlapped with the step loop
    #   staged    — Pallas encoder kernel + per-layer Pallas LIF kernel
    #               (every hop's spike train round-trips between launches)
    #   reference — pure-jnp scans (core.encoding / core.lif); the bit-exact
    #               oracle and the fast path on hosts without a TPU
    #   auto      — on TPU: fused for any stack that fits the residency
    #               budget, else fused_streamed when the streaming scratch
    #               fits, else staged; reference elsewhere (Pallas
    #               interpret mode is a correctness tool, not a fast CPU
    #               path)
    backend: str = "auto"
    # Event-driven tile skipping inside the fused kernels: zero-spike
    # K-tiles and fully-pruned output tiles skip the MXU pass entirely
    # (bit-identical either way — skipped tiles contribute exactly zero).
    # None defers to the REPRO_SPARSE_SKIP env default (on).
    sparse_skip: bool | None = None
    emit_trace: bool = True                    # False: no v/spike-train outputs
                                               # (prediction-only serving)
    # Float-threshold used during training; the int path scales it (below).
    train_threshold: float = 1.0
    # Layer descriptors (core.layers): None is the dense stack
    # ``layer_sizes``.  Given, they fix ``layer_sizes`` (the flat neuron
    # counts, input first: the first conv layer's map, or
    # ``layer_sizes[0]`` for a stack that starts dense); an all-dense list
    # normalises to None, so it builds the same config as ``layer_sizes``.
    layers: tuple | None = None

    def __post_init__(self):
        if self.layers is None:
            return
        layers = tuple(self.layers)
        first = layers[0] if layers else None
        n_in = (math.prod(first.in_shape) if isinstance(first, ConvPool)
                else self.layer_sizes[0])
        validate_stack(n_in, layers)
        object.__setattr__(self, "layer_sizes", stack_sizes(n_in, layers))
        object.__setattr__(self, "layers", layers if has_conv(layers)
                           else None)

    @property
    def layer_descs(self) -> tuple:
        """One descriptor per LIF layer (dense ones for a plain stack)."""
        return (self.layers if self.layers is not None
                else dense_layers(self.layer_sizes))

    @property
    def n_in(self) -> int:
        return self.layer_sizes[0]

    @property
    def n_classes(self) -> int:
        return self.layer_sizes[-1]


def snn_init(key: jax.Array, cfg: SNNConfig) -> dict:
    layers = []
    for shape in weight_shapes(cfg.n_in, cfg.layer_descs):
        key, sub = jax.random.split(key)
        # LeCun-style init scaled for spiking inputs (rate ≲ 0.5); a conv
        # kernel's fan-in is C_in * k * k.
        fan_in = math.prod(shape[1:]) if len(shape) == 4 else shape[0]
        w = jax.random.normal(sub, shape, jnp.float32)
        w = w * (2.0 / jnp.sqrt(fan_in))
        layers.append({"w": w})
    return {"layers": layers}


def _train_lif_cfg(cfg: SNNConfig) -> lif.LIFConfig:
    """Float-threshold LIF used in training (V_th=1.0 instead of 128)."""
    return lif.LIFConfig(
        decay_shift=cfg.lif.decay_shift,
        v_threshold=cfg.train_threshold,  # type: ignore[arg-type]
        v_rest=0,
    )


def snn_apply_float(params: dict, pixels01: jax.Array, key: jax.Array,
                    cfg: SNNConfig):
    """Differentiable forward. pixels01: (batch, n_in) in [0,1].

    Returns dict(rates=(batch, n_classes) mean firing rates,
                 spikes=(T, batch, n_classes)).
    """
    if cfg.layers is not None:
        raise ValueError("float training runs dense stacks only; a "
                         "conv-pool stack serves quantized codes")
    spikes = encoding.poisson_encode_jax(pixels01, key, cfg.num_steps)
    tcfg = _train_lif_cfg(cfg)
    for layer in params["layers"]:
        w = layer["w"]
        if cfg.qat:
            w = fixed_point.fake_quant(w, cfg.weight_bits)
        spikes, v_trace, _ = lif.run_lif_float(spikes, w, tcfg, cfg.surrogate_slope)
    rates = jnp.mean(spikes, axis=0)
    return {"rates": rates, "spikes": spikes, "v_trace": v_trace}


def quantize_params(params: dict, cfg: SNNConfig):
    """Float→fixed-point conversion for the integer engine.

    Scales weights so the float threshold (1.0) maps to the integer
    Threshold-Reg value (e.g. 128): w_q = round(w / s), s chosen per layer
    such that the *effective* threshold matches the RTL register.
    """
    out = []
    # Gain that maps the float threshold (1.0) onto the Threshold-Reg (128):
    # integer weight codes are w·gain, so Σ w_q·S crosses 128 exactly when the
    # float accumulator would cross 1.0 (up to rounding).
    gain = float(cfg.lif.v_threshold) / cfg.train_threshold
    # Paper §V-B: 9-bit signed weight codes (784×10×9 bits ≈ 8.6 KB).
    code_bits = cfg.weight_bits + 1
    qmin, qmax = -(1 << (code_bits - 1)), (1 << (code_bits - 1)) - 1
    for layer in params["layers"]:
        w = layer["w"]
        if cfg.qat:
            w = fixed_point.fake_quant(w, cfg.weight_bits)
        w_q = jnp.clip(jnp.round(w * gain), qmin, qmax).astype(jnp.int16)
        out.append({"w_q": w_q, "scale": jnp.float32(1.0 / gain)})
    return {"layers": out}


def fused_unsupported_reason(cfg: SNNConfig, n_layers: int,
                             layer_sizes: tuple[int, ...] | None = None,
                             trace_steps: int | None = None,
                             local_batch: int | None = None,
                             streamed: bool = False,
                             model_shards: int = 1,
                             block_b: int | None = None) -> str | None:
    """Why the fused megakernel cannot run this configuration (None = ok).

    The kernel handles arbitrary layer stacks, but it keeps every weight
    matrix (int8-packed, 2 bytes/weight) and per-layer state resident
    on-chip for the whole launch — a stack whose footprint exceeds the
    VMEM budget cannot run resident-fused.  With ``streamed=True`` the
    check is for the ``fused_streamed`` realisation instead: weights live
    in HBM and only the 2-slot DMA slab scratch plus the per-layer state
    must fit, so much wider/deeper stacks pass.  ``trace_steps`` is the
    per-launch membrane-trace length: the full window for one-shot
    execution (default), or ``chunk_steps`` for chunked/streaming callers,
    whose launches only ever allocate a chunk of trace.  ``local_batch``
    is the per-device batch tile: VMEM is a per-device resource, so a
    sharded caller (serve.ShardedSNNStreamEngine) validates against the
    launch one device actually executes — ``kernels.fused_snn.block_b_for``
    maps the local tile to the batch block that launch allocates (never
    derived from the global lane count).  ``model_shards`` scopes the
    check the same way along the neuron axis: on a ``model_shards``-way
    model mesh axis each device holds only an output-column shard of
    every layer that divides (``kernels.fused_snn.layer_shard_ways``), so
    feasibility is judged against the per-device shard footprint — how a
    WIDE stack that overflows single-device VMEM becomes resident-fused
    on a 4-way model axis.  ``block_b`` pins the batch block the launch
    will actually use (a tuned dispatch-cache shape) instead of the
    ``block_b_for(local_batch)`` heuristic — feasibility must be judged
    against the geometry the kernel really allocates.
    """
    from ..kernels import fused_snn
    if n_layers < 1:
        return "the network has no layers"
    if model_shards < 1:
        return f"model_shards={model_shards} is not a positive shard count"
    if cfg.layers is not None:
        if streamed:
            return ("conv-pool layers run only in the resident stack "
                    "kernel: fused_streamed streams dense weight slabs")
        if model_shards > 1:
            return (f"conv-pool layers cannot split over a "
                    f"{model_shards}-way model axis: a conv map's neurons "
                    f"share every input row")
    sizes = layer_sizes
    if sizes is None and len(cfg.layer_sizes) - 1 == n_layers:
        sizes = cfg.layer_sizes
    if sizes is None:
        return None                      # shapes unknown — assume it fits
    need = fused_snn.stack_vmem_bytes(
        sizes,
        (fused_snn.block_b_for(local_batch) if block_b is None
         else int(block_b)),
        cfg.num_steps if trace_steps is None else trace_steps,
        streamed=streamed, model_shards=model_shards, layers=cfg.layers)
    if need > fused_snn.VMEM_BUDGET_BYTES:
        kind = "streamed working set" if streamed else \
            "resident stack footprint"
        shard = (f" on a {model_shards}-way model axis"
                 if model_shards > 1 else "")
        return (f"{kind} ~{need / 2**20:.1f} MiB for "
                f"layer_sizes={tuple(sizes)}{shard} exceeds the "
                f"{fused_snn.VMEM_BUDGET_BYTES / 2**20:.0f} MiB VMEM "
                f"budget")
    return None


def resolve_backend(cfg: SNNConfig, backend: str | None = None,
                    n_layers: int = 1, *,
                    layer_sizes: tuple[int, ...] | None = None,
                    trace_steps: int | None = None,
                    local_batch: int | None = None,
                    model_shards: int = 1,
                    block_b: int | None = None,
                    dispatch_cache=None,
                    mesh_shape=(1,)) -> str:
    """Pick the integer-engine backend actually run on this host.

    ``auto`` resolves on TPU through the chain fused → fused_streamed →
    staged: the resident megakernel for any stack whose int8-packed
    footprint fits VMEM, the weight-streaming megakernel for oversized
    stacks whose DMA working set still fits, and the staged per-layer
    kernels only past that; elsewhere it resolves to the pure-jnp
    reference scans (Pallas interpret mode is far slower than XLA on CPU —
    it is a correctness tool, not a serving path).  Explicitly requesting
    ``fused`` (or ``fused_streamed``) for a configuration that realisation
    cannot run raises instead of silently degrading.  ``local_batch``
    scopes the VMEM feasibility check to one device's batch tile (see
    :func:`fused_unsupported_reason`) — data-parallel sharding never
    *shrinks* what fits, but the check must not be run against the global
    lane count either.  ``model_shards`` likewise scopes it to the
    per-device weight shard of a model mesh axis: a WIDE stack that
    resolves ``fused_streamed`` single-device resolves resident ``fused``
    on a 4-way model axis, because each device only keeps a quarter of
    every shardable layer on-chip.

    ``dispatch_cache`` (a ``repro.tune.DispatchCache``, a cache-file
    path, or ``None``) short-circuits an ``auto`` resolution: a cache
    hit for this config's fingerprint on this device kind carries the
    backend that feasibility-resolved during the tuned run, so the VMEM
    chain is consulted once at tuning time instead of recomputed at
    every startup.  A fused-family cached backend is still gated by one
    cheap feasibility check against the *cached* shapes (a mismatched
    or hand-edited cache must fall back to the normal chain, never
    crash); explicit backend requests ignore the cache entirely.
    ``block_b`` pins the tuned batch block for the feasibility math.
    """
    b = backend if backend is not None else cfg.backend
    on_tpu = jax.default_backend() == "tpu"

    if b == "auto" and dispatch_cache is not None:
        from ..tune.cache import decide_dispatch
        decision = decide_dispatch(dispatch_cache, cfg=cfg, backend="auto",
                                   mesh_shape=mesh_shape)
        if decision.hit:
            t = decision.tuned
            cached_ok = t.backend in ("staged", "reference") or (
                on_tpu and fused_unsupported_reason(
                    cfg, n_layers, layer_sizes, trace_steps,
                    t.lanes_per_device if local_batch is None
                    else local_batch,
                    streamed=(t.backend == "fused_streamed"),
                    model_shards=model_shards, block_b=t.block_b) is None)
            if cached_ok:
                return t.backend

    reason = fused_unsupported_reason(cfg, n_layers, layer_sizes,
                                      trace_steps, local_batch,
                                      model_shards=model_shards,
                                      block_b=block_b)

    def streamed_reason():
        return fused_unsupported_reason(cfg, n_layers, layer_sizes,
                                        trace_steps, local_batch,
                                        streamed=True,
                                        model_shards=model_shards,
                                        block_b=block_b)

    if b == "auto":
        if not on_tpu:
            b = "reference"
        elif reason is None:
            b = "fused"
        elif cfg.layers is not None:
            b = "reference"               # no staged or streamed conv path
        elif streamed_reason() is None:
            b = "fused_streamed"
        else:
            b = "staged"
    if b == "staged" and cfg.layers is not None:
        raise ValueError(
            "backend='staged' cannot run conv-pool layers: the staged "
            "kernels contract dense (n_in, n_out) weights — use 'fused' or "
            "'reference'")
    if b == "fused" and reason is not None:
        raise ValueError(
            f"backend='fused' was explicitly requested but the fused "
            f"megakernel does not support this configuration: {reason} — "
            f"use backend='fused_streamed' or 'staged'")
    if b == "fused_streamed":
        sreason = streamed_reason()
        if sreason is not None:
            raise ValueError(
                f"backend='fused_streamed' was explicitly requested but "
                f"even the weight-streaming megakernel cannot run this "
                f"configuration: {sreason} — use backend='staged'")
    if b not in ("fused", "fused_streamed", "staged", "reference"):
        raise ValueError(f"unknown SNN backend {b!r}")
    return b


def readout_pred(counts: jax.Array, first_t: jax.Array, v_final: jax.Array,
                 readout: str, num_steps: int,
                 v_trace: jax.Array | None = None,
                 v_peak: jax.Array | None = None, *, xp=jnp) -> jax.Array:
    """Per-lane prediction under the configured readout.

    The single source of truth shared by ``snn_apply_int``, the streaming
    engine's stability gate / harvest path, and (mirrored op-for-op) the
    gated fused kernel.  ``count``: spike-register argmax.  ``first_spike``:
    earliest-spiking class, membrane potential as the no-spike tiebreak.
    ``membrane``: peak-membrane readout — from the carried per-lane peak
    accumulator ``v_peak`` (the streaming form: max is associative, so the
    chunked running peak is bit-identical to the one-shot maximum) or,
    when only a trace is at hand, from ``max(v_trace)`` over time.

    ``xp`` is the array namespace: ``jnp`` (traced or on the device) or
    ``np`` for rows already in host memory, which the engine's harvest
    ranks without a device round trip.  Both give the same integers; the
    ``v_trace`` form is ``jnp`` only.
    """
    if readout == "count":
        return xp.argmax(counts, axis=-1)
    if readout == "membrane":
        if v_peak is not None:
            return xp.argmax(v_peak, axis=-1)
        return pruning.peak_membrane_readout(v_trace)
    # Two score tiers: any class that spiked outranks every membrane-only
    # class (spiked tier is additive, large + (T - first), so it cannot
    # overflow int32 for any realistic window — (T - first)·large would
    # wrap already at T = 128).
    large = xp.int32(1 << 24)
    score = xp.where(counts > 0, large + (num_steps - first_t),
                     xp.clip(v_final, -large + 1, large - 1))
    return xp.argmax(score, axis=-1)


def snn_apply_int(params_q: dict, pixels_u8: jax.Array, prng_state: jax.Array,
                  cfg: SNNConfig, *, backend: str | None = None):
    """Bit-exact fixed-point inference (the RTL-equivalent engine).

    All backends (see :class:`SNNConfig.backend`; ``backend`` here overrides
    the config) implement the identical integer datapath and produce
    bit-identical spike counts / traces for the same PRNG seeds.

    Args:
      params_q: from :func:`quantize_params`.
      pixels_u8: (batch, n_in) uint8.
      prng_state: (batch, n_in) uint32 xorshift lanes.

    Returns dict(pred, spike_counts, v_trace, v_final, active_adds,
                 input_spikes, first_spike_t, prng_state, v_peak,
                 telemetry).  ``input_spikes`` is None on the fused
    backend — the spike train intentionally never exists as a tensor
    there.  ``v_peak`` is the per-layer peak-membrane tuple;
    ``telemetry`` a ``core.telemetry.ChunkTelemetry`` — both produced
    bit-identically by every backend (the fused kernels emit them as
    kernel outputs, the jnp paths re-derive them), so the activity side
    channel is cross-checkable exactly like the datapath.  Both are None
    when ``cfg.emit_trace`` is off.
    """
    b = resolve_backend(cfg, backend, len(params_q["layers"]),
                        layer_sizes=_stack_sizes(params_q, cfg))
    if b in ("fused", "fused_streamed"):
        res = _apply_int_fused(params_q, pixels_u8, prng_state, cfg,
                               streamed=(b == "fused_streamed"))
    elif b == "staged":
        res = _apply_int_staged(params_q, pixels_u8, prng_state, cfg)
    else:
        res = _apply_int_reference(params_q, pixels_u8, prng_state, cfg)

    # NB: no non-array metadata in the result — callers jit this function.
    vp = res.get("v_peak")
    res["pred"] = readout_pred(res["spike_counts"], res["first_spike_t"],
                               res["v_final"], cfg.readout, cfg.num_steps,
                               v_trace=res["v_trace"],
                               v_peak=None if vp is None else vp[-1])
    return res


def _param_sizes(params_q: dict) -> tuple[int, ...]:
    return tuple([params_q["layers"][0]["w_q"].shape[0]]
                 + [l["w_q"].shape[1] for l in params_q["layers"]])


def _stack_sizes(params_q: dict, cfg: SNNConfig) -> tuple[int, ...]:
    """Flat neuron counts of the stack: from the weights of a dense stack
    (as ever), from the descriptors of a conv-pool one, whose weight
    shapes are checked against them."""
    if cfg.layers is None:
        return _param_sizes(params_q)
    check_weight_shapes(cfg, tuple(l["w_q"] for l in params_q["layers"]))
    return cfg.layer_sizes


def check_weight_shapes(cfg: SNNConfig, weights) -> None:
    """Raise unless ``weights`` have the shapes ``cfg``'s descriptors
    give (a conv-pool stack; a dense one reads its sizes off them)."""
    want = weight_shapes(cfg.n_in, cfg.layer_descs)
    got = tuple(tuple(w.shape) for w in weights)
    if got != want:
        raise ValueError(f"weights of shapes {got} do not fit the layers "
                         f"{cfg.layer_descs}: they need {want}")


def _apply_int_fused(params_q, pixels_u8, prng_state, cfg: SNNConfig, *,
                     streamed: bool = False):
    """Fused Pallas megakernel: the whole window, all layers, one launch
    (weights resident, or HBM-streamed when ``streamed``)."""
    from ..kernels import ops
    ops.validate_weight_codes(
        tuple(layer["w_q"] for layer in params_q["layers"]))
    k = ops.fused_snn_stack_op(
        pixels_u8, prng_state,
        tuple(layer["w_q"] for layer in params_q["layers"]),
        num_steps=cfg.num_steps, decay_shift=cfg.lif.decay_shift,
        v_threshold=cfg.lif.v_threshold, v_rest=cfg.lif.v_rest,
        v_min=cfg.lif.v_min, v_max=cfg.lif.v_max,
        active_pruning=cfg.active_pruning,
        sparse_skip=cfg.sparse_skip, streamed=streamed, layers=cfg.layers)
    return {
        "spike_counts": k["spike_counts"],
        "v_trace": k["v_trace"],
        "v_final": k["v_final"],
        "active_adds": k["active_adds"],
        "input_spikes": None,
        "first_spike_t": k["first_spike_t"],
        "prng_state": k["prng_state"],
        "v_peak": k["v_peak"],
        "telemetry": k["telemetry"],
    }


def _derive_stack_telemetry(layer_ins, layer_outs, layer_vtr,
                            cfg: SNNConfig):
    """Telemetry + peaks re-derived from the staged/reference spike trains.

    The jnp mirror of the fused kernel's side channel: per layer, a
    neuron is enabled at step t iff it has not fired before t (or pruning
    is off), the input-spike count is the layer's consumed activity, and
    the tile counter replays the launch-geometry skip predicates via
    ``core.telemetry.layer_tile_skips`` on the same spike/enable state —
    which is what makes telemetry bit-checkable across all four backends.
    Returns ``(ChunkTelemetry, v_peak tuple)``.
    """
    ss = resolve_sparse_skip(cfg.sparse_skip)
    n_spk_l, n_en_l, tiles_l, peaks = [], [], [], []
    for x, out, vtr in zip(layer_ins, layer_outs, layer_vtr):
        xb = x.astype(bool)
        if cfg.active_pruning:
            out_i = out.astype(jnp.int32)
            en = (jnp.cumsum(out_i, axis=0) - out_i) == 0   # (T, B, n_out)
        else:
            en = jnp.ones(out.shape, bool)
        n_spk_l.append(jnp.sum(xb.astype(jnp.int32), axis=-1))   # (T, B)
        n_en_l.append(jnp.sum(en.astype(jnp.int32), axis=-1))
        tiles_l.append(jax.vmap(
            lambda xt, et: layer_tile_skips(xt, et, sparse_skip=ss))(xb, en))
        peaks.append(jnp.max(vtr, axis=0))
    tel = ChunkTelemetry(n_spk=jnp.stack(n_spk_l, axis=1),
                         n_en=jnp.stack(n_en_l, axis=1),
                         tiles_skipped=jnp.stack(tiles_l, axis=1))
    return tel, tuple(peaks)


def _apply_int_staged(params_q, pixels_u8, prng_state, cfg: SNNConfig):
    """Staged Pallas kernels: encoder launch + one LIF launch per layer."""
    from ..kernels import ops
    if cfg.layers is not None:
        raise ValueError("the staged kernels cannot run conv-pool layers")
    ops.validate_weight_codes(
        tuple(layer["w_q"] for layer in params_q["layers"]))
    spikes, prng_next = ops.poisson_encode_op(
        pixels_u8, prng_state, cfg.num_steps)
    x = spikes
    layer_ins, layer_outs, layer_vtr = [], [], []
    for layer in params_q["layers"]:
        layer_ins.append(x)
        x, v_trace, v_final = ops.lif_forward_op(
            x, layer["w_q"], decay_shift=cfg.lif.decay_shift,
            v_threshold=cfg.lif.v_threshold, v_rest=cfg.lif.v_rest,
            v_min=cfg.lif.v_min, v_max=cfg.lif.v_max,
            active_pruning=cfg.active_pruning)
        layer_outs.append(x)
        layer_vtr.append(v_trace)
    # Energy + activity side channels, re-derived from the spike streams
    # (the fused kernel's counters, double-entry style): telemetry adds
    # summed over layers ARE the executed-add channel.
    telemetry, v_peak = _derive_stack_telemetry(layer_ins, layer_outs,
                                                layer_vtr, cfg)
    adds = jnp.sum(telemetry.adds, axis=1)                     # (T, B)
    out_spikes = x
    counts = jnp.sum(out_spikes.astype(jnp.int32), axis=0)
    t_idx = jnp.arange(cfg.num_steps, dtype=jnp.int32)[:, None, None]
    first_t = jnp.min(jnp.where(out_spikes, t_idx, cfg.num_steps), axis=0)
    return {
        "spike_counts": counts,
        "v_trace": v_trace,
        "v_final": v_final,
        "active_adds": adds,
        "input_spikes": spikes,
        "first_spike_t": first_t,
        "prng_state": prng_next,
        "v_peak": v_peak,
        "telemetry": telemetry,
    }


def _apply_int_reference(params_q, pixels_u8, prng_state, cfg: SNNConfig):
    """Pure-jnp scans (the original engine), incl. the fuse_encoder path."""
    if cfg.layers is not None:
        return _apply_int_reference_conv(params_q, pixels_u8, prng_state,
                                         cfg)
    if cfg.fuse_encoder and len(params_q["layers"]) == 1:
        # single fused scan: xorshift -> compare -> ΣW·S -> LIF, per step —
        # the (T, B, n_in) spike train never round-trips through memory
        # (§Perf; exactly what the RTL datapath does cycle by cycle).
        res, prng_next = _fused_encode_lif(
            params_q["layers"][0]["w_q"], pixels_u8, prng_state, cfg)
        spikes = res["input_spikes"]
        adds = res["active_adds"]
        layer_ins = [spikes]
        layer_outs = [res["spikes"]]
        layer_vtr = [res["v_trace"]]
    else:
        spikes, prng_next = encoding.poisson_encode_hw(
            pixels_u8, prng_state, cfg.num_steps)
        res = None
        adds = 0
        x = spikes
        layer_ins, layer_outs, layer_vtr = [], [], []
        for layer in params_q["layers"]:
            layer_ins.append(x)
            res = lif.run_lif_int(x, layer["w_q"], cfg.lif,
                                  active_pruning=cfg.active_pruning,
                                  dot_impl=cfg.dot_impl)
            # executed adds summed over layers (fused-kernel counter parity)
            adds = adds + res["active_adds"]
            x = res["spikes"]
            layer_outs.append(x)
            layer_vtr.append(res["v_trace"])

    if layer_vtr[0] is not None:
        telemetry, v_peak = _derive_stack_telemetry(layer_ins, layer_outs,
                                                    layer_vtr, cfg)
    else:                                  # emit_trace=False serving mode
        telemetry, v_peak = None, None
    out_spikes = res["spikes"]                       # (T, batch, n_out)
    counts = jnp.sum(out_spikes.astype(jnp.int32), axis=0)
    T = cfg.num_steps
    t_idx = jnp.arange(T, dtype=jnp.int32)[:, None, None]
    first_t = jnp.min(jnp.where(out_spikes, t_idx, T), axis=0)
    return {
        "spike_counts": counts,
        "v_trace": res["v_trace"],
        "v_final": res["state"].v,
        "active_adds": adds,
        "input_spikes": spikes,
        "first_spike_t": first_t,
        "prng_state": prng_next,
        "v_peak": v_peak,
        "telemetry": telemetry,
    }


def _apply_int_reference_conv(params_q, pixels_u8, prng_state,
                              cfg: SNNConfig):
    """The reference of a conv-pool stack: one whole-window chunk of the
    jnp stack step from a fresh window (``snn_window_chunk``)."""
    st, chunk = snn_window_chunk(
        params_q, pixels_u8, snn_window_init(params_q, prng_state, cfg),
        cfg, chunk_steps=cfg.num_steps, backend="reference")
    spikes, _ = encoding.poisson_encode_hw(pixels_u8, prng_state,
                                           cfg.num_steps)
    return {
        "spike_counts": st.counts,
        "v_trace": chunk["v_trace"],
        "v_final": st.v[-1],
        "active_adds": chunk["active_adds"],
        "input_spikes": spikes,
        "first_spike_t": st.first,
        "prng_state": st.rng,
        "v_peak": st.v_peak,
        "telemetry": chunk["telemetry"],
    }


def layer_lif_step(x: jax.Array, state: lif.LIFStateInt, w_q: jax.Array,
                   layer, lif_cfg: lif.LIFConfig, *, dot_impl: str = "int32",
                   active_pruning: bool = False):
    """One LIF layer's timestep from the previous layer's spikes ``x``:
    Σ W·S (a conv-pool layer: :func:`conv_pool_current`) → integrate/leak/
    fire/reset → pruning gate.  ``layer`` is the layer's descriptor (None
    or ``Dense``: dense codes).  Returns (new_state, fired)."""
    if isinstance(layer, ConvPool):
        current = conv_pool_current(x, w_q, layer, dot_impl)
    else:
        current = lif.synaptic_current_int(x, w_q, dot_impl)
    current = jnp.where(state.enable, current, 0)
    new_state, fired = lif.lif_step_int(state, current, lif_cfg)
    if active_pruning:
        new_state = new_state._replace(
            enable=jnp.logical_and(new_state.enable,
                                   jnp.logical_not(fired)))
    return new_state, fired


def encode_lif_timestep(rng: jax.Array, pixels_u8: jax.Array,
                        state: lif.LIFStateInt, w_q: jax.Array,
                        lif_cfg: lif.LIFConfig, *, dot_impl: str = "int32",
                        active_pruning: bool = False):
    """One fused encoder+LIF timestep: PRNG step → spike compare →
    :func:`layer_lif_step` of a dense layer.

    The per-step datapath of the jnp fused scan below; the streaming
    engine's window chunk (:func:`snn_int_stack_step`) runs the same
    encoder and layer step, and both must stay bit-identical to the
    staged pipeline.  Returns (rng, new_state, fired, input_spikes).
    """
    from . import prng as prng_mod
    rng = prng_mod.xorshift32_step(rng)
    s_t = pixels_u8 > prng_mod.uniform_u8(rng)
    new_state, fired = layer_lif_step(s_t, state, w_q, None, lif_cfg,
                                      dot_impl=dot_impl,
                                      active_pruning=active_pruning)
    return rng, new_state, fired, s_t


def _fused_encode_lif(w_q: jax.Array, pixels_u8: jax.Array,
                      prng_state: jax.Array, cfg: SNNConfig):
    """One scan per timestep: PRNG step, spike compare, synaptic sum, LIF
    update.  Bit-identical to the unfused pipeline (same op order)."""
    batch_shape = pixels_u8.shape[:-1]
    n_out = w_q.shape[-1]
    state0 = lif.init_state_int(batch_shape + (n_out,), cfg.lif)

    def body(carry, _):
        rng, state = carry
        rng, new_state, fired, s_t = encode_lif_timestep(
            rng, pixels_u8, state, w_q, cfg.lif, dot_impl=cfg.dot_impl,
            active_pruning=cfg.active_pruning)
        n_spk = jnp.sum(s_t.astype(jnp.int32), axis=-1)
        n_en = jnp.sum(state.enable.astype(jnp.int32), axis=-1)
        ys = (fired, new_state.v, n_spk * n_en, s_t) if cfg.emit_trace \
            else (fired,)
        return (rng, new_state), ys

    (rng_f, state_f), ys = jax.lax.scan(
        body, (prng_state, state0), None, length=cfg.num_steps)
    if cfg.emit_trace:
        spk, vtr, adds, s_all = ys
    else:
        (spk,), vtr, adds, s_all = ys, None, None, None
    res = {"spikes": spk, "v_trace": vtr, "state": state_f,
           "active_adds": adds, "n_in": w_q.shape[0], "input_spikes": s_all}
    return res, rng_f


def snn_int_stack_step(rng: jax.Array, pixels_u8: jax.Array,
                       states: tuple, weights: tuple,
                       lif_cfg: lif.LIFConfig, *, dot_impl: str = "int32",
                       active_pruning: bool = False,
                       sparse_skip: bool | None = None,
                       layers: tuple | None = None):
    """One fused timestep through the WHOLE layer stack.

    The encoder, then :func:`layer_lif_step` per layer, each fired vector
    fed straight into the next Σ W·S — the jnp mirror of the multi-layer
    megakernel's static layer loop.  Returns ``(rng, new_states, fired_out, adds, tel)`` where
    ``adds`` is the executed-add count summed over layers (energy side
    channel) and ``tel`` is this step's telemetry row — ``n_spk``/``n_en``
    (L, B) i32 and ``tiles`` (L, n_blocks) i32, the jnp mirror of the
    megakernel's side channel (``sparse_skip`` resolves the same
    REPRO_SPARSE_SKIP env rule, so the tile counter matches the kernel's
    under the CI forcing).  ``layers`` (``SNNConfig.layers``) runs a
    stack with conv-pool layers (flat (C, H, W) neuron rows): such a layer
    takes its adds from ``conv_pool_adds`` and its tile mirror at the
    kernel's conv geometry.
    """
    from . import prng as prng_mod
    from ..kernels.fused_snn import stack_geometry
    ss = resolve_sparse_skip(sparse_skip)
    descs = layers if layers is not None else (None,) * len(weights)
    geoms = (stack_geometry(pixels_u8.shape[-1], layers)
             or (None,) * len(weights))
    rng = prng_mod.xorshift32_step(rng)
    x = pixels_u8 > prng_mod.uniform_u8(rng)
    n_spk, n_en, tiles, new_states = [], [], [], []
    adds = jnp.zeros(pixels_u8.shape[:-1], jnp.int32)
    for st, w, d, g in zip(states, weights, descs, geoms):
        n_spk.append(jnp.sum(x.astype(jnp.int32), axis=-1))
        n_en.append(jnp.sum(st.enable.astype(jnp.int32), axis=-1))
        tiles.append(layer_tile_skips(x, st.enable, sparse_skip=ss, geom=g))
        if isinstance(d, ConvPool):
            adds = adds + conv_pool_adds(x, st.enable, d)
        else:
            adds = adds + n_spk[-1] * n_en[-1]
        new_st, x = layer_lif_step(x, st, w, d, lif_cfg, dot_impl=dot_impl,
                                   active_pruning=active_pruning)
        new_states.append(new_st)
    tel = {"n_spk": jnp.stack(n_spk), "n_en": jnp.stack(n_en),
           "tiles": jnp.stack(tiles)}
    return rng, tuple(new_states), x, adds, tel


def snn_int_stack_step_sharded(rng: jax.Array, pixels_u8: jax.Array,
                               states: tuple, weights: tuple,
                               lif_cfg: lif.LIFConfig, *,
                               model_axis: str, ways: tuple[int, ...],
                               dot_impl: str = "int32",
                               active_pruning: bool = False,
                               sparse_skip: bool | None = None,
                               contraction: str = "jnp",
                               interpret: bool | None = None):
    """One stack timestep on a model mesh axis — the sharded twin of
    :func:`snn_int_stack_step`, to be traced inside ``shard_map``.

    Layer state, pixels and PRNG lanes arrive FULL (replicated over
    ``model_axis`` — the ``LaneState`` checkpoint stays placement-
    independent); each ``weights[l]`` is the device-LOCAL view: the
    output-column shard for layers ``ways[l] > 1``
    (``kernels.fused_snn.layer_shard_ways``), the whole matrix for
    layers that replicate.  Per sharded layer the device slices its own
    membrane/enable columns (``jax.lax.axis_index``), runs the partial
    Σ W·S of the full input-spike vector against its weight shard —
    ``contraction="pallas"`` launches
    ``kernels.ops.partial_contraction_op``, ``"jnp"`` the reference
    integer dot, bit-identical either way — steps LIF on the shard
    (elementwise, so the shard of the update == the update of the
    shard), then ``jax.lax.all_gather``s the fired/membrane shards back
    to full along the neuron axis.  Disjoint column shards in
    axis-index order concatenate to exactly the single-device integer
    contraction, so every derived quantity (pruning, counts, gate,
    telemetry) is computed on full arrays redundantly by every model
    peer and stays bit-identical to :func:`snn_int_stack_step`.
    Replicated layers skip the exchange entirely.

    Returns ``(rng, new_states, fired_out, adds, tel)`` exactly like the
    unsharded step; the ``tiles`` telemetry row covers THIS device's
    contraction geometry (its shard's skipped tile pairs), which the
    model-sharded chunk concatenates on the block axis.
    """
    from . import prng as prng_mod
    from ..kernels import ops as kops
    ss = resolve_sparse_skip(sparse_skip)
    rng = prng_mod.xorshift32_step(rng)
    x = pixels_u8 > prng_mod.uniform_u8(rng)

    def contract(spikes, en, w_loc):
        if contraction == "pallas":
            return kops.partial_contraction_op(
                spikes, en, w_loc, sparse_skip=ss, interpret=interpret)
        cur = lif.synaptic_current_int(spikes, w_loc, dot_impl)
        return cur, layer_tile_skips(spikes, en, sparse_skip=ss)

    n_spk, n_en, tiles, new_states = [], [], [], []
    adds = jnp.zeros(pixels_u8.shape[:-1], jnp.int32)
    for st, w_loc, w_ways in zip(states, weights, ways):
        n_spk.append(jnp.sum(x.astype(jnp.int32), axis=-1))
        n_en.append(jnp.sum(st.enable.astype(jnp.int32), axis=-1))
        adds = adds + n_spk[-1] * n_en[-1]
        if w_ways == 1:
            current, skipped = contract(x, st.enable, w_loc)
            tiles.append(skipped)
            current = jnp.where(st.enable, current, 0)
            new_st, fired = lif.lif_step_int(st, current, lif_cfg)
        else:
            shard_n = w_loc.shape[1]
            off = jax.lax.axis_index(model_axis) * shard_n
            v_sh = jax.lax.dynamic_slice_in_dim(st.v, off, shard_n, axis=-1)
            en_sh = jax.lax.dynamic_slice_in_dim(st.enable, off, shard_n,
                                                 axis=-1)
            current_sh, skipped = contract(x, en_sh, w_loc)
            tiles.append(skipped)
            current_sh = jnp.where(en_sh, current_sh, 0)
            new_sh, fired_sh = lif.lif_step_int(
                lif.LIFStateInt(v=v_sh, enable=en_sh), current_sh, lif_cfg)
            # spike exchange: every model peer recovers the full fired
            # vector (next layer's input) and membrane row, shards
            # concatenating in axis-index order == the weight slicing
            v_full = jax.lax.all_gather(new_sh.v, model_axis, axis=-1,
                                        tiled=True)
            fired = jax.lax.all_gather(fired_sh, model_axis, axis=-1,
                                       tiled=True)
            new_st = lif.LIFStateInt(v=v_full, enable=st.enable)
        if active_pruning:
            new_st = new_st._replace(
                enable=jnp.logical_and(new_st.enable,
                                       jnp.logical_not(fired)))
        new_states.append(new_st)
        x = fired
    tel = {"n_spk": jnp.stack(n_spk), "n_en": jnp.stack(n_en),
           "tiles": jnp.stack(tiles)}
    return rng, tuple(new_states), x, adds, tel


class SNNWindowState(NamedTuple):
    """Resumable mid-window state of the integer engine (a pytree).

    Carried between :func:`snn_window_chunk` calls so a T-step window can be
    executed in chunks with results bit-identical to one shot — the
    device-side contract behind the streaming engine.
    """

    rng: jax.Array          # (B, n_in) uint32 xorshift lanes
    v: tuple                # per-layer (B, n_l) int32 membranes
    en: tuple               # per-layer (B, n_l) bool clock-gates
    v_peak: tuple           # per-layer (B, n_l) int32 running peak membranes
    counts: jax.Array       # (B, n_out) int32 final-layer spike registers
    first: jax.Array        # (B, n_out) int32, sentinel = cfg.num_steps
    steps: jax.Array        # (B,) int32 window steps executed


def snn_window_init(params_q: dict, prng_state: jax.Array,
                    cfg: SNNConfig) -> SNNWindowState:
    """Fresh start-of-window state for a batch of ``prng_state.shape[0]``."""
    batch = prng_state.shape[0]
    sizes = _stack_sizes(params_q, cfg)
    return SNNWindowState(
        rng=prng_state,
        v=tuple(jnp.full((batch, n), cfg.lif.v_rest, jnp.int32)
                for n in sizes[1:]),
        en=tuple(jnp.ones((batch, n), bool) for n in sizes[1:]),
        v_peak=tuple(jnp.full((batch, n), jnp.iinfo(jnp.int32).min,
                              jnp.int32) for n in sizes[1:]),
        counts=jnp.zeros((batch, sizes[-1]), jnp.int32),
        first=jnp.full((batch, sizes[-1]), cfg.num_steps, jnp.int32),
        steps=jnp.zeros((batch,), jnp.int32),
    )


def snn_window_chunk(params_q: dict, pixels_u8: jax.Array,
                     state: SNNWindowState, cfg: SNNConfig, *,
                     chunk_steps: int, backend: str | None = None):
    """Advance the window by ``chunk_steps`` steps with carried state.

    Dispatches to the resumable fused megakernel (resident or
    weight-streamed) or the pure-jnp reference scan (all bit-identical;
    the staged kernels cannot resume mid-window — requesting them
    explicitly raises, and an ``auto`` resolution that lands on staged —
    a stack too large even for weight streaming on TPU — falls back to
    the chunk-capable reference scan).  Returns ``(new_state, chunk)``
    where ``chunk`` holds the per-step ``v_trace`` (chunk, B, n_out),
    ``active_adds`` (chunk, B) and ``telemetry``
    (``core.telemetry.ChunkTelemetry``) for this segment — concatenated
    over any split of the window, all three are bit-identical to the
    one-shot record, on every chunk-capable backend.
    """
    weights = tuple(layer["w_q"] for layer in params_q["layers"])
    requested = backend if backend is not None else cfg.backend
    if requested == "staged":
        raise ValueError("chunked window execution supports the 'fused', "
                         "'fused_streamed' and 'reference' backends only "
                         "(the staged kernels cannot resume mid-window)")
    b = resolve_backend(cfg, backend, len(weights),
                        layer_sizes=_stack_sizes(params_q, cfg),
                        trace_steps=chunk_steps)
    if b == "staged":                      # auto picked it; we can't run it
        b = "reference"
    if b in ("fused", "fused_streamed"):
        from ..kernels import ops
        ops.validate_weight_codes(weights)
        k = ops.fused_snn_stack_op(
            pixels_u8, state.rng, weights, num_steps=cfg.num_steps,
            chunk_steps=chunk_steps, decay_shift=cfg.lif.decay_shift,
            v_threshold=cfg.lif.v_threshold, v_rest=cfg.lif.v_rest,
            v_min=cfg.lif.v_min, v_max=cfg.lif.v_max,
            active_pruning=cfg.active_pruning,
            sparse_skip=cfg.sparse_skip,
            streamed=(b == "fused_streamed"), layers=cfg.layers,
            init={"v": state.v, "en": state.en, "v_peak": state.v_peak,
                  "counts": state.counts, "first": state.first,
                  "steps": state.steps})
        new_state = SNNWindowState(
            rng=k["prng_state"], v=k["v"], en=k["en"], v_peak=k["v_peak"],
            counts=k["spike_counts"], first=k["first_spike_t"],
            steps=k["steps"])
        return new_state, {"v_trace": k["v_trace"],
                           "active_adds": k["active_adds"],
                           "telemetry": k["telemetry"]}

    def body(carry, _):
        st = carry
        layer_states = tuple(lif.LIFStateInt(v=v, enable=e)
                             for v, e in zip(st.v, st.en))
        rng, new_states, fired, adds, tel = snn_int_stack_step(
            st.rng, pixels_u8, layer_states, weights, cfg.lif,
            dot_impl=cfg.dot_impl, active_pruning=cfg.active_pruning,
            sparse_skip=cfg.sparse_skip, layers=cfg.layers)
        counts = st.counts + fired.astype(jnp.int32)
        first = jnp.where(
            jnp.logical_and(fired, st.first == cfg.num_steps),
            st.steps[:, None], st.first)
        new = SNNWindowState(
            rng=rng,
            v=tuple(s.v for s in new_states),
            en=tuple(s.enable for s in new_states),
            v_peak=tuple(jnp.maximum(p, s.v)
                         for p, s in zip(st.v_peak, new_states)),
            counts=counts, first=first, steps=st.steps + 1)
        return new, (new_states[-1].v, adds, tel["n_spk"], tel["n_en"],
                     tel["tiles"])

    new_state, (vtr, adds, tspk, ten, ttile) = jax.lax.scan(
        body, state, None, length=chunk_steps)
    return new_state, {"v_trace": vtr, "active_adds": adds,
                       "telemetry": ChunkTelemetry(
                           n_spk=tspk, n_en=ten, tiles_skipped=ttile)}


def snn_loss(params: dict, pixels01: jax.Array, labels: jax.Array,
             key: jax.Array, cfg: SNNConfig):
    """Rate-coded cross-entropy: softmax over time-summed spike counts.

    A small L2 on rates discourages saturation (all-neurons-always-fire).
    """
    out = snn_apply_float(params, pixels01, key, cfg)
    # counts in [0, T] -> logits; scale keeps softmax in a sane range.
    logits = out["rates"] * float(cfg.num_steps) * 0.5
    logp = jax.nn.log_softmax(logits, axis=-1)
    nll = -jnp.take_along_axis(logp, labels[:, None], axis=-1).mean()
    reg = 1e-3 * jnp.mean(out["rates"] ** 2)
    acc = jnp.mean((jnp.argmax(logits, -1) == labels).astype(jnp.float32))
    return nll + reg, {"loss": nll, "acc": acc}
