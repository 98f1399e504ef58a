"""Jit'd public wrappers for the Pallas kernels.

Handles: CPU-vs-TPU dispatch (interpret mode on CPU so the whole framework
runs in this container), shape padding to tile multiples, density-based
masked/MXU dispatch for the spike matmul, and unpadding of results.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from ..core.telemetry import (ChunkTelemetry, MatmulTelemetry,
                              DEFAULT_SPIKE_DENSITY_THRESHOLD,
                              resolve_density_threshold, resolve_sparse_skip)
from . import fused_snn, lif_step, poisson_encode, spike_matmul

__all__ = ["poisson_encode_op", "lif_forward_op", "spike_matmul_op",
           "fused_snn_op", "fused_snn_stack_op", "partial_contraction_op",
           "validate_weight_codes",
           "SPIKE_DENSITY_THRESHOLD", "resolve_density_threshold"]

# Below this per-tile spike density the masked (event-driven) spike-matmul
# kernel wins over the MXU dot; the ``mode="auto"`` runtime dispatch in
# :func:`spike_matmul_op` branches on the *observed* density of the batch.
# Kept under its historical name for backward compatibility — it is now
# only the DEFAULT: the live value comes from ``SNNConfig``'s
# ``spike_density_threshold`` / the ``REPRO_SPIKE_DENSITY_THRESHOLD`` env
# override (``core.telemetry.resolve_density_threshold``), and the serving
# controller may retune it from observed traffic.
SPIKE_DENSITY_THRESHOLD = DEFAULT_SPIKE_DENSITY_THRESHOLD

# window-start sentinel for the carried peak-membrane accumulator: the
# first real membrane value always wins the max-fold
V_PEAK_INIT = jnp.iinfo(jnp.int32).min


def _use_interpret() -> bool:
    return jax.default_backend() != "tpu"


def validate_weight_codes(weights) -> None:
    """Raise if concrete weights fall outside the int8-packable range.

    Every Pallas kernel stores weights as two int8 planes (``hi = w >> 1``,
    ``lo = w & 1``) — the MXU's native operand type — exact only for the
    paper's signed 9-bit codes [-256, 255] (``core.snn.quantize_params``'
    output contract); a wider code would wrap the hi plane SILENTLY.
    Checked wherever the weights are concrete (engine construction,
    un-jitted ``snn_apply_int``/``snn_window_chunk`` calls);
    under a caller's jit the values are tracers and the contract is
    trusted.
    """
    for i, w in enumerate(weights):
        if isinstance(w, jax.core.Tracer):
            continue
        lo, hi = int(jnp.min(w)), int(jnp.max(w))
        if lo < -256 or hi > 255:
            raise ValueError(
                f"layer {i} weight codes span [{lo}, {hi}] — outside the "
                f"signed 9-bit range [-256, 255] the fused kernels' int8 "
                f"packing represents exactly (quantize_params' contract); "
                f"use the reference backend for wider codes")


# Trace-time env resolution of the tile-skip flag — the canonical rule
# lives in core.telemetry so the jnp telemetry mirrors resolve identically.
_resolve_sparse_skip = resolve_sparse_skip


def _pad_to(x: jax.Array, axis: int, mult: int, fill=0):
    size = x.shape[axis]
    pad = (-size) % mult
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths, constant_values=fill)


@partial(jax.jit, static_argnames=("num_steps", "interpret"))
def poisson_encode_op(pixels_u8: jax.Array, state_u32: jax.Array,
                      num_steps: int, *, interpret: bool | None = None):
    """Batched hardware-faithful Poisson encoding via the Pallas kernel."""
    interpret = _use_interpret() if interpret is None else interpret
    B, N = pixels_u8.shape
    bB, bN = poisson_encode.DEFAULT_BLOCK
    px = _pad_to(_pad_to(pixels_u8, 0, bB), 1, bN)
    st = _pad_to(_pad_to(state_u32, 0, bB), 1, bN)
    spikes, state = poisson_encode.poisson_encode_pallas(
        px, st, num_steps, interpret=interpret)
    return spikes[:, :B, :N], state[:B, :N]


@partial(jax.jit, static_argnames=(
    "decay_shift", "v_threshold", "v_rest", "v_min", "v_max",
    "active_pruning", "interpret"))
def lif_forward_op(spikes_t: jax.Array, w_q: jax.Array, *, decay_shift: int,
                   v_threshold: int, v_rest: int = 0,
                   v_min: int = -(1 << 20), v_max: int = (1 << 20) - 1,
                   active_pruning: bool = False,
                   interpret: bool | None = None):
    """Fused T-step LIF layer via the Pallas kernel. See lif_step.py.

    ``w_q`` holds signed 9-bit codes (the int8 packing contract of
    :func:`validate_weight_codes`)."""
    interpret = _use_interpret() if interpret is None else interpret
    T, B, n_in = spikes_t.shape
    n_out = w_q.shape[1]
    bB, bN = lif_step.DEFAULT_BLOCK
    lane = fused_snn.LANE
    s = _pad_to(_pad_to(spikes_t, 1, bB), 2, lane)
    w = fused_snn.pack_weights(_pad_to(_pad_to(w_q, 0, lane), 1, bN))
    spk, vtr, vfin = lif_step.lif_forward_pallas(
        s, w, decay_shift=decay_shift, v_threshold=v_threshold,
        v_rest=v_rest, v_min=v_min, v_max=v_max,
        active_pruning=active_pruning, interpret=interpret)
    return spk[:, :B, :n_out], vtr[:, :B, :n_out], vfin[:B, :n_out]


def partial_contraction_op(spikes: jax.Array, en: jax.Array,
                           w_q: jax.Array, *,
                           sparse_skip: bool | None = None,
                           interpret: bool | None = None):
    """One layer's Σ W·S against an output-column weight shard, via Pallas.

    The model-axis datapath's per-device contraction: ``spikes`` (B, n_in)
    bool is the FULL gathered input-spike vector, ``en`` (B, n_out_sh)
    bool and ``w_q`` (n_in, n_out_sh) cover only this device's
    output-neuron shard.  Pads batch to the launch block and both neuron
    axes to 128 (padded pixels never spike, padded neurons are disabled),
    packs the shard's weights into the two int8 planes per call, launches
    :func:`fused_snn.partial_contraction_pallas` and unpads.  Bit-exact
    equal to ``core.lif.synaptic_current_int(spikes, w_q)`` on the shard
    — integer accumulation, no rounding — which is what makes the model-
    sharded fused path == the jnp reference == the single-device kernel.

    Returns ``(current, skipped)``: (B, n_out_sh) int32 and the
    per-batch-block skipped-tile-pair counts (n_blocks,) int32 with
    exactly the geometry ``core.telemetry.layer_tile_skips`` mirrors for
    this shard's (B, n_in, n_out_sh) launch.  Designed to be called
    inside a caller's jit/scan/shard_map (no jit wrapper of its own).
    """
    interpret = _use_interpret() if interpret is None else interpret
    ss = _resolve_sparse_skip(sparse_skip)
    B = spikes.shape[0]
    n_out = w_q.shape[1]
    bB = fused_snn.block_b_for(B)
    x = _pad_to(_pad_to(spikes.astype(jnp.uint8), 0, bB), 1, fused_snn.LANE)
    e = _pad_to(_pad_to(en.astype(jnp.uint8), 0, bB), 1, fused_snn.LANE)
    wp = fused_snn.pack_weights(
        _pad_to(_pad_to(w_q, 0, fused_snn.LANE), 1, fused_snn.LANE))
    cur, skipped = fused_snn.partial_contraction_pallas(
        x, e, wp, sparse_skip=ss, block_b=bB, interpret=interpret)
    return cur[:B, :n_out], skipped


@partial(jax.jit, static_argnames=(
    "num_steps", "chunk_steps", "decay_shift", "v_threshold", "v_rest",
    "v_min", "v_max", "active_pruning", "patience", "readout",
    "sparse_skip", "streamed", "interpret", "block_b", "layers"))
def fused_snn_stack_op(pixels_u8: jax.Array, state_u32: jax.Array,
                       weights, *, num_steps: int, chunk_steps: int | None = None,
                       decay_shift: int, v_threshold: int, v_rest: int = 0,
                       v_min: int = -(1 << 20), v_max: int = (1 << 20) - 1,
                       active_pruning: bool = False, init: dict | None = None,
                       gate: dict | None = None, patience: int = 0,
                       readout: str = "count",
                       sparse_skip: bool | None = None,
                       streamed: bool = False,
                       interpret: bool | None = None,
                       block_b: int | None = None,
                       layers: tuple | None = None, packed=None):
    """Multi-layer encode→LIF stack in one resumable Pallas launch.

    Args:
      weights: tuple of per-layer (n_l, n_{l+1}) int16/int8 matrices
        holding the paper's signed 9-bit codes (range [-256, 255] — the
        ``core.snn.quantize_params`` contract; packing into the kernel's
        resident int8 planes is exact only on that range).
      num_steps: the full window length T (first-spike sentinel and, when
        gated, the per-lane step bound).
      chunk_steps: how many steps THIS launch executes (default: the whole
        window).  Carry ``init``/``gate`` between launches for bit-identical
        chunked execution.
      init: optional carried state dict with ``v``/``en`` (per-layer tuples,
        (B, n_l) i32 / bool), ``counts``/``first`` ((B, n_out) i32, first
        sentinel = num_steps) and ``steps`` ((B,) i32).  May also carry
        ``v_peak`` (per-layer (B, n_l) i32 running peak membranes);
        omitted, the peaks restart from the INT32_MIN sentinel.
      gate: optional per-lane stability-gate state (``active`` bool (B,),
        ``prev``/``streak`` i32 (B,)) — when given, the kernel runs the
        serving early-exit gate each step and freezes retired lanes.
      sparse_skip: event-driven tile skipping inside the kernel —
        bit-identical to dense execution either way (None = the
        REPRO_SPARSE_SKIP env default, on).
      streamed: keep the packed weight planes in HBM and double-buffer
        128-row slabs through VMEM scratch (the ``fused_streamed``
        backend for stacks over the residency budget).
      block_b: batch-block (MXU tile height) override for the launch
        grid — a tunable dispatch shape (the autotuner searches it).
        None derives the historical ``fused_snn.block_b_for(B)``
        heuristic.  Bit-identical for any valid value: blocking only
        changes launch geometry (and the telemetry tile-leaf shape that
        mirrors it), never the integer datapath.
      layers: the stack's descriptors (``core.layers``) when it has
        conv-pool layers, whose ``weights`` are (C_out, C_in, k, k) codes;
        None for a dense stack.  Pixels, PRNG lanes and conv-layer state
        stay flat here and are laid out as the kernel's maps per launch.
      packed: the operands of ``fused_snn.pack_stack(weights, layers)``,
        built once by the caller; None packs them in this launch.

    Returns a dict with ``spike_counts``/``first_spike_t``/``v_final``
    ((B, n_out) i32), ``v_trace`` ((chunk, B, n_out) i32), ``active_adds``
    ((chunk, B) i32, summed over layers), ``prng_state`` ((B, n_in) u32),
    the carried ``v``/``en``/``v_peak``/``steps`` state, ``telemetry``
    (a ``core.telemetry.ChunkTelemetry`` — the kernel's activity side
    channel) and (if gated) ``gate``.  The inter-layer spike tensors are
    never materialised.
    """
    interpret = _use_interpret() if interpret is None else interpret
    sparse_skip = _resolve_sparse_skip(sparse_skip)
    if chunk_steps is None:
        chunk_steps = num_steps
    B, n_in = pixels_u8.shape
    L = len(weights)
    geoms = fused_snn.stack_geometry(n_in, layers)
    if geoms is None:
        sizes = [n_in] + [w.shape[1] for w in weights]
    else:
        sizes = [n_in] + [d.neurons for d in layers]
    n_out = sizes[-1]
    if block_b is None:
        bB = fused_snn.block_b_for(B)
    else:
        bB = int(block_b)
        if bB < 8 or bB % 8:
            raise ValueError(
                f"block_b={block_b} is not a positive multiple of 8 (the "
                f"kernel's sublane granularity) — pass None for the "
                f"derived default")
    lane = fused_snn.LANE
    Bp = B + (-B) % bB

    # Zero-padded pixel/state lanes never spike (0 > r is false, and 0 is
    # the xorshift fixed point), so batch/input padding is invisible to the
    # datapath; padded neurons are masked out of the enable sets below so
    # they cannot fire and do not count toward the executed-add channel.
    aux = ()
    if geoms is None:
        px = _pad_to(_pad_to(pixels_u8, 0, bB), 1, lane)
        st = _pad_to(_pad_to(state_u32, 0, bB), 1, lane)
        ws = tuple(fused_snn.pack_weights(
            _pad_to(_pad_to(w, 0, lane), 1, lane)) for w in weights)
    else:
        img = geoms[0].in_map()
        px = fused_snn.to_map(_pad_to(pixels_u8, 0, bB), img, bB)
        st = fused_snn.to_map(_pad_to(state_u32, 0, bB), img, bB)
        if packed is None:
            packed = fused_snn.pack_stack(weights, layers)
        ws = tuple(p[0] for p in packed)
        aux = tuple(a for p in packed for a in p[1:])

    def is_map(l):
        return geoms is not None and isinstance(geoms[l],
                                                fused_snn.ConvGeom)

    def place(a, l, fill=0):
        """(Bp, n_l) flat rows -> layer l's kernel array."""
        if is_map(l):
            return fused_snn.to_map(a, geoms[l].out_map(), bB, fill)
        return _pad_to(a, 1, lane, fill)

    def unplace(a, l):
        if is_map(l):
            return fused_snn.from_map(a, geoms[l].out_map(), bB)[:B]
        return a[:B, :sizes[l + 1]]

    def valid_mask(n):
        # padded batch rows are disabled (padded neurons by ``place``) —
        # so the tile-skip predicates (and their telemetry mirror) see the
        # identical enable geometry whether the state is fresh or carried
        # (_pad_to pads carried enables with False rows)
        row = jnp.arange(Bp, dtype=jnp.int32)[:, None]
        return jnp.broadcast_to(row < B, (Bp, n))

    def fresh(l, value):
        return place(jnp.full((Bp, sizes[l + 1]), value, jnp.int32), l,
                     value)

    def vp_fresh():
        return tuple(fresh(l, V_PEAK_INIT) for l in range(L))

    if init is None:
        v_in = tuple(fresh(l, v_rest) for l in range(L))
        en_in = tuple(place(valid_mask(sizes[l + 1]), l)
                      for l in range(L))
        vp_in = vp_fresh()
        cnt_in = jnp.zeros((Bp, ws[-1].shape[2]), jnp.int32)
        first_in = jnp.full((Bp, ws[-1].shape[2]), num_steps, jnp.int32)
        steps_in = jnp.zeros((Bp, 1), jnp.int32)
    else:
        v_in = tuple(place(_pad_to(init["v"][l], 0, bB), l)
                     for l in range(L))
        en_in = tuple(place(_pad_to(init["en"][l].astype(bool), 0, bB), l)
                      for l in range(L))
        vp_in = (vp_fresh() if init.get("v_peak") is None else
                 tuple(place(_pad_to(init["v_peak"][l], 0, bB), l)
                       for l in range(L)))
        cnt_in = _pad_to(_pad_to(init["counts"], 0, bB), 1, lane)
        first_in = _pad_to(_pad_to(init["first"], 0, bB), 1, lane)
        steps_in = _pad_to(init["steps"].astype(jnp.int32)[:, None], 0, bB)
    en_in = tuple(e.astype(jnp.uint8) for e in en_in)

    gate_in = None
    if gate is not None:
        gate_in = (
            _pad_to(gate["active"].astype(jnp.int32)[:, None], 0, bB),
            _pad_to(gate["prev"].astype(jnp.int32)[:, None], 0, bB),
            _pad_to(gate["streak"].astype(jnp.int32)[:, None], 0, bB),
        )

    outs = fused_snn.fused_snn_stack_pallas(
        px, st, ws, v_in, en_in, vp_in, cnt_in, first_in, steps_in, gate_in,
        chunk_steps=chunk_steps, window_steps=num_steps,
        decay_shift=decay_shift, v_threshold=v_threshold, v_rest=v_rest,
        v_min=v_min, v_max=v_max, active_pruning=active_pruning,
        patience=patience, readout=readout, sparse_skip=sparse_skip,
        streamed=streamed, block_b=bB, interpret=interpret, aux=aux,
        geoms=geoms)
    (cnt, vtr, first, adds, st_out, v_fin, en_fin, vp_fin, tel,
     steps_out) = outs[:10]
    tspk, ten, ttile = tel
    if geoms is not None:
        st_out = fused_snn.from_map(st_out, geoms[0].in_map(), bB)
    res = {
        "spike_counts": cnt[:B, :n_out],
        "v_trace": vtr[:, :B, :n_out],
        "first_spike_t": first[:B, :n_out],
        "v_final": v_fin[-1][:B, :n_out],
        "active_adds": adds[:, :B],
        "prng_state": st_out[:B, :n_in],
        "v": tuple(unplace(v_fin[l], l) for l in range(L)),
        "en": tuple(unplace(en_fin[l], l).astype(bool) for l in range(L)),
        "v_peak": tuple(unplace(vp_fin[l], l) for l in range(L)),
        "telemetry": ChunkTelemetry(n_spk=tspk[:, :, :B],
                                    n_en=ten[:, :, :B],
                                    tiles_skipped=ttile),
        "steps": steps_out[:B, 0],
    }
    if gate is not None:
        act, prev, streak = outs[10]
        res["gate"] = {"active": act[:B, 0] != 0, "prev": prev[:B, 0],
                       "streak": streak[:B, 0]}
    return res


@partial(jax.jit, static_argnames=(
    "num_steps", "decay_shift", "v_threshold", "v_rest", "v_min", "v_max",
    "active_pruning", "sparse_skip", "streamed", "interpret"))
def fused_snn_op(pixels_u8: jax.Array, state_u32: jax.Array, w_q: jax.Array,
                 *, num_steps: int, decay_shift: int, v_threshold: int,
                 v_rest: int = 0, v_min: int = -(1 << 20),
                 v_max: int = (1 << 20) - 1, active_pruning: bool = False,
                 sparse_skip: bool | None = None, streamed: bool = False,
                 interpret: bool | None = None):
    """Single-layer whole-window convenience wrapper over the stack op.

    Returns a dict with ``spike_counts`` (B, N_out) i32, ``v_trace``
    (T, B, N_out) i32, ``first_spike_t`` (B, N_out) i32, ``v_final``
    (B, N_out) i32, ``active_adds`` (T, B) i32 and ``prng_state``
    (B, N_in) u32 — the (T, B, N_in) spike tensor is never materialised.
    """
    return fused_snn_stack_op(
        pixels_u8, state_u32, (w_q,), num_steps=num_steps,
        decay_shift=decay_shift, v_threshold=v_threshold, v_rest=v_rest,
        v_min=v_min, v_max=v_max, active_pruning=active_pruning,
        sparse_skip=sparse_skip, streamed=streamed, interpret=interpret)


def spike_matmul_op(spikes: jax.Array, w_q: jax.Array, *,
                    mode: str = "auto",
                    density_threshold: float | None = None,
                    with_telemetry: bool = False,
                    interpret: bool | None = None):
    """Event-driven spike×weight contraction.

    ``mode="auto"`` dispatches at RUNTIME on the observed spike density of
    the batch: a ``lax.cond`` picks the masked (event-driven) kernel below
    the dispatch threshold and the MXU dot above it.  Both kernels compute
    the identical int32 contraction (S ∈ {0,1} makes the masked add and
    the dot arithmetically the same), so the dispatch can never change
    results — only which datapath executes.  ``mode="masked"`` /
    ``mode="mxu"`` force one branch.

    ``density_threshold`` is the dispatch boundary: None resolves through
    config/env/default (``core.telemetry.resolve_density_threshold``) —
    the serving controller's retuned value
    (``SNNStreamEngine.dispatch_threshold``) arrives through this
    argument.  It enters the jitted computation as a TRACED scalar
    operand, not a static argument, so the controller walking it per
    chunk never recompiles.  ``with_telemetry=True`` additionally returns
    a ``core.telemetry.MatmulTelemetry`` (observed density + branch
    taken), the per-call twin of the fused kernel's chunk side channel.
    ``w_q`` holds signed 9-bit codes (checked here when concrete).
    """
    validate_weight_codes((w_q,))
    return _spike_matmul_impl(
        spikes, w_q,
        jnp.float32(resolve_density_threshold(density_threshold)),
        mode=mode, with_telemetry=with_telemetry, interpret=interpret)


@partial(jax.jit, static_argnames=("mode", "with_telemetry", "interpret"))
def _spike_matmul_impl(spikes: jax.Array, w_q: jax.Array,
                       threshold: jax.Array, *, mode: str,
                       with_telemetry: bool, interpret: bool | None):
    interpret = _use_interpret() if interpret is None else interpret
    B, n_in = spikes.shape
    n_out = w_q.shape[1]
    bB, bN, bK = spike_matmul.DEFAULT_BLOCK
    s = _pad_to(_pad_to(spikes, 0, bB), 1, bK)
    w = fused_snn.pack_weights(_pad_to(_pad_to(w_q, 0, bK), 1, bN))
    density = jnp.mean((spikes != 0).astype(jnp.float32))
    if mode == "auto":
        used_masked = density < threshold
        out = jax.lax.cond(
            used_masked,
            lambda s, w: spike_matmul.spike_matmul_pallas(
                s, w, mode="masked", interpret=interpret),
            lambda s, w: spike_matmul.spike_matmul_pallas(
                s, w, mode="mxu", interpret=interpret),
            s, w)
    else:
        used_masked = jnp.asarray(mode == "masked")
        out = spike_matmul.spike_matmul_pallas(s, w, mode=mode,
                                               interpret=interpret)
    out = out[:B, :n_out]
    if with_telemetry:
        return out, MatmulTelemetry(density=density, used_masked=used_masked)
    return out
