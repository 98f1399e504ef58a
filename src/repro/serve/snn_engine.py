"""Batched streaming SNN serving engine (paper §IV-C at the request level).

The RTL classifies one image per window.  A TPU serving deployment instead
packs many requests into one batch tile and streams them through the
integer datapath together.  This engine adds the two scheduling ideas that
make that efficient under heavy traffic:

  * **Early exit** — a lane whose running prediction has been stable for
    ``patience`` consecutive steps retires before the window ends (the
    request-level analogue of active pruning; pure gate from
    serve.early_exit, evaluated *inside* the device-side window chunk so a
    lane stops burning adds the step it retires, not at the next host
    sync).
  * **Lane compaction** — at chunk boundaries, retired lanes are compacted
    out of the batch tile and the freed slots admit queued images, so a
    long-running image never blocks throughput (continuous batching).

The window chunk dispatches through the integer engine's backends
(core.snn): on TPU the **resumable fused megakernel** advances every lane
``chunk_steps`` steps in one Pallas launch — layer weights stay resident,
inter-layer spikes never touch HBM, and the stability gate runs inside the
kernel so per-step retirement semantics are preserved bit-for-bit.  On
hosts without a TPU the same datapath runs as a pure-jnp scan over
``core.snn.snn_int_stack_step`` (the reference backend) — both paths
produce identical lane-state evolution for the same seeds.

The per-lane executed-add counter is the same energy side channel the
paper integrates (§V): a retired lane's counter is frozen, which is the
measurable "sleep sooner" win.

Every chunk also returns the structured **telemetry side channel**
(``core.telemetry.ChunkTelemetry`` — per-step/layer spike counts, prune
occupancy, skipped MXU tiles), produced bit-identically by the fused
kernels and the jnp fallback.  The engines feed it to a
``serve.telemetry.TelemetryController``: frozen by default (static
chunk length, zero readbacks), or adaptive (``REPRO_ADAPTIVE_DISPATCH=1``
/ an explicit ``AdaptiveDispatchConfig``), where the observed retirement
rate picks the next chunk length.  Adaptivity is value-neutral: chunk
splits are bit-identical by construction, so only wall-clock moves.

Readouts: all three stream — ``count`` (spike-register argmax),
``first_spike`` (earliest spiking class, membrane tiebreak — the
active-pruning config's readout) and ``membrane`` (peak-membrane argmax:
the per-layer running peak is carried in ``LaneState.v_peak``, so no
per-step trace ever crosses the chunk boundary).

:class:`ShardedSNNStreamEngine` scales the same engine across a device
mesh: the lane tile is data-parallel (one contiguous slot block per
device, weights replicated) and the chunk runs under ``shard_map`` —
bit-identical to single-device serving because every op here is per-lane.
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from functools import cached_property, partial
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from jax.sharding import AxisType, Mesh, NamedSharding, PartitionSpec as P

from ..core import lif as lif_mod
from ..core import prng as prng_mod
from ..core.layers import ConvPool, dense_layers, weight_shapes
from ..core.snn import (SNNConfig, check_weight_shapes, readout_pred,
                        snn_int_stack_step, snn_int_stack_step_sharded)
from ..core.telemetry import (ChunkTelemetry, EngineLoad,
                              telemetry_partition_specs, tiles_total)
from .early_exit import StabilityGateState, stability_specs, stability_step
from .faults import (DeviceLostFault, DispatchFault, EngineFailure,
                     EngineHealthState, FaultInjector, FaultToleranceConfig,
                     PoisonDispatchError, injector_from_env, telemetry_ok)
from .rollout import WeightBank, merge_version_chunks
from . import spans
from .telemetry import AdaptiveDispatchConfig, make_controller, \
    summarize_chunk

__all__ = ["SNNStreamEngine", "ShardedSNNStreamEngine", "LaneState",
           "LaneTileCodec", "RequestResult", "stream_chunk",
           "lane_partition_specs", "weight_partition_specs",
           "make_sharded_stream_chunk"]

_V_PEAK_INIT = np.iinfo(np.int32).min   # window-start peak sentinel


class LaneState(NamedTuple):
    """Device-side state of one batch tile (all arrays leading dim B)."""

    px: jax.Array          # (B, n_in) uint8 pixels
    rng: jax.Array         # (B, n_in) uint32 xorshift lanes
    v: tuple               # per-layer (B, n_l) int32 membrane accumulators
    en: tuple              # per-layer (B, n_l) bool neuron clock-gates
    v_peak: tuple          # per-layer (B, n_l) int32 running peak membranes
    counts: jax.Array      # (B, n_out) int32 spike registers
    first: jax.Array       # (B, n_out) int32 first-spike latch (sentinel=T)
    gate_prev: jax.Array   # (B,) int32 stability-gate memory
    gate_streak: jax.Array  # (B,) int32
    steps: jax.Array       # (B,) int32 window steps executed
    adds: jax.Array        # (B,) int32 executed synaptic adds (energy)
    active: jax.Array      # (B,) bool — lane still consuming compute
    weight_version: jax.Array  # (B,) int32 admission-time WeightBank tag


class ConvStackWeights(NamedTuple):
    """Placed weights of a stack with conv-pool layers: the codes (the
    jnp reference runs on them) and, where a fused backend may run, the
    kernel's operands (``kernels.fused_snn.pack_stack``), built once per
    weight version instead of at every launch."""

    raw: tuple
    packed: tuple | None


@dataclass
class RequestResult:
    request_id: int
    pred: int
    spike_counts: np.ndarray
    steps: int             # window steps actually consumed
    adds: int              # synaptic adds executed (energy side channel)
    early_exit: bool       # retired by the stability gate before T
    weight_version: int = 0  # weight plane version the window ran on


def _init_lanes(batch: int, layer_sizes: tuple[int, ...], num_steps: int,
                v_rest: int) -> LaneState:
    n_in, n_out = layer_sizes[0], layer_sizes[-1]
    return LaneState(
        px=jnp.zeros((batch, n_in), jnp.uint8),
        rng=jnp.full((batch, n_in), 1, jnp.uint32),
        v=tuple(jnp.full((batch, n), v_rest, jnp.int32)
                for n in layer_sizes[1:]),
        en=tuple(jnp.ones((batch, n), bool) for n in layer_sizes[1:]),
        v_peak=tuple(jnp.full((batch, n), _V_PEAK_INIT, jnp.int32)
                     for n in layer_sizes[1:]),
        counts=jnp.zeros((batch, n_out), jnp.int32),
        first=jnp.full((batch, n_out), num_steps, jnp.int32),
        gate_prev=jnp.full((batch,), -1, jnp.int32),
        gate_streak=jnp.zeros((batch,), jnp.int32),
        steps=jnp.zeros((batch,), jnp.int32),
        adds=jnp.zeros((batch,), jnp.int32),
        active=jnp.zeros((batch,), bool),
        weight_version=jnp.zeros((batch,), jnp.int32),
    )


class _Leaf(NamedTuple):
    shape: tuple          # per-lane shape
    dtype: np.dtype
    lo: int               # first uint32 word of the leaf in a lane row
    words: int            # its bytes, padded to a whole word


@dataclass(frozen=True)
class LaneTileCodec:
    """A lane tile as one ``(B, W)`` uint32 slab, so that it crosses the
    host↔device boundary in one transfer each way.

    A lane row is every :class:`LaneState` leaf's per-lane bytes in field
    order (``px``, ``rng``, the per-layer ``v``/``en``/``v_peak``, ...,
    ``weight_version``), each padded to 4 bytes: the layout is a pure
    function of ``layer_sizes``.  On the device :meth:`pack` and
    :meth:`unpack` convert by bitcasts alone, so both are exact; on the
    host :meth:`views` reads the slab's bytes as a ``LaneState`` of numpy
    views, which the engine's host round writes through in place.
    """

    layer_sizes: tuple[int, ...]

    @cached_property
    def _layout(self) -> tuple:
        shapes = jax.eval_shape(partial(_init_lanes, 1, self.layer_sizes,
                                        1, 0))
        flat, treedef = jax.tree.flatten(shapes)
        leaves, lo = [], 0
        for s in flat:
            dtype = np.dtype(s.dtype)
            if dtype.itemsize not in (1, 4):
                raise TypeError(f"no lane-row layout for a {dtype} leaf")
            words = -(-math.prod(s.shape) * dtype.itemsize // 4)
            leaves.append(_Leaf(s.shape[1:], dtype, lo, words))
            lo += words
        return treedef, tuple(leaves), lo

    @property
    def width(self) -> int:
        """uint32 words in one lane row."""
        return self._layout[2]

    @partial(jax.jit, static_argnums=0)
    def pack(self, lanes: LaneState) -> jax.Array:
        """Device ``LaneState`` → its ``(B, W)`` uint32 slab."""
        treedef, leaves, _ = self._layout
        words = []
        for x, leaf in zip(treedef.flatten_up_to(lanes), leaves):
            x = x.reshape(x.shape[0], -1)
            if leaf.dtype.itemsize == 1:        # u8 and bool, as bytes
                x = jnp.pad(x.astype(jnp.uint8),
                            ((0, 0), (0, 4 * leaf.words - x.shape[1])))
                x = x.reshape(x.shape[0], leaf.words, 4)
            words.append(jax.lax.bitcast_convert_type(x, jnp.uint32))
        return jnp.concatenate(words, axis=1)

    @partial(jax.jit, static_argnums=(0, 2))
    def unpack(self, slab: jax.Array, shardings=None) -> LaneState:
        """``(B, W)`` uint32 slab → device ``LaneState``, each leaf under
        ``shardings`` (a ``LaneState`` of them) where given."""
        treedef, leaves, _ = self._layout
        b = slab.shape[0]
        out = []
        for leaf in leaves:
            w = slab[:, leaf.lo:leaf.lo + leaf.words]
            n = math.prod(leaf.shape)
            if leaf.dtype.itemsize == 1:
                x = jax.lax.bitcast_convert_type(w, jnp.uint8)
                x = x.reshape(b, -1)[:, :n].astype(leaf.dtype)
            else:
                x = jax.lax.bitcast_convert_type(w, leaf.dtype)
            out.append(x.reshape((b,) + leaf.shape))
        lanes = treedef.unflatten(out)
        if shardings is not None:
            lanes = jax.lax.with_sharding_constraint(lanes, shardings)
        return lanes

    def views(self, rows: np.ndarray) -> LaneState:
        """Host ``(B, W)`` uint32 rows → a ``LaneState`` whose leaves are
        numpy views into ``rows`` (writes to them write the rows)."""
        treedef, leaves, _ = self._layout
        b = rows.shape[0]
        raw = rows.view(np.uint8)
        return treedef.unflatten(
            raw[:, 4 * l.lo:4 * l.lo + math.prod(l.shape) * l.dtype.itemsize]
            .view(l.dtype).reshape((b,) + l.shape) for l in leaves)

    def rows(self, st: LaneState) -> np.ndarray:
        """The ``(B, W)`` uint32 rows that a host tile from :meth:`views`
        looks into; a tile whose leaves are not all views of one such
        buffer (a leaf replaced, say) is refused."""
        treedef, _, width = self._layout
        flat = treedef.flatten_up_to(st)
        rows = flat[0].base
        if not (isinstance(rows, np.ndarray) and rows.dtype == np.uint32
                and rows.shape[1:] == (width,)
                and all(a.base is rows for a in flat)):
            raise ValueError("not a host lane tile of this layout: every "
                             "leaf must view one (B, W) uint32 buffer")
        return rows


def _stream_chunk_impl(lanes: LaneState, weights: tuple, *, chunk_steps: int,
                       num_steps: int, lif_cfg: lif_mod.LIFConfig,
                       dot_impl: str, active_pruning: bool, patience: int,
                       readout: str = "count", backend: str = "reference",
                       sparse_skip: bool | None = None,
                       interpret: bool | None = None,
                       model_axis: str | None = None,
                       model_ways: tuple[int, ...] | None = None,
                       block_b: int | None = None,
                       layers: tuple | None = None):
    """Un-jitted chunk body: every op is per-lane (no cross-batch contact),
    which is what lets the same code run whole-tile under ``jax.jit`` or
    per-device-slice under ``shard_map`` with bit-identical results.
    Returns ``(lanes', telemetry)`` — the telemetry record is produced
    bit-identically by the fused kernels and this jnp fallback (frozen
    lanes report zero activity, matching the frozen add counters; the
    tile counter reflects the block work the launch geometry executed).

    ``model_axis``/``model_ways`` switch the datapath to the model-sharded
    step (``core.snn.snn_int_stack_step_sharded``): ``weights`` are then
    the device-LOCAL per-layer views (output-column shards for layers
    whose ``model_ways`` entry > 1) and the layer loop exchanges spikes
    over ``model_axis``, so this body must be traced inside ``shard_map``
    on a mesh carrying that axis.  The whole-chunk single-launch
    megakernel cannot host the exchange (collectives cannot run inside a
    ``pallas_call``), so a fused backend decomposes into per-(step, layer)
    Pallas partial-contraction launches — still VMEM-resident weights,
    still bit-identical: the gate/freeze logic below runs on full
    (gathered) arrays identically on every model peer.

    ``layers`` (``SNNConfig.layers``) runs a stack with conv-pool layers;
    its ``weights`` are then a :class:`ConvStackWeights`.
    """
    packed = None
    if isinstance(weights, ConvStackWeights):
        weights, packed = weights.raw, weights.packed
    if model_axis is not None and backend in ("fused", "fused_streamed"):
        contraction = "pallas"
    else:
        contraction = "jnp"
    if backend in ("fused", "fused_streamed") and model_axis is None:
        from ..kernels import ops
        k = ops.fused_snn_stack_op(
            lanes.px, lanes.rng, weights, num_steps=num_steps,
            chunk_steps=chunk_steps, decay_shift=lif_cfg.decay_shift,
            v_threshold=lif_cfg.v_threshold, v_rest=lif_cfg.v_rest,
            v_min=lif_cfg.v_min, v_max=lif_cfg.v_max,
            active_pruning=active_pruning,
            init={"v": lanes.v, "en": lanes.en, "v_peak": lanes.v_peak,
                  "counts": lanes.counts, "first": lanes.first,
                  "steps": lanes.steps},
            gate={"active": lanes.active, "prev": lanes.gate_prev,
                  "streak": lanes.gate_streak},
            patience=patience, readout=readout, sparse_skip=sparse_skip,
            streamed=(backend == "fused_streamed"), interpret=interpret,
            block_b=block_b, layers=layers, packed=packed)
        return LaneState(
            px=lanes.px, rng=k["prng_state"], v=k["v"], en=k["en"],
            v_peak=k["v_peak"],
            counts=k["spike_counts"], first=k["first_spike_t"],
            gate_prev=k["gate"]["prev"], gate_streak=k["gate"]["streak"],
            steps=k["steps"],
            adds=lanes.adds + jnp.sum(k["active_adds"], axis=0),
            active=k["gate"]["active"],
            weight_version=lanes.weight_version), k["telemetry"]

    def body(carry, _):
        st = carry
        act = st.active
        layer_states = tuple(lif_mod.LIFStateInt(v=v, enable=e)
                             for v, e in zip(st.v, st.en))
        if model_axis is not None:
            rng, new_states, fired, adds_t, tel = \
                snn_int_stack_step_sharded(
                    st.rng, st.px, layer_states, weights, lif_cfg,
                    model_axis=model_axis, ways=model_ways,
                    dot_impl=dot_impl, active_pruning=active_pruning,
                    sparse_skip=sparse_skip, contraction=contraction,
                    interpret=interpret)
        else:
            rng, new_states, fired, adds_t, tel = snn_int_stack_step(
                st.rng, st.px, layer_states, weights, lif_cfg,
                dot_impl=dot_impl, active_pruning=active_pruning,
                sparse_skip=sparse_skip, layers=layers)
        counts = st.counts + fired.astype(jnp.int32)
        first = jnp.where(
            jnp.logical_and(fired, st.first == num_steps),
            st.steps[:, None], st.first)
        v_peak = tuple(jnp.maximum(p, s.v)
                       for p, s in zip(st.v_peak, new_states))
        # stability gate on the running prediction (pure, in-loop); a lane
        # with no output spikes yet has no prediction to be stable about —
        # its gate state stays at init so neither the streak nor the retire
        # can trigger before the first spike (argmax(zeros)=0 is not a
        # stable class-0 vote, and the streak must not pre-accumulate).
        has_spike = jnp.max(counts, axis=-1) > 0
        pred = readout_pred(counts, first, new_states[-1].v, readout,
                            num_steps, v_peak=v_peak[-1]).astype(jnp.int32)
        gate, done = stability_step(
            StabilityGateState(prev=st.gate_prev, streak=st.gate_streak),
            pred, patience)
        gate_prev = jnp.where(has_spike, gate.prev, -1)
        gate_streak = jnp.where(has_spike, gate.streak, 0)
        done = jnp.logical_and(done, has_spike)
        steps = st.steps + act.astype(jnp.int32)
        still = jnp.logical_and(act, jnp.logical_not(done))
        still = jnp.logical_and(still, steps < num_steps)

        def keep(new, old, mask=act):
            return jnp.where(mask.reshape((-1,) + (1,) * (new.ndim - 1)),
                             new, old)

        # telemetry rows: frozen lanes execute nothing → zeroed, mirroring
        # the gated kernel; tiles stay raw (block-level executed work)
        tel_spk = jnp.where(act[None, :], tel["n_spk"], 0)
        tel_en = jnp.where(act[None, :], tel["n_en"], 0)
        return LaneState(
            px=st.px,
            rng=keep(rng, st.rng),
            v=tuple(keep(s.v, ov) for s, ov in zip(new_states, st.v)),
            en=tuple(keep(s.enable, oe)
                     for s, oe in zip(new_states, st.en)),
            v_peak=tuple(keep(nv, ov)
                         for nv, ov in zip(v_peak, st.v_peak)),
            counts=keep(counts, st.counts),
            first=keep(first, st.first),
            gate_prev=keep(gate_prev, st.gate_prev),
            gate_streak=keep(gate_streak, st.gate_streak),
            steps=steps,
            adds=st.adds + jnp.where(act, adds_t, 0),
            active=jnp.where(act, still, st.active),
            weight_version=st.weight_version,
        ), (tel_spk, tel_en, tel["tiles"])

    lanes, (tspk, ten, ttile) = jax.lax.scan(body, lanes, None,
                                             length=chunk_steps)
    return lanes, ChunkTelemetry(n_spk=tspk, n_en=ten, tiles_skipped=ttile)


@partial(jax.jit, static_argnames=(
    "chunk_steps", "num_steps", "lif_cfg", "dot_impl", "active_pruning",
    "patience", "readout", "backend", "sparse_skip", "interpret",
    "block_b", "layers"))
def stream_chunk(lanes: LaneState, weights: tuple, *, chunk_steps: int,
                 num_steps: int, lif_cfg: lif_mod.LIFConfig,
                 dot_impl: str, active_pruning: bool, patience: int,
                 readout: str = "count", backend: str = "reference",
                 sparse_skip: bool | None = None,
                 interpret: bool | None = None,
                 block_b: int | None = None,
                 layers: tuple | None = None):
    """Advance every active lane by up to ``chunk_steps`` window steps.

    ``backend="fused"`` runs the whole chunk — every layer, every step,
    the stability gate included — inside one resumable Pallas launch
    (kernels.fused_snn); ``backend="fused_streamed"`` is the same launch
    with the packed weights double-buffered out of HBM (stacks over the
    VMEM residency budget); ``backend="reference"`` scans the same
    datapath in jnp via ``core.snn.snn_int_stack_step``.  All are
    bit-identical on shared lane state, including mid-chunk retirement: a
    retired or inactive lane is completely frozen — PRNG, membranes,
    counters and the add counter stop, which is what the compaction test
    measures.  ``sparse_skip`` forwards the event-driven tile skipping
    flag (value-neutral).  Returns ``(lanes', ChunkTelemetry)`` — the
    structured activity record the adaptive controller consumes, itself
    bit-identical across the chunk backends.  ``block_b`` forwards the
    tuned batch-block override to the fused launch (value-neutral — it
    only reshapes the launch grid and its telemetry tile mirror).
    ``layers`` carries a conv-pool stack's descriptors (static).
    """
    return _stream_chunk_impl(
        lanes, weights, chunk_steps=chunk_steps, num_steps=num_steps,
        lif_cfg=lif_cfg, dot_impl=dot_impl, active_pruning=active_pruning,
        patience=patience, readout=readout, backend=backend,
        sparse_skip=sparse_skip, interpret=interpret, block_b=block_b,
        layers=layers)


def lane_partition_specs(n_layers: int,
                         axis_name: str | None = "data",
                         model_axis: str | None = None) -> LaneState:
    """Per-leaf ``PartitionSpec``s of a data-parallel lane tile.

    Every :class:`LaneState` leaf leads with the batch axis and the chunk
    body never looks across it, so the whole tile shards on one mesh axis;
    quantized weights are the replicated operand.  The gate leaves come
    from ``early_exit.stability_specs`` — the per-lane shardability of the
    in-kernel early exit is that module's contract, not this one's.

    ``model_axis`` is accepted for symmetry with the weight/telemetry
    specs and deliberately changes nothing: the lane checkpoint is
    REPLICATED over the model axis (no leaf mentions it), which is the
    placement-independence contract — a row snapshotted from a
    model-sharded engine adopts into any other engine unchanged, so
    failover/evacuation works identically on 1-D and 2-D meshes.
    """
    del model_axis                       # lane state never shards on it
    p = P(axis_name)
    gate = stability_specs(axis_name)
    return LaneState(
        px=p, rng=p, v=(p,) * n_layers, en=(p,) * n_layers,
        v_peak=(p,) * n_layers,
        counts=p, first=p, gate_prev=gate.prev, gate_streak=gate.streak,
        steps=p, adds=p, active=p, weight_version=p)


def weight_partition_specs(model_ways: tuple[int, ...],
                           model_axis: str | None) -> tuple:
    """Per-layer ``PartitionSpec``s of the quantized weight planes.

    Layers whose effective shard count (``kernels.fused_snn.
    layer_shard_ways``) exceeds 1 split their output-column axis over the
    model mesh axis; non-dividing layers (and every layer on a 1-D data
    mesh) replicate.
    """
    if model_axis is None:
        return tuple(P() for _ in model_ways)
    return tuple(P(None, model_axis) if w > 1 else P() for w in model_ways)


def make_sharded_stream_chunk(mesh: Mesh, axis_name: str, n_layers: int, *,
                              chunk_steps: int, num_steps: int,
                              lif_cfg: lif_mod.LIFConfig, dot_impl: str,
                              active_pruning: bool, patience: int,
                              readout: str = "count",
                              backend: str = "reference",
                              sparse_skip: bool | None = None,
                              interpret: bool | None = None,
                              model_axis: str | None = None,
                              model_ways: tuple[int, ...] | None = None,
                              block_b: int | None = None):
    """Build the (data × model) chunk executor for ``mesh``.

    Returns a jitted ``(lanes, weights) -> (lanes, telemetry)`` whose body
    runs under ``shard_map``: each device executes the fused megakernel
    (or the jnp scan fallback) on its local lane slice with the weights
    replicated — the software analogue of the paper's replicated
    neuron-core lanes.  On a 1-D data mesh no collectives are emitted: the
    stability gate, lane freezing and the telemetry record are
    per-lane/per-block, so the mapped body is embarrassingly parallel and
    bit-identical to the single-device :func:`stream_chunk` on the
    concatenation of the slices (telemetry's tile leaf concatenates the
    device-local block lists — the geometry each device's launch actually
    executed).

    With ``model_axis``/``model_ways`` the weights arrive pre-sharded per
    layer (:func:`weight_partition_specs`: output-column shards over the
    model axis for layers that divide) and the body runs the model-sharded
    datapath — per-device partial contraction, ``all_gather`` spike
    exchange at layer boundaries.  Lane state stays data-sharded /
    model-replicated, the per-lane telemetry counts are derived from full
    gathered arrays (still bit-identical to single-device), and the tile
    leaf concatenates per-shard skip counts data-outer / model-inner on
    the block axis.
    """
    specs = lane_partition_specs(n_layers, axis_name, model_axis)
    tel_specs = telemetry_partition_specs(axis_name, model_axis)
    if model_ways is None:
        w_specs = P()
    else:
        w_specs = weight_partition_specs(model_ways, model_axis)
    body = partial(
        _stream_chunk_impl, chunk_steps=chunk_steps, num_steps=num_steps,
        lif_cfg=lif_cfg, dot_impl=dot_impl, active_pruning=active_pruning,
        patience=patience, readout=readout, backend=backend,
        sparse_skip=sparse_skip, interpret=interpret,
        model_axis=model_axis, model_ways=model_ways, block_b=block_b)
    # no replication check: the mapped body runs Pallas calls, which
    # have no replication rule
    mapped = jax.shard_map(body, mesh=mesh, in_specs=(specs, w_specs),
                           out_specs=(specs, tel_specs), check_vma=False)
    return jax.jit(mapped)


class SNNStreamEngine:
    """Continuous-batching front end over the streaming window chunk.

    Usage::

        eng = SNNStreamEngine(params_q, cfg, batch_size=8)
        ids = [eng.submit(img) for img in images]     # queue requests
        results = eng.run()                            # {id: RequestResult}

    ``backend`` picks the chunk executor: ``"fused"`` (resumable Pallas
    megakernel, int8-packed weights resident — interpret mode off-TPU, so
    slow but bit-exact there), ``"fused_streamed"`` (the same launch with
    weights double-buffered out of HBM, for stacks over the VMEM
    residency budget), ``"reference"`` (jnp scan), or None/"auto" (fused →
    fused_streamed on TPU by per-device VMEM feasibility, reference
    elsewhere).  Arbitrary layer stacks are supported — hidden-layer spike
    traffic stays on-chip on the fused paths.  All three config readouts
    stream, including ``membrane`` (peak-membrane argmax off the carried
    ``LaneState.v_peak`` accumulator).

    ``adaptive`` configures the telemetry controller
    (serve.telemetry.TelemetryController): None reads the
    REPRO_ADAPTIVE_DISPATCH env default (frozen off it) — frozen mode
    serves the static chunk length with zero telemetry readbacks;
    adaptive mode picks each next chunk's length from the observed
    retirement stream.  Either way results are bit-identical — the
    controller only ever moves a value-neutral knob.
    """

    def __init__(self, params_q: dict, cfg: SNNConfig, *,
                 batch_size: int | None = None,
                 chunk_steps: int | None = None, patience: int = 2,
                 seed: int = 0,
                 backend: str | None = None,
                 local_batch: int | None = None,
                 model_shards: int = 1,
                 adaptive: AdaptiveDispatchConfig | None = None,
                 engine_id: int = 0,
                 injector: FaultInjector | None = None,
                 fault_cfg: FaultToleranceConfig | None = None,
                 initial_weight_version: int = 0,
                 block_b: int | None = None,
                 dispatch_cache=None):
        if cfg.readout not in ("count", "first_spike", "membrane"):
            raise ValueError(
                f"unknown readout {cfg.readout!r}: the streaming engine "
                f"implements 'count', 'first_spike' and 'membrane'")
        from ..core.snn import fused_unsupported_reason
        from ..tune.cache import CacheDecision, decide_dispatch
        weights = tuple(layer["w_q"] for layer in params_q["layers"])
        # conv-pool descriptors (None: a dense stack, sized by its weights)
        self.layers = cfg.layers
        if cfg.layers is None:
            self.layer_sizes = tuple([weights[0].shape[0]]
                                     + [w.shape[1] for w in weights])
        else:
            check_weight_shapes(cfg, weights)
            self.layer_sizes = cfg.layer_sizes
        # ---- dispatch cache (repro.tune): tuned startup shapes ----------
        # Resolved exactly once per engine (explicit argument → the
        # REPRO_DISPATCH_CACHE env → none; the sharded subclass passes a
        # pre-made decision keyed by its 2-D mesh shape) and always
        # recorded as ``self.cache_decision`` — a miss or a rejected file
        # serves today's static defaults, never an error.  Explicit
        # constructor arguments beat tuned values knob by knob.
        if isinstance(dispatch_cache, CacheDecision):
            self.cache_decision = dispatch_cache
        else:
            self.cache_decision = decide_dispatch(
                dispatch_cache, cfg=cfg, backend=backend, mesh_shape=(1,))
        tuned = (self.cache_decision.tuned if self.cache_decision.hit
                 else None)
        if tuned is not None:
            if batch_size is None:
                # single-device serving: the whole tile IS one device's
                # lanes, so the tuned per-device lane count applies as-is
                batch_size = tuned.lanes_per_device
            if chunk_steps is None:
                chunk_steps = tuned.chunk_steps
            if block_b is None:
                block_b = tuned.block_b
        if batch_size is None:
            batch_size = 8
        if chunk_steps is None:
            chunk_steps = 4
        self._block_b = block_b
        # Per-device lane tile (the sharded subclass passes its slice;
        # single-device serving holds the whole tile) — scopes the fused
        # VMEM feasibility checks below to one device's launch.  The
        # sharded subclass likewise passes the model-axis width so the
        # checks run against the per-device weight SHARD: a WIDE stack
        # over single-device VMEM resolves resident fused on a 4-way
        # model axis instead of falling back to fused_streamed.
        self.local_batch = batch_size if local_batch is None else local_batch
        self.model_shards = int(model_shards)

        def reason_for(streamed: bool) -> str | None:
            return fused_unsupported_reason(
                cfg, len(weights), self.layer_sizes,
                trace_steps=chunk_steps, local_batch=self.local_batch,
                streamed=streamed, model_shards=self.model_shards,
                block_b=self._block_b)

        if backend in (None, "auto"):
            # A cache hit whose shapes this engine is actually running
            # (no knob overridden) carries the backend that resolved
            # during the tuned run — adopt it after ONE feasibility
            # check against the cached shapes instead of walking the
            # whole chain; a mismatched entry falls through to the
            # normal resolution below (a bad cache degrades to static
            # behavior, it never crashes serving).
            cached_backend = None
            if (tuned is not None
                    and chunk_steps == tuned.chunk_steps
                    and self._block_b == tuned.block_b
                    and self.local_batch == tuned.lanes_per_device):
                t = tuned.backend
                if t == "reference":
                    cached_backend = t
                elif (t in ("fused", "fused_streamed")
                        and jax.default_backend() == "tpu"
                        and reason_for(t == "fused_streamed") is None):
                    cached_backend = t
            # the resumable-backend mirror of core.snn.resolve_backend's
            # fused → fused_streamed chain (staged cannot resume, so the
            # last resort here is the jnp reference scan)
            if cached_backend is not None:
                backend = cached_backend
            elif jax.default_backend() != "tpu":
                backend = "reference"
            elif reason_for(False) is None:
                backend = "fused"
            elif reason_for(True) is None:
                backend = "fused_streamed"
            else:
                backend = "reference"
        if backend not in ("fused", "fused_streamed", "reference"):
            raise ValueError(
                f"streaming chunk backend must be 'fused', 'fused_streamed'"
                f" or 'reference' (the staged kernels cannot resume "
                f"mid-window); got {backend!r}")
        self.backend = backend
        if backend in ("fused", "fused_streamed"):
            from ..kernels.ops import validate_weight_codes
            validate_weight_codes(weights)  # int8-packing range
            reason = reason_for(backend == "fused_streamed")
            if reason is not None:
                raise ValueError(f"{backend} streaming backend unavailable:"
                                 f" {reason} — use backend='reference'")
        # Degradation ladder (serve.faults): the resumable slice of the
        # resolve_backend chain below the configured backend — staged
        # cannot resume mid-window, so the last rung is always the jnp
        # reference scan; infeasible rungs (a streamed launch over budget)
        # are skipped at construction so a demotion can never fault on
        # feasibility.  health.demotion_level indexes this tuple.
        rungs = ("fused", "fused_streamed", "reference")
        self._ladder = tuple(
            b for b in rungs[rungs.index(backend):]
            if b in (backend, "reference")
            or reason_for(b == "fused_streamed") is None)
        self.engine_id = int(engine_id)
        self.injector = (injector if injector is not None
                         else injector_from_env(engine_id))
        self.fault_cfg = fault_cfg or FaultToleranceConfig()
        self.health = EngineHealthState()
        self._cooldown = 0           # scheduling rounds left to sit out
        self._adoptions: list[tuple[int, LaneState]] = []  # evacuated rows
        # Version-tagged weight store (serve.rollout): new admissions bind
        # bank.current; in-flight lanes keep their admission-time version.
        self.bank = WeightBank(self._place_weights(weights),
                               version=int(initial_weight_version))
        self.cfg = cfg
        self.batch_size = batch_size
        self.patience = patience
        self.seed = seed
        # `chunk_steps` is already the effective value (tuned unless the
        # caller overrode it); frozen mode serves it with zero readbacks,
        # adaptive walks its law from it.
        self.controller = make_controller(
            adaptive, chunk_steps=chunk_steps, num_steps=cfg.num_steps)
        self.n_in, self.n_out = self.layer_sizes[0], self.layer_sizes[-1]
        self.lanes = _init_lanes(batch_size, self.layer_sizes,
                                 cfg.num_steps, cfg.lif.v_rest)
        # the tile crosses host<->device as one slab (the sharded engine
        # places the slab and its leaves on its mesh)
        self._codec = LaneTileCodec(self.layer_sizes)
        self._slab_sharding = self._shardings = None
        self.lane_req: list[int | None] = [None] * batch_size
        self.queue: list[tuple[int, np.ndarray]] = []
        self.results: dict[int, RequestResult] = {}
        self._next_id = 0
        # Host mirror of LaneState.weight_version (only admission writes
        # it, so no device sync is ever needed to know which versions are
        # in flight) + the load-summary estimators the router reads.
        self._lane_versions = np.zeros(batch_size, np.int64)
        self._service_ewma: float | None = None
        self._retired_total = 0
        # span counts (serve.spans): chunk launches so far, the last
        # committed chunk's telemetry (kept only while spans record, read
        # at the next sync) and the 128x128 tile pairs one batch block of
        # one launch holds per step (a model peer's own weight shard)
        from ..kernels.fused_snn import layer_shard_ways
        self._launches = 0
        self._chunk_tel: ChunkTelemetry | None = None
        ways = layer_shard_ways(self.layer_sizes, self.model_shards)
        self._tiles_per_block = sum(
            tiles_total((k, n // w))[0] for k, n, w in
            zip(self.layer_sizes[:-1], self.layer_sizes[1:], ways))
        self._conv_layers: tuple[int, ...] = ()
        if self.layers is not None:
            pairs = tiles_total(self.layer_sizes, self.layers)
            self._tiles_per_block = sum(pairs)
            self._conv_layers = tuple(l for l, d in enumerate(self.layers)
                                      if isinstance(d, ConvPool))
            self._conv_tiles_per_block = sum(pairs[l]
                                             for l in self._conv_layers)

    _SERVICE_EWMA_ALPHA = 0.25
    n_devices = 1        # lane slot blocks; compaction keeps a lane in its own

    @property
    def weights(self) -> tuple:
        """Device-placed weight planes of the CURRENT bank version (new
        admissions bind these; draining lanes may still run older ones)."""
        return self.bank.weights(self.bank.current)

    def _place_weights(self, weights: tuple) -> tuple:
        """Device-placement hook for a weight-plane tuple (the sharded
        engine replicates over its mesh here).  A conv-pool stack places a
        :class:`ConvStackWeights`, packed once here for a fused ladder."""
        placed = tuple(jnp.asarray(w) for w in weights)
        if self.layers is None:
            return placed
        from ..kernels.fused_snn import pack_stack
        fused = any(b.startswith("fused") for b in self._ladder)
        return ConvStackWeights(
            raw=placed,
            packed=pack_stack(placed, self.layers) if fused else None)

    @property
    def chunk_steps(self) -> int:
        """Window steps of the NEXT chunk dispatch — the controller's live
        choice (always the configured static value in frozen mode), so
        the public attribute can never go stale under adaptive tuning."""
        return self.controller.chunk_steps

    # ---- request intake -------------------------------------------------
    def submit(self, pixels_u8: np.ndarray, *,
               request_id: int | None = None) -> int:
        """Enqueue one image; returns its request id.

        ``request_id`` lets a routing tier impose its GLOBAL id: the PRNG
        seeds from ``seed + request_id``, so a request served by any
        engine of a same-seed fleet computes the identical window — the
        tier-level bit-identity contract rides on this hook.
        """
        pixels_u8 = np.asarray(pixels_u8, np.uint8).reshape(self.n_in)
        if request_id is None:
            rid = self._next_id
        else:
            rid = int(request_id)
            if (rid in self.results or rid in self.lane_req
                    or any(q[0] == rid for q in self.queue)
                    or any(a[0] == rid for a in self._adoptions)):
                raise ValueError(f"request id {rid} already in use")
        self._next_id = max(self._next_id, rid + 1)
        self.queue.append((rid, pixels_u8))
        return rid

    def load_summary(self) -> EngineLoad:
        """Routing-tier load signals — pure host bookkeeping, no syncs.

        Includes the health surface: consecutive-fault count, degradation
        rung and hang-watchdog margin (chunks of no-progress headroom
        left; ``None`` when no fault harness is armed and the watchdog
        therefore never runs), and liveness.  ``load_score`` folds these
        into the routing comparison, steering traffic away from degraded
        engines without any new device syncs.
        """
        return EngineLoad(
            lanes_total=self.batch_size,
            lanes_busy=sum(r is not None for r in self.lane_req),
            queue_depth=len(self.queue) + len(self._adoptions),
            mean_service_steps=(float(self.cfg.num_steps)
                                if self._service_ewma is None
                                else self._service_ewma),
            retired_total=self._retired_total,
            consecutive_faults=self.health.consecutive_faults,
            demotion_level=self.health.demotion_level,
            watchdog_margin=(None if self.injector is None
                             else self.fault_cfg.watchdog_chunks
                             - self.health.stalled_chunks),
            alive=self.health.alive,
        )

    @property
    def pending(self) -> int:
        return (len(self.queue) + len(self._adoptions)
                + sum(r is not None for r in self.lane_req))

    # ---- readout --------------------------------------------------------
    def _host_pred(self, counts: np.ndarray, first: np.ndarray,
                   v_last: np.ndarray, v_peak: np.ndarray) -> int:
        """Harvest-time prediction for one retired lane, ranked in numpy
        on the host row: no device round trip per request."""
        return int(readout_pred(counts, first, v_last, self.cfg.readout,
                                self.cfg.num_steps, v_peak=v_peak, xp=np))

    # ---- scheduling -----------------------------------------------------
    def _harvest(self, st: LaneState, finished: np.ndarray) -> list[int]:
        """Collect RequestResults for every lane in the ``finished`` mask."""
        with spans.span("snn.harvest") as counts:
            done_ids = []
            for i in np.nonzero(finished)[0]:
                rid = self.lane_req[int(i)]
                steps = int(st.steps[i])
                self.results[rid] = RequestResult(
                    request_id=rid,
                    pred=self._host_pred(st.counts[i], st.first[i],
                                         st.v[-1][i], st.v_peak[-1][i]),
                    spike_counts=st.counts[i].copy(),
                    steps=steps,
                    adds=int(st.adds[i]),
                    early_exit=steps < self.cfg.num_steps,
                    weight_version=int(st.weight_version[i]),
                )
                done_ids.append(rid)
                self._retired_total += 1
                a = self._SERVICE_EWMA_ALPHA
                self._service_ewma = (
                    float(steps) if self._service_ewma is None
                    else (1 - a) * self._service_ewma + a * steps)
            if counts is not None:
                counts.update(n=len(done_ids), rids=tuple(done_ids))
        return done_ids

    def _admit_into(self, st: LaneState, slot: int) -> None:
        """Fill host-side lane ``slot`` with the next waiting request.

        Evacuated-lane adoptions take priority over fresh admissions: an
        adopted request already spent window steps elsewhere, so it is
        the oldest work waiting, and its row is written back verbatim —
        mid-window resume is bit-exact because the row IS the complete
        chunk-boundary state.

        For fresh requests the PRNG lanes are seeded from
        ``seed + request_id``, so a request's entire window is a pure
        function of its id — independent of which slot, device, chunk
        *or engine* it lands in.  This is what makes sharded,
        single-device and post-failover serving bit-identical per
        request.
        """
        if self._adoptions:
            rid, row = self._adoptions.pop(0)
            for f in LaneState._fields:
                dst, src = getattr(st, f), getattr(row, f)
                if isinstance(dst, tuple):
                    for d, s in zip(dst, src):
                        d[slot] = s
                else:
                    dst[slot] = src
            self.lane_req[slot] = rid
            return
        rid, pixels = self.queue.pop(0)
        st.px[slot] = pixels
        st.rng[slot] = prng_mod.seed_state_host(self.seed + rid,
                                                (self.n_in,))
        for v in st.v:
            v[slot] = self.cfg.lif.v_rest
        for en in st.en:
            en[slot] = True
        for vp in st.v_peak:
            vp[slot] = _V_PEAK_INIT
        st.counts[slot] = 0
        st.first[slot] = self.cfg.num_steps
        st.gate_prev[slot] = -1
        st.gate_streak[slot] = 0
        st.steps[slot] = 0
        st.adds[slot] = 0
        st.active[slot] = True
        st.weight_version[slot] = self.bank.current
        self.lane_req[slot] = rid

    def _upload(self, st: LaneState) -> LaneState:
        """Host tile → device: one put of the rows ``st`` views, then
        :meth:`LaneTileCodec.unpack` on the device."""
        rows = self._codec.rows(st)
        with spans.span("snn.upload", bytes=rows.nbytes):
            return self._codec.unpack(
                jax.device_put(rows, self._slab_sharding), self._shardings)

    def _read_tile(self) -> LaneState:
        """Device tile → host: :meth:`LaneTileCodec.pack` on the device,
        one copy into a writable numpy buffer, read as views of it."""
        with spans.span("snn.readback") as counts:
            rows = np.array(self._codec.pack(self.lanes))
            if counts is not None:
                counts.update(bytes=rows.nbytes)
            return self._codec.views(rows)

    def _reorder(self, st: LaneState, order: np.ndarray) -> LaneState:
        """Host tile with its lanes in ``order``: one gather of its rows."""
        return self._codec.views(self._codec.rows(st)[order])

    def _needs_compaction(self) -> bool:
        """Cheap pre-check: only the (B,) active mask crosses the device
        boundary.  The full lane-state round trip happens only when a lane
        actually retired or a queued request can be admitted.

        This is where the host waits for the previous chunk.  While spans
        record, that chunk's skipped tile pairs come back in the same
        transfer and ride on the ``snn.sync`` span."""
        occupied = np.array([r is not None for r in self.lane_req])
        tel, self._chunk_tel = self._chunk_tel, None
        with spans.span("snn.sync") as counts:
            if tel is None or counts is None:
                active = np.asarray(self.lanes.active)
            else:
                active, skipped = jax.device_get(
                    (self.lanes.active, tel.tiles_skipped))
                chunk, _, blocks = skipped.shape
                counts.update(tiles_skipped=int(skipped.sum()),
                              tile_pairs=(self._tiles_per_block * blocks
                                          * chunk))
                if self._conv_layers:
                    counts.update(
                        conv_tiles_skipped=int(
                            skipped[:, list(self._conv_layers)].sum()),
                        conv_tile_pairs=(self._conv_tiles_per_block
                                         * blocks * chunk))
        waiting = bool(self.queue or self._adoptions)
        return bool((occupied & ~active).any() or (
            waiting and not (occupied & active).all()))

    def _admit_and_compact(self) -> list[int]:
        """Harvest retired lanes, compact active ones, admit queued images.

        Returns the request ids finished in this call.  Runs on the host at
        chunk boundaries: the batch tile stays dense, so freed slots start
        contributing to throughput on the very next chunk.  Compaction is
        local to each device's slot block (one block on one device), so a
        lane never changes device and no resharding traffic is generated;
        admission fills freed slots round-robin across the blocks,
        adoptions first (:meth:`_admit_into` drains them before the fresh
        queue).
        """
        if not self._needs_compaction():
            return []
        occupied = np.array([r is not None for r in self.lane_req])
        st = self._read_tile()
        done_ids = self._harvest(st, occupied & ~st.active)

        # Compact each block: live lanes first (stable), freed slots after.
        live = occupied & st.active
        size = self.batch_size // self.n_devices
        order, lane_req, free_slots = [], [], []
        for lo in range(0, self.batch_size, size):
            block = np.arange(lo, lo + size)
            keep = block[live[block]]
            order.extend(keep.tolist() + block[~live[block]].tolist())
            lane_req.extend([self.lane_req[int(i)] for i in keep]
                            + [None] * (size - len(keep)))
            free_slots.append(list(range(lo + len(keep), lo + size)))
        st = self._reorder(st, np.asarray(order, np.int32))
        self.lane_req = lane_req

        with spans.span("snn.admit") as counts:
            admitted = []
            while (self.queue or self._adoptions) and any(free_slots):
                for slots in free_slots:
                    if not (self.queue or self._adoptions):
                        break
                    if slots:
                        slot = slots.pop(0)
                        self._admit_into(st, slot)
                        admitted.append(self.lane_req[slot])
            if counts is not None:
                counts.update(n=len(admitted), rids=tuple(admitted))

        self._sync_versions(st)
        self.lanes = self._upload(st)
        return done_ids

    def _sync_versions(self, st: LaneState) -> None:
        """Refresh the host version mirror; retire drained weight planes.

        Called with the compacted host tile just before upload — the only
        moment lane↔version bindings change.  Dropping the last
        old-version plane here IS rollout completion (recorded in
        ``bank.history``): zero drain, because admission never paused.
        """
        self._lane_versions = np.asarray(st.weight_version).astype(np.int64)
        self.bank.gc({int(v) for v, r in zip(self._lane_versions,
                                             self.lane_req)
                      if r is not None})

    # ---- failover (serve.faults) ----------------------------------------
    def snapshot_lanes(self) -> list[tuple[int, LaneState]]:
        """Host snapshot of every in-flight lane — the evacuation source.

        Called by the tier on an engine that declared failure (with its
        lane state intact).  Lanes that already finished are harvested
        into ``results`` first — they need no evacuation — then each
        still-active lane is returned as ``(request_id, row)``, where
        ``row`` is the lane's complete chunk-boundary state (membranes,
        enables, peaks, PRNG, counters, step/add totals, weight version).
        Because chunked execution is bit-identical to one-shot, adopting
        the row on any same-seed engine resumes the window bit-exactly.
        The snapshot empties the engine: every slot is released and the
        version mirror cleared, so a dead engine holds no live versions.
        """
        occupied = np.array([r is not None for r in self.lane_req])
        st = self._read_tile()
        self._harvest(st, occupied & ~st.active)
        rows = []
        for i in np.nonzero(occupied & st.active)[0]:
            idx = int(i)
            rows.append((self.lane_req[idx],
                         jax.tree.map(lambda a, idx=idx: a[idx].copy(), st)))
        self.lane_req = [None] * self.batch_size
        self._lane_versions = np.zeros(self.batch_size, np.int64)
        return rows

    def checkpoint_lanes(self) -> list[tuple[int, LaneState]]:
        """Non-destructive host copy of every in-flight lane.

        Same ``(request_id, row)`` contract as :meth:`snapshot_lanes`,
        but the engine keeps running: slots stay bound and the version
        mirror is untouched.  The cluster coordinator ships these rows
        with every step reply so its shadow copy is always the current
        chunk-boundary checkpoint — a worker killed before its next
        reply resumes from here bit-exactly (the chunked==one-shot
        invariant makes the row placement-independent).
        """
        occupied = np.array([r is not None for r in self.lane_req])
        st = self._read_tile()
        rows = []
        for i in np.nonzero(occupied & st.active)[0]:
            idx = int(i)
            rows.append((self.lane_req[idx],
                         jax.tree.map(lambda a, idx=idx: a[idx].copy(), st)))
        return rows

    def evict_lane(self, request_id: int) -> LaneState:
        """Pull one in-flight lane off the tile (poison-request path).

        Returns the lane's host row (same contract as
        :meth:`snapshot_lanes`) and frees the slot, so the tier can retry
        the request on another engine — or quarantine it — without
        touching any other lane.
        """
        slot = self.lane_req.index(request_id)
        st = self._read_tile()
        row = jax.tree.map(lambda a: a[slot].copy(), st)
        st.active[slot] = False
        self.lane_req[slot] = None
        self._sync_versions(st)
        self.lanes = self._upload(st)
        return row

    def adopt(self, request_id: int, row: LaneState) -> None:
        """Queue an evacuated lane row for admission on this engine.

        Adoptions are admitted ahead of the fresh-request queue at the
        next compaction and resume bit-exactly (see :meth:`_admit_into`).
        The row's weight version must already be in this engine's bank —
        the tier restores garbage-collected versions via ``bank.ensure``
        before adopting, so an old-version lane never silently runs on
        the wrong planes.
        """
        rid = int(request_id)
        if (rid in self.results or rid in self.lane_req
                or any(q[0] == rid for q in self.queue)
                or any(a[0] == rid for a in self._adoptions)):
            raise ValueError(f"request id {rid} already in use")
        v = int(row.weight_version)
        if v not in self.bank.versions:
            raise KeyError(
                f"adopting request {rid} needs weight version {v}, not in "
                f"bank {self.bank.versions} — restore it via bank.ensure()")
        self._adoptions.append((rid, row))
        self._next_id = max(self._next_id, rid + 1)

    def begin_rollout(self, params_q: dict) -> int:
        """Publish new weight planes without draining in-flight windows.

        New admissions bind the returned version immediately; lanes
        already in flight finish on their admission-time planes (the
        version-split dispatch in :meth:`_dispatch_chunk`).  The rollout
        completes — old planes freed, ``bank.history`` records it — when
        the last old-version lane retires.  Topology is fixed: the lane
        state layout is a function of the layer descriptors, so the new
        weights must have the shapes they give.
        """
        ws = tuple(layer["w_q"] for layer in params_q["layers"])
        descs = (self.layers if self.layers is not None
                 else dense_layers(self.layer_sizes))
        want = weight_shapes(self.n_in, descs)
        got = tuple(tuple(w.shape) for w in ws)
        if got != want:
            raise ValueError(
                f"rollout cannot change the topology: engine serves "
                f"{descs} (weights {want}), new weights are {got}")
        if self.backend in ("fused", "fused_streamed"):
            from ..kernels.ops import validate_weight_codes
            validate_weight_codes(ws)
        return self.bank.begin(self._place_weights(ws))

    def _advance(self, lanes: LaneState, weights: tuple):
        """Dispatch one chunk on the device (async under jax dispatch).

        The chunk length comes from the controller: the configured static
        value when frozen, the live retirement-tuned one when adaptive
        (jit caches one executable per length — the tuning range is small
        and bounded).  Returns ``(lanes', telemetry)``.
        """
        return stream_chunk(
            lanes, weights, chunk_steps=self.controller.chunk_steps,
            num_steps=self.cfg.num_steps, lif_cfg=self.cfg.lif,
            dot_impl=self.cfg.dot_impl,
            active_pruning=self.cfg.active_pruning, patience=self.patience,
            readout=self.cfg.readout, backend=self.backend_effective,
            sparse_skip=self.cfg.sparse_skip, block_b=self._block_b,
            layers=self.layers)

    def _dispatch_versions(self, lanes: LaneState):
        """Version-aware chunk dispatch.

        Single live weight version (steady state): one ordinary chunk.
        Mid-rollout: one gated run per live version — each freezes every
        other version's lanes through the existing ``active`` mask, and
        the per-lane merge (``serve.rollout.merge_version_chunks``)
        reconstructs the tile exactly as if each version's lanes had been
        served alone, so a rollout never perturbs pre-rollout windows.
        """
        occ = [r is not None for r in self.lane_req]
        versions = sorted({int(v) for v, o in zip(self._lane_versions, occ)
                           if o})
        self._launches += max(1, len(versions))
        if len(versions) <= 1:
            v = versions[0] if versions else self.bank.current
            return self._advance(lanes, self.bank.weights(v))
        outs = []
        for v in versions:
            mask = self._lane_versions == v
            sub = lanes._replace(active=jnp.logical_and(
                lanes.active, jnp.asarray(mask)))
            out, tel = self._advance(sub, self.bank.weights(v))
            outs.append((mask, out, tel))
        return merge_version_chunks(outs)

    # ---- fault-guarded dispatch (serve.faults) --------------------------
    @property
    def backend_effective(self) -> str:
        """The ladder rung chunks currently dispatch on (== the
        configured ``backend`` until faults demote the engine)."""
        return self._ladder[self.health.demotion_level]

    def _health_event(self, ev: dict) -> None:
        """Record a health transition where decisions are audited: the
        health log AND the telemetry controller's history."""
        self.health.events.append(ev)
        self.controller.history.append(ev)

    def _demote(self) -> None:
        lvl = self.health.demotion_level
        self._health_event({"event": "demote", "from": self._ladder[lvl],
                            "to": self._ladder[lvl + 1], "level": lvl + 1})
        self.health.demotion_level = lvl + 1
        # the new rung gets a fresh fault budget and a fresh clean streak
        self.health.consecutive_faults = 0
        self.health.clean_chunks = 0

    def _promote(self) -> None:
        lvl = self.health.demotion_level
        self._health_event({"event": "promote", "from": self._ladder[lvl],
                            "to": self._ladder[lvl - 1], "level": lvl - 1})
        self.health.demotion_level = lvl - 1
        self.health.clean_chunks = 0

    def _fail(self, reason: str, *, state_lost: bool = False):
        self.health.alive = False
        self._health_event({"event": "engine_failure", "reason": reason,
                            "state_lost": state_lost})
        raise EngineFailure(
            f"engine {self.engine_id} failed: {reason}",
            engine=self.engine_id, reason=reason, state_lost=state_lost)

    def _dispatch_chunk(self, lanes: LaneState):
        """Chunk dispatch with the fault harness in the loop.

        With no injector armed this is exactly :meth:`_dispatch_versions`
        — zero overhead, zero readbacks, the historical engine
        bit-for-bit.  Armed, every launch consults the injector and the
        recovery ladder runs:

        * **transient dispatch fault** → up to ``max_retries`` immediate
          re-launches (each a fresh injector roll); retries are the pure
          chunk function on unchanged lane state, so a recovered launch
          is bit-identical to a never-faulted one.  ``demote_after``
          consecutive faults step the backend down the degradation
          ladder; a faulting round past the retry budget backs off a
          bounded, deterministic number of scheduling rounds; and
          ``fail_after`` consecutive faults with no rung left escalate to
          :class:`EngineFailure` (the tier evacuates).
        * **hang** → the chunk makes no progress; ``watchdog_chunks``
          consecutive no-progress chunks trip the chunk-deadline watchdog
          and the engine declares failure *with its lane state intact*.
        * **device loss** → immediate failure, optionally with the lane
          state unrecoverable.
        * **poison request** → the typed per-request fault propagates for
          the tier to evict/quarantine; the launch never ran, so every
          other lane is untouched.
        * **corrupted telemetry** → the record fails host validation and
          is dropped (the controller never observes it); the datapath
          result stands — telemetry is a side channel, not the result.

        Returns ``(lanes', telemetry | None)`` — ``None`` marks a round
        that produced no observable record (hang / backoff / corruption).
        """
        with spans.span("snn.dispatch") as counts:
            launches = self._launches
            out = self._dispatch_guarded(lanes)
            if counts is not None:
                counts.update(
                    launches=self._launches - launches,
                    lanes_busy=sum(r is not None for r in self.lane_req),
                    chunk_steps=self.controller.chunk_steps)
        return out

    def _dispatch_guarded(self, lanes: LaneState):
        if self.injector is None:
            return self._dispatch_versions(lanes)
        if not self.health.alive:
            raise EngineFailure(
                f"engine {self.engine_id} is dead", engine=self.engine_id,
                reason="dead", state_lost=False)
        ft = self.fault_cfg
        attempt = 0
        while True:
            try:
                tok = self.injector.before_dispatch(
                    attempt, backend=self.backend_effective,
                    rids=[r for r in self.lane_req if r is not None])
            except DeviceLostFault as e:
                self._fail("device_lost", state_lost=e.state_lost)
            except PoisonDispatchError:
                raise
            except DispatchFault as e:
                self.health.record_fault("dispatch", str(e))
                if (self.health.consecutive_faults >= ft.demote_after
                        and self.health.demotion_level + 1
                        < len(self._ladder)):
                    self._demote()
                    attempt = 0
                    continue
                if self.health.consecutive_faults >= ft.fail_after:
                    self._fail("dispatch_exhausted")
                attempt += 1
                if attempt <= ft.max_retries:
                    continue
                # the whole round faulted: deterministic bounded backoff,
                # counted in scheduling rounds (the tier's step currency)
                burst = self.health.consecutive_faults - 1
                self._cooldown = min(ft.backoff_base << min(burst, 8),
                                     ft.backoff_max)
                return lanes, None
            if tok == "hang":
                self.health.stalled_chunks += 1
                if self.health.stalled_chunks >= ft.watchdog_chunks:
                    self._fail("hang")
                return lanes, None
            out, tel = self._dispatch_versions(lanes)
            self.health.stalled_chunks = 0
            tel = self.injector.filter_telemetry(tel)
            if not telemetry_ok(tel):
                self.health.telemetry_faults += 1
                self._health_event({"event": "fault", "kind": "telemetry"})
                tel = None
            else:
                self.health.record_clean()
                if (self.health.demotion_level > 0
                        and self.health.clean_chunks >= ft.promote_after):
                    self._promote()
            return out, tel

    def _observe(self, src: LaneState, nxt: LaneState,
                 tel: ChunkTelemetry | None) -> None:
        """Take one committed chunk's telemetry: kept for the next sync's
        tile counts while spans record, and fed to the controller
        (adaptive only — frozen mode never forces the device→host
        readback)."""
        self._chunk_tel = tel if spans.enabled() else None
        if tel is None or self.controller.frozen:
            return
        self.controller.observe(summarize_chunk(
            tel, self.layer_sizes,
            steps_before=src.steps, steps_after=nxt.steps,
            active_before=src.active, active_after=nxt.active))

    def step(self) -> list[int]:
        """Admit + run one chunk.  Returns request ids finished so far."""
        with spans.span("snn.step", engine=self.engine_id):
            return self._step()

    def _step(self) -> list[int]:
        done = self._admit_and_compact()
        if self._cooldown > 0:
            # transient-fault backoff: sit this scheduling round out
            self._cooldown -= 1
            return done
        src = self.lanes
        self.lanes, tel = self._dispatch_chunk(src)
        self._observe(src, self.lanes, tel)
        return done

    def run(self, max_chunks: int | None = None) -> dict[int, RequestResult]:
        """Drive chunks until every submitted request has a result."""
        limit = max_chunks if max_chunks is not None else (
            (self.pending + self.batch_size)
            * (self.cfg.num_steps // max(1, self.controller.min_chunk_steps)
               + 2)
            # fault rounds (retry backoff, hang stalls) make no progress;
            # give an armed harness bounded slack instead of a hard wedge
            + (0 if self.injector is None else 64))
        for _ in range(limit):
            if self.pending == 0:
                break
            self.step()
        self._admit_and_compact()
        return self.results


class ShardedSNNStreamEngine(SNNStreamEngine):
    """(Data × model)-parallel lane mesh over the streaming engine.

    The batch tile is sharded over the ``axis_name`` axis of a
    ``jax.sharding.Mesh`` — each device owns ``batch_size // n_devices``
    contiguous lane slots and executes the fused (or jnp-scan fallback)
    chunk on its local slice under ``shard_map``, with the quantized
    weights replicated (the software analogue of replicating the paper's
    neuron core across parallel hardware lanes).  Because every part of
    the chunk — datapath, stability gate, lane freezing, add counter — is
    per-lane, results are bit-identical to :class:`SNNStreamEngine` on the
    same seeds: same predictions, same retirement steps, same frozen
    executed-add counters.

    If the mesh also carries a ``model_axis_name`` axis (build one with
    ``distributed.sharding.make_2d_device_mesh``), each layer whose
    output width divides the axis splits its weight columns across the
    model peers — the multi-core neuron partitioning of the SNN-hardware
    literature: per-device partial contraction of the full input-spike
    vector against the local weight shard, per-shard LIF, then an
    ``all_gather`` spike exchange at the layer boundary so every peer
    enters the next layer with the full fired vector.  Layers that don't
    divide (the 10-class head on a 4-way axis) replicate and skip the
    exchange.  Lane state stays model-replicated, so the ``LaneState``
    checkpoint is placement-independent — ``snapshot_lanes``/``adopt``
    failover works unchanged between 1-D and 2-D engines — and the VMEM
    feasibility check runs against the per-device weight shard, which is
    what lets a WIDE stack serve VMEM-resident ``fused`` on a 4-way
    model axis instead of streaming weights from HBM.  Results stay
    bit-identical: disjoint integer column shards concatenate exactly.

    Scheduling on the mesh:

      * **Device-local compaction** — retired lanes are compacted within
        their device's slot block, never across blocks, so lane state is
        re-uploaded onto the same device and no resharding traffic is
        generated at chunk boundaries (the base engine's compaction, one
        block per device).
      * **Round-robin admission** — queued requests fill freed slots
        cycling across device blocks, keeping every device's live-lane
        count balanced under partial load.
      * **Admission/compute overlap**, this class's own — after
        dispatching chunk *k* the engine speculatively enqueues chunk
        *k+1* on its (not yet ready) output, so the devices keep running
        while the host blocks on the chunk-*k* retirement readback and
        does queue bookkeeping.  If the readback shows a retirement or a
        possible admission, the speculative state is discarded and the
        chunk re-dispatched from the compacted tile — speculation is the
        pure chunk function on the same state, so using it never changes
        results.
        ``stats['spec_used']``/``stats['spec_wasted']`` count the
        outcomes (the benchmark's admission-overlap timing).
    """

    def __init__(self, params_q: dict, cfg: SNNConfig, *,
                 mesh: Mesh | None = None, axis_name: str = "data",
                 model_axis_name: str = "model",
                 lanes_per_device: int | None = None,
                 batch_size: int | None = None,
                 chunk_steps: int | None = None, patience: int = 2,
                 seed: int = 0,
                 backend: str | None = None, overlap: bool = True,
                 adaptive: AdaptiveDispatchConfig | None = None,
                 engine_id: int = 0,
                 injector: FaultInjector | None = None,
                 fault_cfg: FaultToleranceConfig | None = None,
                 initial_weight_version: int = 0,
                 block_b: int | None = None,
                 dispatch_cache=None):
        from ..kernels.fused_snn import layer_shard_ways
        from ..tune.cache import CacheDecision, decide_dispatch
        if cfg.layers is not None:
            raise ValueError(
                "conv-pool layers are served by SNNStreamEngine on one "
                "device: a model axis cannot split a conv map (its neurons "
                "share every input row), and no data mesh runs them")
        if mesh is None:
            mesh = jax.make_mesh((len(jax.devices()),), (axis_name,),
                                 (AxisType.Auto,))
        if axis_name not in mesh.axis_names:
            raise ValueError(f"mesh {mesh.axis_names} has no "
                             f"{axis_name!r} axis")
        if model_axis_name == axis_name:
            raise ValueError(
                f"model_axis_name {model_axis_name!r} must differ from the "
                f"lane axis {axis_name!r}")
        self.mesh = mesh
        self.axis_name = axis_name
        self.n_devices = mesh.shape[axis_name]
        # Model axis: present in the mesh → each layer that divides holds
        # only an output-column weight shard per device and the chunk
        # exchanges spikes at layer boundaries; absent (or 1-wide) → the
        # historical pure data-parallel engine, bit-for-bit.
        self.model_axis_name = model_axis_name
        self.model_devices = (int(mesh.shape[model_axis_name])
                              if model_axis_name in mesh.axis_names else 1)
        self.model_axis = (model_axis_name if self.model_devices > 1
                           else None)
        w_shapes = [layer["w_q"].shape for layer in params_q["layers"]]
        sizes = tuple([w_shapes[0][0]] + [s[1] for s in w_shapes])
        self.model_ways = layer_shard_ways(sizes, self.model_devices)
        # The cache consultation happens HERE (not in the base __init__)
        # because the tuned per-device lane count must be known before
        # the global tile shape is fixed, and the lookup key carries this
        # engine's 2-D mesh shape — a cache tuned for one topology must
        # miss on another, not silently re-tile it.  The resolved
        # decision is handed to the base constructor so it is only made
        # once.
        if isinstance(dispatch_cache, CacheDecision):
            decision = dispatch_cache
        else:
            decision = decide_dispatch(
                dispatch_cache, cfg=cfg, backend=backend,
                mesh_shape=(self.n_devices, self.model_devices))
        if (decision.hit and batch_size is None
                and lanes_per_device is None):
            lanes_per_device = decision.tuned.lanes_per_device
        if batch_size is None:
            batch_size = (8 if lanes_per_device is None
                          else lanes_per_device) * self.n_devices
        elif (lanes_per_device is not None
              and batch_size != lanes_per_device * self.n_devices):
            raise ValueError(
                f"conflicting tile shape: batch_size={batch_size} but "
                f"lanes_per_device={lanes_per_device} × "
                f"{self.n_devices} devices = "
                f"{lanes_per_device * self.n_devices} — pass one or the "
                f"other")
        if batch_size % self.n_devices:
            raise ValueError(
                f"batch_size={batch_size} must divide evenly over the "
                f"{self.n_devices}-device {axis_name!r} axis")
        self.overlap = overlap
        self.stats = {"chunks": 0, "spec_used": 0, "spec_wasted": 0}
        self._spec: tuple | None = None
        self._spec_src: LaneState | None = None
        self._spec_steps: int | None = None
        super().__init__(params_q, cfg, batch_size=batch_size,
                         chunk_steps=chunk_steps, patience=patience,
                         seed=seed, backend=backend,
                         local_batch=batch_size // self.n_devices,
                         model_shards=self.model_devices,
                         adaptive=adaptive, engine_id=engine_id,
                         injector=injector, fault_cfg=fault_cfg,
                         initial_weight_version=initial_weight_version,
                         block_b=block_b, dispatch_cache=decision)
        specs = lane_partition_specs(len(self.weights), axis_name,
                                     self.model_axis)
        self._shardings = jax.tree.map(
            lambda s: NamedSharding(mesh, s), specs,
            is_leaf=lambda x: isinstance(x, P))
        # one sharded executor per (chunk length, ladder rung) the
        # runtime dispatches (exactly one entry when frozen and healthy)
        self._chunk_fns: dict[tuple[int, str], object] = {}
        self._chunk_fn_for(self.controller.chunk_steps)
        self._slab_sharding = NamedSharding(mesh, P(axis_name, None))
        self.lanes = jax.device_put(self.lanes, self._shardings)

    # ---- device placement ----------------------------------------------
    def _place_weights(self, weights: tuple) -> tuple:
        # per-layer placement: output-column shards over the model axis
        # for layers that divide, replicated otherwise (and always
        # replicated on a pure data mesh) — rollout versions land the
        # same way the construction-time planes do
        w_specs = weight_partition_specs(self.model_ways, self.model_axis)
        return tuple(
            jax.device_put(jnp.asarray(w), NamedSharding(self.mesh, s))
            for w, s in zip(weights, w_specs))

    def _chunk_fn_for(self, n_steps: int):
        key = (n_steps, self.backend_effective)
        if key not in self._chunk_fns:
            self._chunk_fns[key] = make_sharded_stream_chunk(
                self.mesh, self.axis_name, len(self.weights),
                chunk_steps=n_steps, num_steps=self.cfg.num_steps,
                lif_cfg=self.cfg.lif, dot_impl=self.cfg.dot_impl,
                active_pruning=self.cfg.active_pruning,
                patience=self.patience, readout=self.cfg.readout,
                backend=self.backend_effective,
                sparse_skip=self.cfg.sparse_skip,
                model_axis=self.model_axis,
                model_ways=self.model_ways if self.model_axis else None,
                block_b=self._block_b)
        return self._chunk_fns[key]

    def _advance(self, lanes: LaneState, weights: tuple):
        return self._chunk_fn_for(self.controller.chunk_steps)(
            lanes, weights)

    # ---- scheduling -----------------------------------------------------
    def _step(self) -> list[int]:
        """Admit + run one chunk, overlapping the next with host work."""
        done = self._admit_and_compact()
        if self._cooldown > 0:
            self._cooldown -= 1
            return done
        if (self._spec is not None and self.lanes is self._spec_src
                and self._spec_steps == self.controller.chunk_steps):
            # the tile object is the very one the speculative chunk was
            # dispatched from (no compaction replaced it — here OR in any
            # intervening run()/_admit_and_compact call) AND the
            # controller still wants the chunk length the speculation ran
            # at: the speculation IS this step's chunk (same pure
            # function, same input).  The length guard is load-bearing —
            # an adaptive retune landing between dispatch and commit
            # (this engine's own observe, or a tier/coordinator feeding
            # the controller out-of-band) means the speculative state
            # advanced the lanes by the WRONG number of window steps;
            # committing it would silently serve a stale-length chunk.
            src = self._spec_src
            nxt, tel = self._spec
            self.stats["spec_used"] += 1
        else:
            if self._spec is not None:
                self.stats["spec_wasted"] += 1
            src = self.lanes
            nxt, tel = self._dispatch_chunk(src)
        self._spec = self._spec_src = None
        self._spec_steps = None
        self.lanes = nxt
        self.stats["chunks"] += 1
        self._observe(src, nxt, tel)
        # Speculation is off while a fault harness is armed: a speculative
        # launch would consume injector consults (and could fault) one
        # step early, detaching the fault coordinates from the committed
        # dispatch sequence the deterministic-replay contract pins.
        if self.overlap and self.injector is None \
                and (self.queue
                     or any(r is not None for r in self.lane_req)):
            # enqueue chunk k+1 now — the devices stay busy while the next
            # step's host-side readback and queue bookkeeping run (the
            # lane↔version map only changes at compaction, which discards
            # the speculation, so version-split dispatch speculates safely).
            # Record the chunk length this speculation ran at: the commit
            # path discards it (spec_wasted) if a retune moves the
            # controller's choice before the next step.
            self._spec_src = nxt
            self._spec_steps = self.controller.chunk_steps
            self._spec = self._dispatch_chunk(nxt)
        return done
