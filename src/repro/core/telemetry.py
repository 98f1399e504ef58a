"""Structured kernel↔host telemetry: the runtime's activity side channel.

The paper's efficiency story is *event-driven* — work should track the
spike activity the hardware actually observes (Bouvier et al. 2020 call
activity monitoring the standard control plane of neuromorphic runtimes;
SparrowSNN feeds measured spike statistics back into scheduling).  Until
this module existed, the runtime steered itself with compile-time guesses:
the masked-vs-MXU dispatch threshold was a hard-coded constant and the
fused kernel's tile-skip decisions were invisible to the host even though
the kernel computes every ingredient per step.

:class:`ChunkTelemetry` is the structured record every integer-engine
backend emits for a window chunk — per-step, per-layer spike counts,
prune-enable occupancy and (derived) executed adds per lane, plus the
per-block MXU tile pairs the event-driven contraction skipped.  The
contract that makes it trustworthy is that telemetry is **bit-checkable
cross-backend**: the fused megakernel emits it as extra kernel outputs,
and the staged / reference / jnp-scan paths re-derive the identical
numbers from their own state (``kernels.ref`` re-derives the tile
geometry independently, double-entry-bookkeeping style), so a telemetry
regression is caught exactly like a datapath regression.

On top of the record, ``serve.telemetry`` builds the adaptive controller
that retunes the dispatch threshold and picks chunk lengths from live
traffic; this module only defines the channel and the pure helpers shared
by every producer.
"""

from __future__ import annotations

import os
from typing import NamedTuple

import jax
import jax.numpy as jnp

__all__ = [
    "ChunkTelemetry",
    "EngineLoad",
    "MatmulTelemetry",
    "DEFAULT_SPIKE_DENSITY_THRESHOLD",
    "engine_load_from_wire",
    "engine_load_to_wire",
    "estimate_eta_steps",
    "load_score",
    "resolve_density_threshold",
    "resolve_sparse_skip",
    "layer_tile_skips",
    "tiles_total",
    "telemetry_partition_specs",
    "concat_telemetry",
]

# The compile-time guess this subsystem exists to replace: below this
# per-batch spike density the masked (event-driven) spike-matmul kernel
# wins over the MXU dot.  Kept under its historical home as
# ``kernels.ops.SPIKE_DENSITY_THRESHOLD`` too — it is now only the
# *default* for ``SNNConfig.spike_density_threshold`` / the env override,
# and the serving controller may retune the live value.
DEFAULT_SPIKE_DENSITY_THRESHOLD = 0.25


def resolve_density_threshold(threshold: float | None = None) -> float:
    """Explicit value → env ``REPRO_SPIKE_DENSITY_THRESHOLD`` → default.

    The resolution order mirrors ``REPRO_SPARSE_SKIP``: an explicit config
    value always wins, the env var lets CI sweep the dispatch boundary
    across a whole run without touching call sites, and the exported
    module constant keeps its historical meaning as the default.
    """
    if threshold is not None:
        return float(threshold)
    env = os.environ.get("REPRO_SPIKE_DENSITY_THRESHOLD")
    if env:
        return float(env)
    return DEFAULT_SPIKE_DENSITY_THRESHOLD


def resolve_sparse_skip(sparse_skip: bool | None) -> bool:
    """None → the REPRO_SPARSE_SKIP env default (on unless set to "0").

    Resolved at trace time (``sparse_skip`` is a static argument
    everywhere), which is what lets CI force the dense and sparse tile
    paths across a whole test run without touching call sites.  The
    single source of truth shared by the kernel launcher
    (``kernels.ops``) and the jnp telemetry mirrors below.
    """
    if sparse_skip is None:
        return os.environ.get("REPRO_SPARSE_SKIP", "1") != "0"
    return bool(sparse_skip)


class ChunkTelemetry(NamedTuple):
    """Per-chunk activity record, identical across all four backends.

    Shapes (``chunk`` = steps this launch executed, ``L`` = layers,
    ``B`` = lanes, ``n_blocks`` = batch-block programs of the fused
    launch geometry):

      n_spk          (chunk, L, B) int32 — input spikes layer ``l``
                     consumed at step ``t`` for each lane (layer 0 =
                     encoder output).  Zeroed for lanes the stability
                     gate had already frozen, matching the executed-add
                     channel.
      n_en           (chunk, L, B) int32 — prune-enable occupancy: how
                     many of layer ``l``'s neurons were still enabled.
                     Zeroed for frozen lanes.
      tiles_skipped  (chunk, L, n_blocks) int32 — 128×128 MXU tile pairs
                     the event-driven contraction skipped per batch
                     block (0 everywhere when ``sparse_skip`` is off).
                     Block-level by construction: the skip predicate
                     spans all lanes of a block, so this leaf tracks the
                     launch geometry, not individual lanes.

    ``adds`` is derived, not stored: per lane the executed synaptic adds
    of a dense layer ``l`` are exactly ``n_spk · n_en`` (a skipped tile
    pair has zero of one factor), so the record stays minimal and the
    invariant "telemetry adds == the frozen energy counters" is checkable
    rather than tautological.  A conv-pool layer's spike reaches only
    the neurons whose fields cover it, so there ``n_spk · n_en`` bounds
    its adds from above and the energy counter holds the count.
    """

    n_spk: jax.Array
    n_en: jax.Array
    tiles_skipped: jax.Array

    @property
    def adds(self) -> jax.Array:
        """Executed synaptic adds per (step, layer, lane) of a dense
        layer — n_spk · n_en."""
        return self.n_spk * self.n_en

    def densities(self, layer_sizes) -> jax.Array:
        """Observed input-spike density per (step, layer, lane) in [0, 1].

        Layer ``l``'s fan-in is ``layer_sizes[l]`` — the quantity the
        masked-vs-MXU dispatch threshold is compared against.
        """
        fan_in = jnp.asarray(layer_sizes[:-1], jnp.float32)
        return self.n_spk.astype(jnp.float32) / fan_in[None, :, None]


class EngineLoad(NamedTuple):
    """Host-side load summary of one serving engine (router currency).

    Every field is either free host bookkeeping (occupancy, queue depth —
    the engine already tracks both) or an estimate the telemetry loop
    maintains without extra device syncs: ``mean_service_steps`` is the
    EWMA of window steps retired requests actually consumed (early exit
    makes this traffic-dependent — exactly why routing on the *measured*
    rate beats routing on ``num_steps``), ``density_ewma`` is the
    adaptive controller's estimate (``None`` when frozen or unobserved).
    The serving tier sprays requests by :func:`load_score` and gates
    admission with :func:`estimate_eta_steps` — both pure functions of
    this record, so routing decisions are deterministic and replayable.
    """

    lanes_total: int               # batch-tile slots the engine owns
    lanes_busy: int                # slots currently bound to a request
    queue_depth: int               # host-queue requests not yet admitted
    mean_service_steps: float      # EWMA of consumed steps per request
    retired_total: int             # requests completed since construction
    density_ewma: float | None     # controller estimate (None if frozen)
    # Health surface (serve.faults) — defaulted so positional construction
    # of the historical six-field record keeps meaning "healthy engine".
    consecutive_faults: int = 0    # dispatch faults since the last clean chunk
    demotion_level: int = 0        # rungs down the backend degradation ladder
    watchdog_margin: int | None = None  # chunks left before the hang deadline
    alive: bool = True             # False once the engine declared failure

    @property
    def occupancy(self) -> float:
        """Fraction of lane slots currently serving a request."""
        return self.lanes_busy / max(1, self.lanes_total)


def engine_load_to_wire(load: EngineLoad) -> dict:
    """JSON-safe dict of one load record (the cluster RPC surface).

    Every field is already a JSON scalar (ints, floats, bools, None), so
    ``_asdict`` is the whole codec — kept as a named function so the RPC
    layer depends on the *contract* (roundtrips through
    :func:`engine_load_from_wire` reproduce the record exactly and the
    routing scores computed from it) rather than a NamedTuple detail.
    """
    return dict(load._asdict())


def engine_load_from_wire(d: dict) -> EngineLoad:
    """Inverse of :func:`engine_load_to_wire` (exact roundtrip)."""
    return EngineLoad(**d)


def _effective_service_steps(load: EngineLoad) -> float:
    """Sanitized mean service window for the routing estimators.

    A just-constructed engine has retired nothing, and an ``EngineLoad``
    assembled by an external coordinator may carry a zero, negative or
    non-finite ``mean_service_steps`` (empty EWMA serialized as 0.0 /
    NaN).  Feeding that into :func:`load_score` made a cold engine's
    score collapse to 0 (or NaN) regardless of its queue, so it
    spuriously beat every warmed healthy engine; :func:`estimate_eta_steps`
    likewise returned 0 / NaN instead of a usable wait bound.  Any value
    that cannot be a measured window (non-finite or ≤ 0) falls back to
    one step — the smallest window a request can consume — so a cold
    engine's outstanding work still counts, while every legitimately
    measured mean (engines seed the EWMA with ``num_steps``) passes
    through untouched and the historical scoring formula is preserved
    bit-for-bit for healthy warmed records.
    """
    mean = float(load.mean_service_steps)
    if not (0.0 < mean < float("inf")):   # ≤0, NaN and ±inf all fail this
        return 1.0
    return mean


def load_score(load: EngineLoad) -> float:
    """Expected outstanding work per lane slot, in window steps.

    Busy lanes owe on average half a service window; queued requests owe
    a full one.  Normalizing by the slot count makes engines of different
    widths comparable, and scaling by the *measured* mean service steps
    lets an engine whose traffic exits early absorb proportionally more
    load.  Pure and deterministic — the router's least-loaded comparison
    (ties broken by engine index) is reproducible in CI.

    The health surface folds in as an additive degradation charge: each
    rung down the backend ladder counts like half the tile being busy and
    each consecutive unresolved fault like a quarter, so a degraded
    engine keeps serving but stops being anyone's first choice; a dead
    engine scores infinite and can never win a least-loaded comparison.
    A fully healthy record scores exactly what the historical six-field
    formula scored, keeping the tier's routing-determinism contract.
    """
    if not load.alive:
        return float("inf")
    owed = 0.5 * load.lanes_busy + load.queue_depth
    degraded = (0.5 * load.demotion_level
                + 0.25 * load.consecutive_faults) * load.lanes_total
    return ((owed + degraded) * _effective_service_steps(load)
            / max(1, load.lanes_total))


def estimate_eta_steps(load: EngineLoad) -> float:
    """Expected window steps until a NEW admission would complete.

    Queue-wave model: a request entering the host queue waits zero waves
    if a lane slot is free, else one wave per ``lanes_total`` requests
    already ahead of it, each wave lasting the measured mean service
    window; its own service appends one more.  Deliberately coarse — the
    admission policy needs a monotone, deterministic feasibility
    estimate, not a simulator — and conservative in the right direction:
    early-exit traffic shortens the measured wave, never lengthens it.

    Cold-engine edge: a record whose service EWMA is still empty (zero /
    NaN mean) estimates with a one-step wave via
    :func:`_effective_service_steps`, so the ETA is always finite and
    ≥ 1 — an admission gate comparing it against a deadline never sees
    0 or NaN from an engine that simply hasn't retired anything yet.
    """
    free = load.lanes_total - load.lanes_busy
    if load.queue_depth < free:
        waves = 0
    else:
        waves = 1 + (load.queue_depth - free) // max(1, load.lanes_total)
    return (waves + 1) * _effective_service_steps(load)


class MatmulTelemetry(NamedTuple):
    """Side channel of one ``spike_matmul_op(mode="auto")`` dispatch."""

    density: jax.Array     # f32 scalar — observed batch spike density
    used_masked: jax.Array  # bool scalar — which datapath the cond took


def _pad_axis(x: jax.Array, axis: int, mult: int) -> jax.Array:
    pad = (-x.shape[axis]) % mult
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


def tiles_total(layer_sizes, layers=None) -> tuple[int, ...]:
    """Total 128×128 tile pairs per layer, per batch block, per step
    (``layers``: the stack's descriptors, for conv-pool stages)."""
    from ..kernels.fused_snn import layer_tile_pairs, stack_geometry
    return layer_tile_pairs(layer_sizes,
                            stack_geometry(int(layer_sizes[0]), layers))


def layer_tile_skips(x: jax.Array, en: jax.Array, *,
                     sparse_skip: bool, geom=None) -> jax.Array:
    """jnp mirror of the fused kernel's per-layer tile-skip predicates.

    ``x``: (B, n_in) bool input spikes; ``en``: (B, n_out) bool enables.
    Returns (n_blocks,) int32 — skipped (K-tile, N-tile) pairs per batch
    block, with exactly the launch geometry ``kernels.ops`` pads to:
    neuron axes to 128 (padded pixels never spike, padded neurons are
    disabled), lanes to the ``block_b_for`` batch block.  A pair is
    skipped when its K-tile carries no spike in any lane of the block OR
    its output tile is fully pruned across the block — the
    ``lax.cond`` predicate of ``fused_snn._tiled_contraction``, which is
    why this pure function is bit-checkable against the kernel's own
    counter.  All-jnp, so it runs inside scan/jit/shard_map bodies.

    ``geom`` (``kernels.fused_snn.stack_geometry``) mirrors a conv-pool
    stage (``ConvGeom``: :func:`_conv_tile_skips`) or the dense layer that
    reads the last conv map as its row blocks side by side
    (``FlatGeom``); the flat rows are laid out as the kernel's maps.
    """
    from ..kernels.fused_snn import (LANE, ConvGeom, FlatGeom, block_b_for,
                                     to_map)
    B = x.shape[0]
    bB = block_b_for(B)
    if isinstance(geom, ConvGeom):
        return _conv_tile_skips(x, en, geom, bB, sparse_skip=sparse_skip)
    if isinstance(geom, FlatGeom):
        xm = to_map(_pad_axis(x.astype(bool), 0, bB), geom.in_map(), bB)
        nb = xm.shape[0] // (geom.m * geom.hs * bB)
        x = jnp.transpose(xm.reshape(nb, geom.m * geom.hs, bB, geom.nh),
                          (0, 2, 1, 3)).reshape(nb * bB, geom.k_in)
    xp = _pad_axis(_pad_axis(x.astype(bool), 0, bB), 1, LANE)
    ep = _pad_axis(_pad_axis(en.astype(bool), 0, bB), 1, LANE)
    nb = xp.shape[0] // bB
    nkt, nnt = xp.shape[1] // LANE, ep.shape[1] // LANE
    any_x = jnp.any(xp.reshape(nb, bB, nkt, LANE), axis=(1, 3))  # (nb, nkt)
    any_e = jnp.any(ep.reshape(nb, bB, nnt, LANE), axis=(1, 3))  # (nb, nnt)
    live = jnp.logical_and(any_x[:, :, None], any_e[:, None, :])
    if not sparse_skip:
        return jnp.zeros((nb,), jnp.int32)
    return jnp.sum(jnp.logical_not(live), axis=(1, 2)).astype(jnp.int32)


def _conv_tile_skips(x, en, g, bB: int, *, sparse_skip: bool) -> jax.Array:
    """The conv-pool stage's skipped tile pairs per batch block: for each
    (row class q, row phase u, tap row dy), a K tile of the input rows the
    stage reads is live if a lane of the block spikes in it, an N tile of
    the pre-pool currents if class q holds an enabled neuron in its
    pooled column tile (``kernels.fused_snn._conv_stage``)."""
    from ..kernels.fused_snn import LANE, to_map
    xm = to_map(_pad_axis(x.astype(bool), 0, bB), g.in_map(), bB)
    em = to_map(_pad_axis(en.astype(bool), 0, bB), g.out_map(), bB)
    nb = xm.shape[0] // (g.m_in * g.hs_in * bB)
    any_x = jnp.any(xm.reshape(nb, g.m_in * g.hs_in, bB, g.kc // LANE, LANE),
                    axis=(2, 4))                      # (nb, row blocks, kt)
    any_e = jnp.any(em.reshape(nb, g.m_out * g.hs_out, bB, g.nh // LANE,
                               LANE), axis=(2, 4))
    if not sparse_skip:
        return jnp.zeros((nb,), jnp.int32)
    skipped = jnp.zeros((nb,), jnp.int32)
    for q in range(g.m_out):
        e_q = jnp.any(any_e[:, q * g.hs_out:(q + 1) * g.hs_out], axis=1)
        e_pre = jnp.concatenate([e_q] * g.pool, axis=1)
        for u in range(g.pool):
            for dy in range(g.k):
                s = g.pool * q + u + dy
                r0 = (s % g.m_in) * g.hs_in + s // g.m_in
                x_s = jnp.any(any_x[:, r0:r0 + g.hs_out], axis=1)
                live = jnp.logical_and(x_s[:, :, None], e_pre[:, None, :])
                skipped = skipped + jnp.sum(jnp.logical_not(live),
                                            axis=(1, 2)).astype(jnp.int32)
    return skipped


def telemetry_partition_specs(axis_name: str | None = "data",
                              model_axis: str | None = None):
    """PartitionSpecs of a ChunkTelemetry on a lane (× model) mesh.

    The per-lane leaves shard on the lane axis (last); the tile leaf
    shards on its batch-*block* axis, which nests inside the lane axis
    (device-local blocks concatenate to the global block list).  With a
    ``model_axis`` the per-lane counts stay data-sharded only — every
    model peer derives them from the *full* gathered spike vector, so
    they are replicated over the model axis — while the tile leaf
    concatenates per-shard skip counts on the block axis, data-outer /
    model-inner: each model peer counts the tile pairs of its own weight
    shard's contraction geometry.  No leaf looks across devices, so the
    record composes with the engines' ``shard_map`` chunk.
    """
    from jax.sharding import PartitionSpec as P
    p = P(None, None, axis_name)
    tiles_axes = axis_name if model_axis is None else (axis_name, model_axis)
    return ChunkTelemetry(n_spk=p, n_en=p,
                          tiles_skipped=P(None, None, tiles_axes))


def concat_telemetry(chunks) -> ChunkTelemetry:
    """Concatenate per-chunk records along the step axis.

    Telemetry is per-step, so the concatenation over any split of a
    window is bit-identical to the one-shot record — the same invariant
    the carried lane state satisfies.
    """
    chunks = list(chunks)
    return ChunkTelemetry(*[jnp.concatenate([getattr(c, f) for c in chunks],
                                            axis=0)
                            for f in ChunkTelemetry._fields])
