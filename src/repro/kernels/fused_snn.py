"""Pallas TPU megakernel: fused Poisson-encode → LIF *stack* in ONE launch.

The paper's efficiency argument (§V-B) is that the encoder and the LIF
datapath share a chip, so the spike stream never crosses an external-memory
boundary.  The staged kernels (poisson_encode.py + lif_step.py) break that
property on TPU: the full ``(T, B, N)`` spike tensor round-trips through
HBM between every pair of launches — and for multi-layer stacks the
inter-layer spike traffic dominates (Bouvier et al. 2020; Abderrahmane et
al. 2019).  This kernel restores the RTL's event-stream locality for an
**arbitrary layer stack**, and makes the paper's two *sparsity* mechanisms
— Poisson spike sparsity and active pruning — real skipped compute:

  * pixels and the per-pixel xorshift32 PRNG lanes are loaded into VMEM
    once and stay there for the whole chunk (the free-running LFSR bank of
    Fig. 2);
  * every layer's weight matrix is resident as the paper's native 8-bit
    fixed-point codes: the 9-bit signed weight codes are **packed into two
    int8 planes** (``hi = w >> 1``, ``lo = w & 1``; see
    :func:`pack_weights`) that feed the MXU directly as two int8 passes
    with int32 accumulation (:func:`spike_dot`) — 2 bytes/weight resident,
    which is what lets deep/wide stacks fit the VMEM residency budget;
  * the per-layer Σ W·S contraction is tiled 128×128 and **event-driven**
    (``sparse_skip=True``): a K-tile whose spike block is all-zero, or an
    output tile whose enable block is fully pruned, is skipped via
    ``lax.cond`` — no MXU pass — instead of merely having its
    result masked.  Skipped tiles contribute exactly zero to the integer
    accumulator and zero executed adds, so the sparse path is bit-identical
    to the dense one (results AND energy counters; integer addition is
    exact and associative);
  * each timestep generates the input spike vector in registers/VMEM and
    walks it through a *static Python layer loop*; the fired vector feeds
    the next layer directly.  Inter-layer spikes are **never written to
    HBM**.
  * ``streamed=True`` runs stacks that exceed the residency budget in one
    launch anyway: the packed weight planes stay in HBM and a
    **double-buffered DMA pipeline** copies one 128-row K-slab at a time
    into a 2-slot VMEM scratch, with the next slab's copy overlapped
    against the current slab's contraction (and the tile-skip predicates
    still gating the compute).
  * the kernel is **resumable**: it accepts initial per-layer membrane and
    enable state, per-layer peak-membrane accumulators, the PRNG lanes,
    the spike-count / first-spike registers and a per-lane step counter,
    and returns the advanced versions — so a T-step window split into
    chunks is bit-identical to one launch (serve.snn_engine streams
    through this).  The carried peak accumulator is what lets the
    ``membrane`` readout stream without a per-step trace buffer.
  * every launch also emits the **telemetry side channel**
    (``core.telemetry.ChunkTelemetry``): per-step, per-layer input-spike
    counts and prune-enable occupancy per lane, plus the per-block MXU
    tile pairs the event-driven contraction skipped — the measured
    activity the serving layer's adaptive dispatch controller consumes.
    The jnp backends re-derive the identical record, so telemetry is
    bit-checkable exactly like the datapath.
  * **conv-pool layers** (``core.layers.ConvPool``; :func:`stack_geometry`)
    run inside the same launch, a prefix of the stack: each keeps its
    spike and neuron maps as 2-D arrays grouped by row residue, so every
    input run a (row class, row phase, tap row) contraction reads is a
    contiguous row slice; the tap rows side by side go through the same
    event-driven :func:`_tiled_contraction` against banded planes packed
    once per weight set (:func:`pack_stack`), and the pooled current is
    a max over the column phases (plane halves) and the row phases
    (:func:`_conv_stage`).
  * optionally the kernel also runs the serving-layer **stability gate**
    per step (``gated=True``): a lane whose running prediction has been
    stable for ``patience`` steps freezes in place (PRNG, membranes,
    counters), mirroring ``serve.snn_engine.stream_chunk``'s jnp fallback
    bit-for-bit.

Only per-neuron outputs come back: final-layer spike counts, first-spike
times and membrane trace, per-layer membrane/enable state, the per-step
executed-add count (energy side channel, summed over layers) and the
advanced PRNG state.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["fused_snn_stack_pallas", "pack_weights", "stack_vmem_bytes",
           "layer_shard_ways", "partial_contraction_pallas",
           "block_b_for", "VMEM_BUDGET_BYTES", "DEFAULT_BLOCK_B", "LANE",
           "ConvGeom", "FlatGeom", "stack_geometry", "map_positions",
           "to_map", "from_map", "pack_stack", "layer_tile_pairs"]

DEFAULT_BLOCK_B = 8     # batch tile per program
LANE = 128              # TPU lane width: every neuron axis pads to this

# Conservative share of the ~16 MB/core VMEM the resident stack may claim
# (weights + state + trace + temporaries).  ``core.snn.resolve_backend``
# streams the weights (``fused_streamed``) or falls back to staged when
# the estimate exceeds this.
VMEM_BUDGET_BYTES = 12 << 20


def _pad128(n: int) -> int:
    return n + (-n) % LANE


def block_b_for(batch: int | None) -> int:
    """Batch block actually launched for a ``batch``-row tile.

    The default block, shrunk to the 8-row-sublane-padded batch when that
    is smaller — the single source of truth shared by the launcher
    (kernels.ops.fused_snn_stack_op) and the VMEM feasibility estimate
    (core.snn.fused_unsupported_reason), so the footprint a sharded
    caller validates with ``local_batch`` is exactly the block its
    per-device launch allocates.  With the current 8-row default the two
    coincide for every batch; the clamp matters the day DEFAULT_BLOCK_B
    grows past the sublane minimum.
    """
    if batch is None:
        return DEFAULT_BLOCK_B
    return min(DEFAULT_BLOCK_B, max(8, int(batch) + (-int(batch)) % 8))


def pack_weights(w_q: jax.Array) -> jax.Array:
    """Pack 9-bit signed weight codes into two int8 planes.

    ``w = 2*hi + lo`` with ``hi = w >> 1`` (arithmetic) and ``lo = w & 1``
    — exact for every code in the paper's signed 9-bit range [-256, 255]
    (``core.snn.quantize_params``' output contract), which is what lets
    the resident stack live at 2 bytes/weight instead of int16 + a
    whole-matrix int32 cast.  Returns ``(2, n_in, n_out)`` int8 with
    plane 0 = hi, plane 1 = lo; the kernels contract each plane on the
    MXU (:func:`spike_dot`).
    """
    w32 = w_q.astype(jnp.int32)
    hi = jnp.right_shift(w32, 1)
    lo = w32 - 2 * hi                      # ∈ {0, 1}
    return jnp.stack([hi.astype(jnp.int8), lo.astype(jnp.int8)])


def spike_dot(x: jax.Array, packed: jax.Array) -> jax.Array:
    """Exact Σ W·S of 0/1 spikes ``x`` (b, k) against int8 planes (2, k, n).

    Two int8 MXU passes with int32 accumulation, ``2·(x·hi) + x·lo`` —
    the form the TPU MXU takes natively (it has no int32×int32 matmul),
    exact for any contraction length because the operands are the packed
    codes themselves and integer accumulation does not round.
    """
    xi = x.astype(jnp.int8)

    def dot(w):
        return jax.lax.dot_general(xi, w, (((1,), (0,)), ((), ())),
                                   preferred_element_type=jnp.int32)

    return 2 * dot(packed[0]) + dot(packed[1])


def _any(a: jax.Array) -> jax.Array:
    """Scalar "any nonzero" of a 0/1 tile as an int32 reduction (Mosaic
    cannot relayout an i1 vector into the scalar ``jnp.any`` needs)."""
    return jnp.max(a.astype(jnp.int32)) > 0


def layer_shard_ways(layer_sizes, model_shards: int):
    """Effective model-axis shard count per layer (len = n_layers).

    A layer's output-neuron dimension shards ``model_shards``-way only
    when the RAW width divides evenly — contiguous column slices of
    identical width are what make the sharded integer contraction
    concatenate back to the single-device result bit-for-bit.  A layer
    that doesn't divide (e.g. the 10-class head on a 4-way axis)
    replicates instead: every model peer holds its full weight matrix,
    computes the identical output redundantly, and skips the spike
    exchange entirely.  Shared by the VMEM feasibility estimate, the
    sharded stack step (``core.snn.snn_int_stack_step_sharded``) and the
    engine's per-layer weight placement, so all three agree on which
    layers actually split.
    """
    if model_shards <= 1:
        return tuple(1 for _ in layer_sizes[1:])
    return tuple(int(model_shards) if int(n) % int(model_shards) == 0 else 1
                 for n in layer_sizes[1:])


class ConvGeom(NamedTuple):
    """Static layout of one conv-pool layer inside the stack kernel.

    A spike or neuron map of shape (C, H, W) lives, per batch block, as a
    2-D array: map row ``i`` of lane ``b`` at row ``((i % m) * hs + i // m)
    * bB + b``, column ``j * C + c`` (padded to 128).  Rows grouped by
    their residue mod ``m`` make every slice the stage reads a contiguous
    run of 8-row blocks: the input map is split ``m_in = pool * m_out``
    ways, so the pre-pool rows ``pool * y + u`` of the output rows
    ``y = m_out * k + q`` read input rows ``s + m_in * k``, ``s = pool*q +
    u + dy`` — one residue class, consecutive ``k``.  The last conv layer
    has ``m_out = 1``.
    """

    k: int
    pool: int
    c_in: int
    c_out: int
    h_in: int
    w_in: int
    hp: int
    wp: int
    m_in: int
    m_out: int
    hs_in: int       # stored rows a residue class, input map
    hs_out: int      # the same, pooled output map
    kc: int          # input map columns: w_in * c_in padded to 128
    nh: int          # output map columns: wp * c_out padded to 128

    def in_map(self):
        return (self.c_in, self.h_in, self.w_in, self.m_in, self.hs_in,
                self.kc)

    def out_map(self):
        return (self.c_out, self.hp, self.wp, self.m_out, self.hs_out,
                self.nh)


class FlatGeom(NamedTuple):
    """A dense layer reading the last conv layer's map: its input vector
    is the map's row blocks side by side, (bB, m * hs * nh)."""

    c: int
    h: int
    w: int
    m: int
    hs: int
    nh: int

    def in_map(self):
        return (self.c, self.h, self.w, self.m, self.hs, self.nh)

    @property
    def k_in(self) -> int:
        return self.m * self.hs * self.nh


def stack_geometry(n_in: int, layers) -> tuple | None:
    """Per layer: ``ConvGeom`` for a conv-pool layer, ``FlatGeom`` for the
    dense layer after the last one, None for any other dense layer; or
    None for a stack with no conv-pool layer (the dense kernel as is)."""
    from ..core.layers import ConvPool
    if layers is None or not any(isinstance(d, ConvPool) for d in layers):
        return None
    n_conv = sum(isinstance(d, ConvPool) for d in layers)
    geoms: list = [None] * len(layers)
    m_out, hs_out = 1, None
    for l in range(n_conv - 1, -1, -1):          # the splits run backwards
        d = layers[l]
        c_in, h_in, w_in = d.in_shape
        c_out, hp, wp = d.out_shape
        if hs_out is None:
            hs_out = hp
        m_in = d.pool * m_out
        top = d.pool * (m_out - 1) + d.pool - 1 + d.kernel - 1
        hs_in = max(-(-h_in // m_in), hs_out + top // m_in)
        geoms[l] = ConvGeom(
            k=d.kernel, pool=d.pool, c_in=c_in, c_out=c_out, h_in=h_in,
            w_in=w_in, hp=hp, wp=wp, m_in=m_in, m_out=m_out, hs_in=hs_in,
            hs_out=hs_out, kc=_pad128(w_in * c_in), nh=_pad128(wp * c_out))
        m_out, hs_out = m_in, hs_in
    last = geoms[n_conv - 1]
    geoms[n_conv] = FlatGeom(c=last.c_out, h=last.hp, w=last.wp, m=1,
                             hs=last.hs_out, nh=last.nh)
    return tuple(geoms)


def map_positions(c: int, h: int, w: int, m: int, hs: int):
    """Row block and column of every flat (C, H, W) index in a map."""
    f = np.arange(c * h * w)
    ci, i, j = f // (h * w), (f // w) % h, f % w
    return (i % m) * hs + i // m, j * c + ci


def to_map(a: jax.Array, geom, bB: int, fill=0) -> jax.Array:
    """(Bp, C*H*W) flat rows -> the kernel's (Bp // bB * rows, cols) map,
    ``geom = (C, H, W, m, hs, cols)``; padding holds ``fill``."""
    c, h, w, m, hs, cols = geom
    rb, col = map_positions(c, h, w, m, hs)
    n = c * h * w
    src = np.full((m * hs, cols), n, np.int32)        # n: the fill column
    src[rb, col] = np.arange(n)
    nb = a.shape[0] // bB
    a = jnp.concatenate([a.reshape(nb, bB, n),
                         jnp.full((nb, bB, 1), fill, a.dtype)], axis=2)
    out = jnp.take(a, src.reshape(-1), axis=2).reshape(nb, bB, m * hs, cols)
    return jnp.transpose(out, (0, 2, 1, 3)).reshape(nb * m * hs * bB, cols)


def from_map(a: jax.Array, geom, bB: int) -> jax.Array:
    """Inverse of :func:`to_map`: the (Bp, C*H*W) flat rows."""
    c, h, w, m, hs, cols = geom
    rb, col = map_positions(c, h, w, m, hs)
    nb = a.shape[0] // (m * hs * bB)
    vals = a.reshape(nb, m * hs, bB, cols)[:, rb, :, col]   # (n, nb, bB)
    return jnp.transpose(vals, (1, 2, 0)).reshape(nb * bB, -1)


def _conv_planes(w_q: jax.Array, g: ConvGeom):
    """The conv-pool stage's resident operands, built once per weight set.

    ``planes`` (2, k * kc, pool * nh) int8: row ``dy * kc + j * c_in +
    c'``, column ``v * nh + x * c_out + c`` holds W[c, c', dy, j - (pool *
    x + v)] (zero off the k-tap band), so the dy-concatenated input rows
    times it are the pre-pool currents of column phase ``v``.  ``field``
    (k * kc, 128) int8 counts, per output column x, the taps of both
    phases each input reaches (its product is the spikes in the pre-pool
    fields); ``per_x`` (nh, 128) int8 sums a neuron column over channels.
    """
    k, p = g.k, g.pool
    c, ci, dy, dx, x, v = np.meshgrid(
        np.arange(g.c_out), np.arange(g.c_in), np.arange(k), np.arange(k),
        np.arange(g.wp), np.arange(p), indexing="ij")
    j = p * x + v + dx
    rows = (dy * g.kc + j * g.c_in + ci).ravel()
    cols = (v * g.nh + x * g.c_out + c).ravel()
    src = (((c * g.c_in + ci) * k + dy) * k + dx).ravel()
    w = jnp.zeros((k * g.kc, p * g.nh), jnp.int32).at[rows, cols].set(
        w_q.reshape(-1).astype(jnp.int32)[src])
    field = np.zeros((k * g.kc, LANE), np.int8)
    per_x = np.zeros((g.nh, LANE), np.int8)
    for xx in range(g.wp):
        for vv in range(p):
            for jj in range(p * xx + vv, p * xx + vv + k):
                for dd in range(k):
                    field[dd * g.kc + jj * g.c_in:
                          dd * g.kc + (jj + 1) * g.c_in, xx] += 1
        per_x[xx * g.c_out:(xx + 1) * g.c_out, xx] = 1
    return pack_weights(w), jnp.asarray(field), jnp.asarray(per_x)


def _flat_plane(w_q: jax.Array, g: FlatGeom) -> jax.Array:
    """A dense layer's codes with its rows moved from the (C, H, W)
    flatten to the kernel's row-blocks-side-by-side input vector."""
    rb, col = map_positions(g.c, g.h, g.w, g.m, g.hs)
    n_out = w_q.shape[1]
    w = jnp.zeros((g.k_in, _pad128(n_out)), w_q.dtype)
    return pack_weights(w.at[rb * g.nh + col, :n_out].set(w_q))


def pack_stack(weights, layers) -> tuple:
    """Every layer's resident kernel operands for a stack with conv-pool
    layers, built once when the weights are placed: per layer a tuple —
    ``(planes,)`` dense, ``(planes, field, per_x)`` conv-pool."""
    n_in = int(np.prod(layers[0].in_shape))
    geoms = stack_geometry(n_in, layers)
    out = []
    for w, g in zip(weights, geoms):
        if isinstance(g, ConvGeom):
            out.append(_conv_planes(w, g))
        elif isinstance(g, FlatGeom):
            out.append((_flat_plane(w, g),))
        else:
            k, n = w.shape
            pad = jnp.zeros((_pad128(k), _pad128(n)), w.dtype)
            out.append((pack_weights(pad.at[:k, :n].set(w)),))
    return tuple(out)


def layer_tile_pairs(layer_sizes, geoms=None) -> tuple[int, ...]:
    """128x128 tile pairs each layer's contraction holds, per batch block
    and step: K tiles x N tiles, dense; for a conv-pool layer that, over
    its m_out x pool (row class, row phase) contractions of k * kc
    inputs into pool * nh pre-pool columns."""
    out = []
    for l, (k, n) in enumerate(zip(layer_sizes[:-1], layer_sizes[1:])):
        g = None if geoms is None else geoms[l]
        if isinstance(g, ConvGeom):
            out.append(g.m_out * g.pool * (g.k * g.kc // LANE)
                       * (g.pool * g.nh // LANE))
        else:
            k_pad = g.k_in if isinstance(g, FlatGeom) else _pad128(int(k))
            out.append((k_pad // LANE) * (_pad128(int(n)) // LANE))
    return tuple(out)


def stack_vmem_bytes(layer_sizes, block_b: int = DEFAULT_BLOCK_B,
                     num_steps: int = 1, streamed: bool = False,
                     model_shards: int = 1, layers=None) -> int:
    """Estimate of the kernel's resident VMEM footprint for one program.

    Counts the padded int8-packed weight planes (2 bytes/weight resident;
    replaced by the 2-slot DMA slab scratch when ``streamed``), pixels +
    PRNG lanes and the pixels widened to int32 for the comparator,
    per-layer membrane/enable state, the final-layer trace block, the
    per-step lane-dense side-channel rows and a working-set allowance for
    the per-step spike/current temporaries.  Kept in
    lockstep with the launcher: same padding, same ``block_b_for`` block,
    same scratch shapes as :func:`fused_snn_stack_pallas` allocates.

    With ``model_shards > 1`` the estimate is the PER-DEVICE footprint on
    a model axis: each layer that divides (:func:`layer_shard_ways`)
    contributes only its output-column shard — weight planes, membrane /
    enable state and current all shrink by the shard count (padded back
    to the 128-lane boundary), while the input-spike side stays full
    (every device holds the gathered spike vector).  Layers that don't
    divide stay whole.  ``model_shards=1`` reproduces the historical
    single-device estimate exactly.

    ``layers`` with conv-pool descriptors (resident, unsharded only)
    counts the conv stages instead: their planes and counting matrices,
    the maps' state, the pixel map and a stage's concatenated input rows
    and pre-pool currents.
    """
    geoms = stack_geometry(int(layer_sizes[0]), layers)
    if geoms is not None:
        return _conv_stack_vmem_bytes(layer_sizes, geoms, block_b,
                                      num_steps)
    sizes_raw = [int(n) for n in layer_sizes]
    ways = layer_shard_ways(sizes_raw, model_shards)
    sizes = [_pad128(n) for n in sizes_raw]
    shard_outs = [_pad128(n // w) for n, w in zip(sizes_raw[1:], ways)]
    bB = block_b
    max_out = max(shard_outs)
    total = sizes[0] * bB * (1 + 4 + 4)          # pixels + PRNG + i32 pixels
    for n_in, n_out in zip(sizes[:-1], shard_outs):
        if not streamed:
            total += n_in * n_out * 2                    # packed int8 hi+lo
        total += bB * n_out * (4 + 4 + 1 + 4)            # v + v_peak + en + current
    if streamed:
        total += 2 * 2 * LANE * max_out                  # 2-slot DMA slabs
    total += num_steps * bB * shard_outs[-1] * 4         # v_trace block
    total += num_steps * bB * LANE * 4                   # side-channel rows
    total += bB * max(sizes[0], max_out) * 8             # spike temporaries
    return total


def _conv_stack_vmem_bytes(layer_sizes, geoms, bB: int,
                           num_steps: int) -> int:
    g0 = geoms[0]
    total = g0.m_in * g0.hs_in * bB * g0.kc * (1 + 4 + 4)  # pixel map
    widest = 0
    for k, n_out, g in zip(layer_sizes[:-1], layer_sizes[1:], geoms):
        if isinstance(g, ConvGeom):
            rows = g.m_out * g.hs_out * bB
            total += 2 * g.k * g.kc * g.pool * g.nh          # planes
            total += (g.k * g.kc + g.nh) * LANE              # counters
            total += rows * g.nh * (4 + 4 + 1 + 4)           # state + current
            widest = max(widest, g.hs_out * bB * (g.k * g.kc + 2 * g.pool
                                                  * g.nh) * 4)
        else:
            k_in = g.k_in if isinstance(g, FlatGeom) else _pad128(int(k))
            n_pad = _pad128(int(n_out))
            total += k_in * n_pad * 2 + bB * n_pad * (4 + 4 + 1 + 4)
            widest = max(widest, bB * k_in * 4)
    total += num_steps * bB * _pad128(int(layer_sizes[-1])) * 4  # trace
    total += num_steps * bB * LANE * 4                   # side-channel rows
    return total + 2 * widest                            # stage temporaries


def _first_argmax(x: jax.Array, n_true: int) -> jax.Array:
    """First index of the row max — matches jnp.argmax tie-breaking.

    x: (bB, n) int32.  Returns (bB, 1) int32.  Implemented with iota+min so
    it lowers cleanly inside a Pallas TPU kernel.
    """
    bB, n = x.shape
    m = jnp.max(x, axis=-1, keepdims=True)
    col = jax.lax.broadcasted_iota(jnp.int32, (bB, n), 1)
    return jnp.min(jnp.where(x == m, col, n_true), axis=-1, keepdims=True)


def _tiled_contraction(x, en, read_tile, n_out_pad: int, sparse_skip: bool,
                       pre_k=None):
    """Event-driven Σ W·S over 128×128 tiles (K-outer, N-inner).

    ``x``: (bB, n_in_pad) bool spikes; ``en``: (bB, n_out_pad) bool enable;
    ``read_tile(kt, nt)`` returns the packed (2, LANE, LANE) int8 weight
    tile; ``pre_k(kt)`` (streamed mode) runs unconditionally at the top of
    each K iteration — it advances the DMA double buffer, so the K-outer
    order is what lets one 2-slot scratch cover arbitrarily wide layers.
    With ``sparse_skip`` each (kt, nt) tile pair runs under a
    ``lax.cond``: skipped when the K-tile carries no spike in any lane OR
    the output tile is fully pruned across the block.  Both predicates
    only ever skip tiles whose contribution is exactly zero (no spikes →
    zero rows; fully pruned → the result is zeroed by the enable mask),
    so dense and sparse execution are bit-identical — the skip saves the
    MXU passes, not correctness (integer addition is exact, so the
    K-tiled accumulation order cannot change results either).

    Returns ``(result, skipped)`` where ``skipped`` is the scalar i32
    count of tile pairs the predicates skipped this call (0 when
    ``sparse_skip`` is off) — the telemetry side channel's per-block
    tile counter, emitted instead of staying a kernel-private decision.
    """
    bB, n_in_pad = x.shape
    nkt, nnt = n_in_pad // LANE, n_out_pad // LANE
    zeros = jnp.zeros((bB, LANE), jnp.int32)
    accs = [zeros] * nnt
    skipped = jnp.int32(0)
    for kt in range(nkt):
        if pre_k is not None:
            pre_k(kt)
        x_t = x[:, kt * LANE:(kt + 1) * LANE]
        for nt in range(nnt):
            en_t = en[:, nt * LANE:(nt + 1) * LANE]

            def tile(x_t=x_t, kt=kt, nt=nt):
                return spike_dot(x_t, read_tile(kt, nt))

            if sparse_skip:
                live = jnp.logical_and(_any(x_t), _any(en_t))
                skipped = skipped + (1 - live.astype(jnp.int32))
                accs[nt] = accs[nt] + jax.lax.cond(live, tile,
                                                   lambda: zeros)
            else:
                accs[nt] = accs[nt] + tile()
    out = accs[0] if nnt == 1 else jnp.concatenate(accs, axis=-1)
    return out, skipped


def _partial_kernel(x_ref, en_ref, w_ref, out_ref, skip_ref, *,
                    sparse_skip: bool):
    x = x_ref[...] != 0
    en = en_ref[...] != 0

    def read_tile(kt, nt):
        return w_ref[:, kt * LANE:(kt + 1) * LANE, nt * LANE:(nt + 1) * LANE]

    cur, skipped = _tiled_contraction(x, en, read_tile, w_ref.shape[2],
                                      sparse_skip)
    out_ref[...] = cur
    skip_ref[pl.program_id(0)] = skipped         # whole (n_blocks,) SMEM


def partial_contraction_pallas(x_u8: jax.Array, en_u8: jax.Array,
                               w_packed: jax.Array, *,
                               sparse_skip: bool = True,
                               block_b: int = DEFAULT_BLOCK_B,
                               interpret: bool = False):
    """One layer's per-device partial Σ W·S over an output-column shard.

    The model-axis datapath building block: each device calls this with
    the FULL input-spike vector ``x_u8`` (B, n_in_pad) and the packed
    weight planes of ITS output-neuron shard ``w_packed``
    (2, n_in_pad, n_out_shard_pad) — concatenating the per-device results
    over the model axis in shard order IS the single-device contraction,
    bit-for-bit, because the column shards are disjoint and integer
    accumulation is exact.  Unlike :func:`fused_snn_stack_pallas` this is
    one layer, one step: the spike exchange between layers happens
    OUTSIDE the launch (``jax.lax.all_gather`` under ``shard_map`` in
    ``core.snn.snn_int_stack_step_sharded``) — kernel-level inter-chip
    RDMA collectives are TPU-only and would break the CPU-interpretable
    bit-identity contract every backend here honors.

    Same event-driven tile skipping as the megakernel
    (:func:`_tiled_contraction`, ``en_u8`` = the shard's enable columns),
    and the same telemetry: returns ``(current, skipped)`` with
    ``current`` (B, n_out_shard_pad) int32 and ``skipped`` (n_blocks,)
    int32 — this shard's skipped tile pairs per batch block, which the
    model-sharded telemetry record concatenates on the block axis.
    """
    B, n_in_pad = x_u8.shape
    n_out_pad = w_packed.shape[2]
    bB = block_b
    grid = (pl.cdiv(B, bB),)
    n_blocks = grid[0]
    kernel = functools.partial(_partial_kernel, sparse_skip=sparse_skip)
    out, skipped = pl.pallas_call(
        kernel, grid=grid,
        in_specs=[
            pl.BlockSpec((bB, n_in_pad), lambda i: (i, 0)),
            pl.BlockSpec((bB, n_out_pad), lambda i: (i, 0)),
            pl.BlockSpec(w_packed.shape, lambda i: (0, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((bB, n_out_pad), lambda i: (i, 0)),
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, n_out_pad), jnp.int32),
            jax.ShapeDtypeStruct((n_blocks,), jnp.int32),
        ],
        interpret=interpret, name="snn_partial_contraction")(
        x_u8, en_u8, w_packed)
    return out, skipped


def _int8_dot(a: jax.Array, b: jax.Array) -> jax.Array:
    """Exact int32 product of small non-negative integers held as int8."""
    return jax.lax.dot_general(a.astype(jnp.int8), b,
                               (((1,), (0,)), ((), ())),
                               preferred_element_type=jnp.int32)


def _lane_sum(rows: jax.Array, bB: int) -> jax.Array:
    """(n * bB, 1) counts of a map's rows -> (bB, 1) sums per lane."""
    if rows.shape[0] == bB:
        return rows
    out = rows[:bB]
    for r in range(bB, rows.shape[0], bB):
        out = out + rows[r:r + bB]
    return out


def _conv_stage(x, en, w_ref, field_ref, per_x_ref, g: ConvGeom, bB: int,
                sparse_skip: bool):
    """One conv-pool layer on the maps of a batch block (``ConvGeom``).

    For each output row class q and row phase u, the k input row runs the
    taps dy read are laid side by side, (hs_out * bB, k * kc), and
    contracted against the layer's planes by :func:`_tiled_contraction`
    (the same event-driven tile skipping, the output tile's enables being
    class q's, once per column phase): the pre-pool currents of both
    column phases.  Their max over the phases and over u is the pooled
    current.  The executed adds come from the same rows: ``field`` counts
    the spikes in each pooled neuron column's pre-pool fields, ``per_x``
    its enabled channels.  Returns (currents (m_out * hs_out * bB, nh),
    skipped tile pairs, adds (bB, 1)).
    """
    xi = x.astype(jnp.int32)
    en_i = en.astype(jnp.int32)
    hsb = g.hs_out * bB

    def read_tile(kt, nt):
        return w_ref[:, kt * LANE:(kt + 1) * LANE, nt * LANE:(nt + 1) * LANE]

    field_w, per_x_w = field_ref[...], per_x_ref[...]
    pooled, adds_rows, skipped = [], None, jnp.int32(0)
    for q in range(g.m_out):
        en_q = en_i[q * hsb:(q + 1) * hsb]
        en_pre = jnp.concatenate([en_q] * g.pool, axis=1)
        best, field = None, None
        for u in range(g.pool):
            runs = []
            for dy in range(g.k):
                s = g.pool * q + u + dy
                r0 = ((s % g.m_in) * g.hs_in + s // g.m_in) * bB
                runs.append(xi[r0:r0 + hsb])
            xcat = jnp.concatenate(runs, axis=1)
            pre, sk = _tiled_contraction(xcat, en_pre, read_tile,
                                         g.pool * g.nh, sparse_skip)
            skipped = skipped + sk
            for v in range(g.pool):
                a = pre[:, v * g.nh:(v + 1) * g.nh]
                best = a if best is None else jnp.maximum(best, a)
            f = _int8_dot(xcat, field_w)
            field = f if field is None else field + f
        rows = jnp.sum(field * _int8_dot(en_q, per_x_w), axis=1,
                       keepdims=True)
        adds_rows = rows if adds_rows is None else adds_rows + rows
        pooled.append(best)
    cur = pooled[0] if g.m_out == 1 else jnp.concatenate(pooled, axis=0)
    return cur, skipped, _lane_sum(adds_rows, bB)


def _stack_kernel(*refs, num_layers: int, chunk_steps: int, window_steps: int,
                  decay_shift: int, v_threshold: int, v_rest: int,
                  v_min: int, v_max: int, active_pruning: bool,
                  gated: bool, patience: int, readout: str,
                  sparse_skip: bool, streamed: bool, geoms=None):
    L = num_layers
    it = iter(refs)
    px_ref, st_ref = next(it), next(it)
    w_refs = [next(it) for _ in range(L)]   # packed (2, K, N) int8 planes
    # conv-pool layers: their (field, per_x) counting matrices
    aux_refs = [(next(it), next(it)) if isinstance(g, ConvGeom) else None
                for g in (geoms or (None,) * L)]
    v_refs = [next(it) for _ in range(L)]
    en_refs = [next(it) for _ in range(L)]
    vp_refs = [next(it) for _ in range(L)]  # per-layer peak membranes
    cnt_ref, first_ref, steps_ref = next(it), next(it), next(it)
    if gated:
        act_ref, gprev_ref, gstreak_ref = next(it), next(it), next(it)
    cnt_out, vtr_out, first_out, side_out, st_out = (
        next(it), next(it), next(it), next(it), next(it))
    v_outs = [next(it) for _ in range(L)]
    en_outs = [next(it) for _ in range(L)]
    vp_outs = [next(it) for _ in range(L)]
    ttile_out = next(it)
    steps_out = next(it)
    if gated:
        act_out, gprev_out, gstreak_out = next(it), next(it), next(it)

    blk = pl.program_id(0)           # batch block: row of the tile counters
    # widened once per chunk: the chip's compiler has no 8-bit compare
    px = px_ref[...].astype(jnp.int32)                 # (bB, n_in)
    n_pads = [w.shape[2] for w in w_refs]              # padded layer widths
    n_out = cnt_ref.shape[1]
    # streamed mode: (layer, K-slab) pairs in execution order — the DMA
    # pipeline walks them with a 2-slot double buffer each step.
    slabs = [(l, kt) for l in range(L)
             for kt in range(w_refs[l].shape[1] // LANE)]

    bB = cnt_ref.shape[0]
    side_col = jax.lax.broadcasted_iota(jnp.int32, (bB, LANE), 1)

    def side_row(cols):
        """Pack (bB, 1) counters into one lane-dense (bB, LANE) row."""
        row = jnp.zeros(side_col.shape, jnp.int32)
        for c, v in enumerate(cols):
            row = jnp.where(side_col == c, v, row)
        return row

    def run(w_scr=None, sems=None):
        def slab_dma(i: int):
            l, kt = slabs[i]
            slot = i % 2
            return pltpu.make_async_copy(
                w_refs[l].at[:, pl.ds(kt * LANE, LANE), pl.ds(0, n_pads[l])],
                w_scr.at[slot, :, :, pl.ds(0, n_pads[l])],
                sems.at[slot])

        # enables and the gate's active flag ride the loop as int32 0/1:
        # Mosaic cannot carry i1 vectors through the step loop
        carry0 = (
            st_ref[...],
            tuple(v_refs[l][...] for l in range(L)),
            tuple(en_refs[l][...].astype(jnp.int32) for l in range(L)),
            tuple(vp_refs[l][...] for l in range(L)),
            cnt_ref[...],
            first_ref[...],
            steps_ref[...],                            # (bB, 1) i32
        )
        if gated:
            carry0 = carry0 + (act_ref[...], gprev_ref[...],
                               gstreak_ref[...])

        def body(t, carry):
            if gated:
                (s, vs, ens, vps, cnt, first, steps, act, gprev,
                 gstreak) = carry
            else:
                s, vs, ens, vps, cnt, first, steps = carry

            # --- encoder: xorshift32 step + 8-bit comparator (Fig. 2) ----
            s_new = s ^ (s << 13)
            s_new = s_new ^ (s_new >> 17)
            s_new = s_new ^ (s_new << 5)
            r = (s_new >> 24).astype(jnp.int32)        # top byte, 0..255
            x = px > r                                 # (bB, n_in) on-chip
            if streamed:
                slab_dma(0).start()                    # warm the pipeline

            # --- static layer loop: spikes stay in VMEM between layers ---
            adds_t = jnp.zeros(steps.shape, jnp.int32)  # (bB, 1)
            new_vs, new_ens, new_vps = [], [], []
            side = []                                  # (bB, 1) columns
            base = 0                                   # streamed slab cursor
            for l in range(L):
                en = ens[l] != 0
                g = None if geoms is None else geoms[l]
                if isinstance(g, ConvGeom):
                    cur, skipped, adds_l = _conv_stage(
                        x, en, w_refs[l], *aux_refs[l], g, bB, sparse_skip)
                else:
                    if isinstance(g, FlatGeom):
                        # the last map's row blocks side by side, one row
                        # a lane
                        xi = x.astype(jnp.int32)
                        x = jnp.concatenate(
                            [xi[r:r + bB] for r in range(0, xi.shape[0], bB)],
                            axis=1)
                    if streamed:
                        # Double-buffered HBM→VMEM slab pipeline: each K
                        # iteration kicks off the NEXT slab's copy (into
                        # the other scratch slot) before waiting on the
                        # current one, so the copy of slab p+1 overlaps
                        # the contraction against slab p.  ``base``
                        # indexes this layer's first entry in the step's
                        # (layer, K-slab) order.
                        def pre_k(kt, base=base):
                            if base + kt + 1 < len(slabs):
                                slab_dma(base + kt + 1).start()
                            slab_dma(base + kt).wait()

                        def read_tile(kt, nt, l=l, base=base):
                            return w_scr[(base + kt) % 2, :, :,
                                         nt * LANE:(nt + 1) * LANE]
                        base += w_refs[l].shape[1] // LANE
                    else:
                        pre_k = None

                        def read_tile(kt, nt, l=l):
                            return w_refs[l][:, kt * LANE:(kt + 1) * LANE,
                                             nt * LANE:(nt + 1) * LANE]

                    cur, skipped = _tiled_contraction(x, en, read_tile,
                                                      n_pads[l], sparse_skip,
                                                      pre_k)
                    adds_l = None
                cur = jnp.where(en, cur, 0)            # pruning clock-gate
                v_int = jnp.clip(vs[l] + cur, v_min, v_max)
                v_leak = v_int - (v_int >> decay_shift)
                fired = jnp.logical_and(v_leak >= v_threshold, en)
                v_new = jnp.where(fired, jnp.int32(v_rest), v_leak)
                v_new = jnp.where(en, v_new, vs[l])    # frozen when gated
                # per lane: a map's rows summed over its row blocks
                n_spk = _lane_sum(jnp.sum(x.astype(jnp.int32), axis=-1,
                                          keepdims=True), bB)
                n_en = _lane_sum(jnp.sum(en.astype(jnp.int32), axis=-1,
                                         keepdims=True), bB)
                # energy: adds executed = input spikes × enabled outputs
                # (a conv-pool stage counts its own, per receptive field).
                # Identical on the sparse path: a skipped tile pair has
                # either zero spikes or zero enabled outputs, so its
                # n_spk·n_en term of the Σ_{kt,nt} expansion is zero —
                # the dense product below already counts only executed
                # work.
                adds_t = adds_t + (n_spk * n_en if adds_l is None
                                   else adds_l)
                side += [n_spk, n_en]
                # per-block skipped tile pairs, unmasked in gated mode: it
                # records what the block's contraction actually executed,
                # and frozen lanes still sit in the block
                ttile_out[t, l, blk] = skipped
                if active_pruning:
                    en = jnp.logical_and(en, jnp.logical_not(fired))
                new_vs.append(v_new)
                new_ens.append(en.astype(jnp.int32))
                new_vps.append(jnp.maximum(vps[l], v_new))
                x = fired                              # next layer's input

            # --- final-layer readout registers ---------------------------
            cnt_new = cnt + x.astype(jnp.int32)
            first_new = jnp.where(
                jnp.logical_and(x, first == window_steps), steps, first)
            v_last = new_vs[-1]

            if gated:
                # stability gate, mirroring serve.snn_engine.stream_chunk's
                # jnp fallback bit-for-bit (same op order, tie-breaking).
                has_spike = jnp.max(cnt_new, axis=-1, keepdims=True) > 0
                if readout == "first_spike":
                    large = jnp.int32(1 << 24)
                    score = jnp.where(
                        cnt_new > 0, large + (window_steps - first_new),
                        jnp.clip(v_last, -large + 1, large - 1))
                    pred = _first_argmax(score, n_out)
                elif readout == "membrane":
                    # streamed peak-membrane readout off the carried
                    # accumulator — no trace buffer needed
                    pred = _first_argmax(new_vps[-1], n_out)
                else:                                  # count
                    pred = _first_argmax(cnt_new, n_out)
                streak_raw = jnp.where(pred == gprev, gstreak + 1, 0)
                done = streak_raw >= patience
                gprev_new = jnp.where(has_spike, pred, -1)
                gstreak_new = jnp.where(has_spike, streak_raw, 0)
                done = jnp.logical_and(done, has_spike)
                live = act != 0
                steps_new = steps + act
                still = jnp.logical_and(live, jnp.logical_not(done))
                still = jnp.logical_and(still, steps_new < window_steps)

                def keep(new, old):
                    if new.shape[0] == bB:
                        return jnp.where(live, new, old)
                    # a map: the lane's flag over its row blocks
                    rows = jnp.concatenate([act] * (new.shape[0] // bB),
                                           axis=0)
                    return jnp.where(rows != 0, new, old)

                s_new = keep(s_new, s)
                new_vs = [keep(nv, ov) for nv, ov in zip(new_vs, vs)]
                new_ens = [keep(ne, oe) for ne, oe in zip(new_ens, ens)]
                new_vps = [keep(nv, ov) for nv, ov in zip(new_vps, vps)]
                cnt_new = keep(cnt_new, cnt)
                first_new = keep(first_new, first)
                gprev_new = keep(gprev_new, gprev)
                gstreak_new = keep(gstreak_new, gstreak)
                vtr_out[t, :, :] = new_vs[-1]
                # frozen lanes execute nothing, so their executed-add and
                # telemetry rows are zero
                side_out[t, :, :] = keep(side_row([adds_t] + side), 0)
                return (s_new, tuple(new_vs), tuple(new_ens),
                        tuple(new_vps), cnt_new, first_new, steps_new,
                        still.astype(jnp.int32), gprev_new, gstreak_new)

            vtr_out[t, :, :] = v_last
            side_out[t, :, :] = side_row([adds_t] + side)
            return (s_new, tuple(new_vs), tuple(new_ens), tuple(new_vps),
                    cnt_new, first_new, steps + 1)

        carry_f = jax.lax.fori_loop(0, chunk_steps, body, carry0)
        if gated:
            (s_f, vs_f, ens_f, vps_f, cnt_f, first_f, steps_f, act_f, gp_f,
             gs_f) = carry_f
            act_out[...] = act_f
            gprev_out[...] = gp_f
            gstreak_out[...] = gs_f
        else:
            s_f, vs_f, ens_f, vps_f, cnt_f, first_f, steps_f = carry_f
        cnt_out[...] = cnt_f
        first_out[...] = first_f
        st_out[...] = s_f
        steps_out[...] = steps_f
        for l in range(num_layers):
            v_outs[l][...] = vs_f[l]
            en_outs[l][...] = ens_f[l].astype(en_outs[l].dtype)
            vp_outs[l][...] = vps_f[l]

    if streamed:
        max_out = max(n_pads)
        pl.run_scoped(
            run,
            w_scr=pltpu.VMEM((2, 2, LANE, max_out), jnp.int8),
            sems=pltpu.SemaphoreType.DMA((2,)))
    else:
        run()


def fused_snn_stack_pallas(pixels_u8: jax.Array, state_u32: jax.Array,
                           weights_packed, v_init, en_init, vp_init,
                           counts_init: jax.Array,
                           first_init: jax.Array, steps_init: jax.Array,
                           gate_init=None, *, chunk_steps: int,
                           window_steps: int, decay_shift: int,
                           v_threshold: int, v_rest: int = 0,
                           v_min: int = -(1 << 20),
                           v_max: int = (1 << 20) - 1,
                           active_pruning: bool = False, patience: int = 0,
                           readout: str = "count",
                           sparse_skip: bool = True, streamed: bool = False,
                           block_b: int = DEFAULT_BLOCK_B,
                           interpret: bool = False, aux=(), geoms=None):
    """Run ``chunk_steps`` timesteps of the full encode→LIF stack.

    All arrays must already be padded: batch to ``block_b``, every neuron
    axis to 128 (use ``kernels.ops.fused_snn_stack_op``, which also masks
    padded neurons out of the enable sets and packs the weights).

      pixels_u8/state_u32: (B, n_in)
      weights_packed: [(2, n_l, n_{l+1}) int8] from :func:`pack_weights`
      v_init/en_init: per-layer (B, n_{l+1}) int32 / uint8
      vp_init: per-layer (B, n_{l+1}) int32 carried peak membranes
        (INT32_MIN at window start — max-folded per step, so a chunked
        window's running peak is bit-identical to the one-shot maximum)
      counts_init/first_init: (B, n_out) int32 (first sentinel=window_steps)
      steps_init: (B, 1) int32 — per-lane absolute step counter
      gate_init: None, or (active u8, prev i32, streak i32) each (B, 1)

    ``sparse_skip`` gates the event-driven tile skipping (bit-identical
    either way); ``streamed`` keeps the packed weight planes in HBM and
    double-buffers 128-row slabs through VMEM scratch — the path for
    stacks whose resident footprint exceeds the VMEM budget.

    ``geoms`` (:func:`stack_geometry`) runs conv-pool layers: their
    weights are the planes of :func:`pack_stack`, ``aux`` their
    (field, per_x) matrices in layer order, and the pixels, PRNG lanes
    and each conv layer's v / en / v_peak arrive as maps (:func:`to_map`),
    ``rows x bB`` rows a batch block; the dense arrays are as above.

    Returns (counts, v_trace (chunk,B,n_out), first, adds (chunk,B),
    state_u32', v_final tuple, en_final tuple (uint8), v_peak tuple,
    (tel_spk (chunk,L,B), tel_en (chunk,L,B),
    tel_tiles (chunk,L,n_blocks)), steps', and — when gated —
    (active', prev', streak')).
    """
    B = counts_init.shape[0]
    L = len(weights_packed)
    n_out = weights_packed[-1].shape[2]
    gated = gate_init is not None
    grid = (pl.cdiv(B, block_b),)
    bB = block_b
    if geoms is not None and streamed:
        raise ValueError("conv-pool layers run only resident")

    kernel = functools.partial(
        _stack_kernel, num_layers=L, chunk_steps=chunk_steps,
        window_steps=window_steps, decay_shift=decay_shift,
        v_threshold=v_threshold, v_rest=v_rest, v_min=v_min, v_max=v_max,
        active_pruning=active_pruning, gated=gated, patience=patience,
        readout=readout, sparse_skip=sparse_skip, streamed=streamed,
        geoms=geoms)

    def row(shape):      # batch-tiled 2-D state block (a map: rows x bB)
        return pl.BlockSpec((shape[0] // grid[0],) + shape[1:],
                            lambda i: (i,) + (0,) * (len(shape) - 1))

    def whole(shape):    # fully VMEM-resident (packed weight planes)
        return pl.BlockSpec(shape, lambda i: (0,) * len(shape))

    # Streamed weights never enter VMEM whole: the kernel DMAs 128-row
    # slabs out of HBM/ANY on demand.
    w_spec = ((lambda w: pl.BlockSpec(memory_space=pl.ANY)) if streamed
              else (lambda w: whole(w.shape)))

    in_specs = [row(pixels_u8.shape), row(state_u32.shape)]
    in_specs += [w_spec(w) for w in weights_packed]
    in_specs += [whole(a.shape) for a in aux]
    in_specs += [row(v.shape) for v in v_init]
    in_specs += [row(e.shape) for e in en_init]
    in_specs += [row(v.shape) for v in vp_init]
    in_specs += [row(counts_init.shape), row(first_init.shape),
                 row(steps_init.shape)]
    inputs = ([pixels_u8, state_u32] + list(weights_packed) + list(aux)
              + list(v_init)
              + list(en_init) + list(vp_init)
              + [counts_init, first_init, steps_init])
    if gated:
        in_specs += [row(g.shape) for g in gate_init]
        inputs += list(gate_init)

    # per-step side channel, one lane-dense (bB, LANE) row per step:
    # column 0 = executed adds, then (input spikes, enabled outputs) per
    # layer — the energy + telemetry counters of each lane
    if 1 + 2 * L > LANE:
        raise ValueError(f"{L} layers overflow the {LANE}-column side row")
    steps_block = lambda i: (0, i, 0)                       # noqa: E731
    out_specs = [
        row((B, n_out)),
        pl.BlockSpec((chunk_steps, bB, n_out), steps_block),
        row((B, n_out)),
        pl.BlockSpec((chunk_steps, bB, LANE), steps_block),
        row(state_u32.shape),
    ]
    out_shape = [
        jax.ShapeDtypeStruct((B, n_out), jnp.int32),
        jax.ShapeDtypeStruct((chunk_steps, B, n_out), jnp.int32),
        jax.ShapeDtypeStruct((B, n_out), jnp.int32),
        jax.ShapeDtypeStruct((chunk_steps, B, LANE), jnp.int32),
        jax.ShapeDtypeStruct(state_u32.shape, jnp.uint32),
    ]
    for dtype in (jnp.int32, jnp.uint8, jnp.int32):   # v, en, peak membranes
        for v in v_init:
            out_specs.append(row(v.shape))
            out_shape.append(jax.ShapeDtypeStruct(v.shape, dtype))
    # skipped tile pairs per step, layer and batch block: scalars, in SMEM
    out_specs.append(pl.BlockSpec(memory_space=pltpu.SMEM))
    out_shape.append(
        jax.ShapeDtypeStruct((chunk_steps, L, grid[0]), jnp.int32))
    out_specs.append(row((B, 1)))
    out_shape.append(jax.ShapeDtypeStruct((B, 1), jnp.int32))
    if gated:
        for _ in range(3):
            out_specs.append(row((B, 1)))
            out_shape.append(jax.ShapeDtypeStruct((B, 1), jnp.int32))

    outs = pl.pallas_call(
        kernel, grid=grid, in_specs=in_specs, out_specs=out_specs,
        out_shape=out_shape, interpret=interpret,
        name="snn_stack_kernel")(*inputs)

    cnt, vtr, first, side, st_out = outs[:5]
    v_fin = tuple(outs[5:5 + L])
    en_fin = tuple(outs[5 + L:5 + 2 * L])
    vp_fin = tuple(outs[5 + 2 * L:5 + 3 * L])
    # (chunk, B, 1 + 2L) side row → adds (chunk, B), spk/en (chunk, L, B)
    adds = side[:, :, 0]
    pairs = jnp.transpose(side[:, :, 1:1 + 2 * L], (0, 2, 1))
    tel = (pairs[:, 0::2], pairs[:, 1::2], outs[5 + 3 * L])
    steps_out = outs[6 + 3 * L]
    if gated:
        return (cnt, vtr, first, adds, st_out, v_fin, en_fin, vp_fin, tel,
                steps_out, tuple(outs[7 + 3 * L:10 + 3 * L]))
    return (cnt, vtr, first, adds, st_out, v_fin, en_fin, vp_fin, tel,
            steps_out)
