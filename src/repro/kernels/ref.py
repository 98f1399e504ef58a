"""Pure-jnp oracles for every Pallas kernel in this package.

These are the semantics contracts: each kernel's test sweeps shapes/dtypes
and asserts exact equality (integer datapaths) or allclose (float) against
these functions.  They intentionally re-derive the math independently of
``repro.core`` so that kernel bugs and core bugs cannot cancel.
"""

from __future__ import annotations

import os

import jax
import jax.numpy as jnp

from ..core.telemetry import ChunkTelemetry

__all__ = ["poisson_encode_ref", "lif_forward_ref", "spike_matmul_ref",
           "fused_snn_ref", "fused_snn_stack_ref", "weight_pack_ref",
           "tile_skips_ref", "conv_pool_ref"]

# The megakernel's launch geometry, re-derived here independently of
# kernels.fused_snn (oracle style): 128-lane neuron tiles, 8-row batch
# blocks.  If the kernel's tiling ever changes these must be updated in
# lockstep — which is the point: a silent geometry change breaks the
# telemetry bit-identity tests instead of going unnoticed.
_REF_LANE = 128
_REF_BLOCK_B = 8


def tile_skips_ref(x: jax.Array, en: jax.Array, *,
                   sparse_skip: bool) -> jax.Array:
    """Oracle for the kernel's per-layer tile-skip telemetry counter.

    ``x``: (B, n_in) bool input spikes; ``en``: (B, n_out) bool enables
    (true sizes — padding is re-derived here).  Returns (n_blocks,) i32
    skipped (K-tile, N-tile) pairs per batch block: a pair is skipped
    when its 128-wide K slice carries no spike in any lane of the 8-row
    block OR its 128-wide output slice is fully pruned across the block.
    Derived independently of both ``kernels.fused_snn`` and
    ``core.telemetry`` so kernel bugs and mirror bugs cannot cancel.
    """
    B = x.shape[0]
    bB = _REF_BLOCK_B
    Bp = B + (-B) % bB

    def pad(a, n_lane):
        out = jnp.zeros((Bp, n_lane + (-n_lane) % _REF_LANE), bool)
        return out.at[:B, :n_lane].set(a.astype(bool))

    xp, ep = pad(x, x.shape[1]), pad(en, en.shape[1])
    nb = Bp // bB
    nkt, nnt = xp.shape[1] // _REF_LANE, ep.shape[1] // _REF_LANE
    any_x = jnp.any(xp.reshape(nb, bB, nkt, _REF_LANE), axis=(1, 3))
    any_e = jnp.any(ep.reshape(nb, bB, nnt, _REF_LANE), axis=(1, 3))
    live = jnp.logical_and(any_x[:, :, None], any_e[:, None, :])
    if not sparse_skip:
        return jnp.zeros((nb,), jnp.int32)
    return jnp.sum(jnp.logical_not(live), axis=(1, 2)).astype(jnp.int32)


def _pad_cols(a, mult):
    return jnp.pad(a, ((0, 0),) * (a.ndim - 1) + ((0, (-a.shape[-1]) % mult),))


def _conv_splits(layers):
    """Per conv-pool layer: (m_in, m_out, hs_in, hs_out), the kernel's
    row split of its input and output maps — the output rows y = m_out*k
    + q, read by pre-pool rows pool*y + u from input rows s + m_in*k with
    s = pool*q + u + dy; hs the rows a class holds, padded so every read
    stays inside the map.  Re-derived here from the layer equations."""
    convs = [d for d in layers if hasattr(d, "c_out")]
    out, m_out, hs_out = [], 1, None
    for d in reversed(convs):
        k, p = d.kernel, d.pool
        h_in = d.in_shape[1]
        hp = (h_in - k + 1) // p
        hs_out = hp if hs_out is None else hs_out
        m_in = p * m_out
        hs_in = max(-(-h_in // m_in),
                    hs_out + (p * m_out - 1 + k - 1) // m_in)
        out.append((m_in, m_out, hs_in, hs_out))
        m_out, hs_out = m_in, hs_in
    return out[::-1]


def conv_pool_ref(x, w_q, layer):
    """Oracle of a conv-pool layer: pooled currents (B, C*Hp*Wp) i32 and
    the per-lane field counts pooled (B, Hp, Wp) i32 of spikes ``x``
    (B, C_in*H*W), by XLA's convolution in f32 (exact: integer operands,
    partial sums below 2**24)."""
    c_in, h, w = layer.in_shape
    k, p = layer.kernel, layer.pool
    xs = x.reshape(-1, c_in, h, w).astype(jnp.float32)

    def conv(a, b):
        return jax.lax.conv_general_dilated(
            a, b, (1, 1), "VALID", precision=jax.lax.Precision.HIGHEST)

    a = conv(xs, w_q.astype(jnp.float32))
    ones = conv(xs, jnp.ones((1, c_in, k, k), jnp.float32))
    B, c, ho, wo = a.shape
    cur = jnp.max(a.reshape(B, c, ho // p, p, wo // p, p), axis=(3, 5))
    field = jnp.sum(ones.reshape(B, ho // p, p, wo // p, p), axis=(2, 4))
    return (cur.reshape(B, -1).astype(jnp.int32), field.astype(jnp.int32))


def _conv_tile_skips_ref(x, en, layer, split, *, sparse_skip):
    """Skipped tile pairs of one conv-pool stage per 8-row batch block,
    from the flat maps: a (q, u, dy) contraction's K tile is a 128-column
    slice of the (W * C_in)-wide rows it reads, live if any lane of the
    block spikes there; an N tile of its pre-pool currents is live if
    class q holds an enabled neuron in the matching pooled column tile."""
    m_in, m_out, hs_in, hs_out = split
    c_in, h, w = layer.in_shape
    k, p = layer.kernel, layer.pool
    hp, wp = (h - k + 1) // p, (w - k + 1) // p
    B = x.shape[0]
    Bp = B + (-B) % _REF_BLOCK_B
    nb = Bp // _REF_BLOCK_B

    def row_tiles(a, c, rows, cols):
        """(B, c*rows*cols) -> (nb, rows, col tiles) any over the block."""
        a = jnp.transpose(a.reshape(B, c, rows, cols), (0, 2, 3, 1))
        a = _pad_cols(a.reshape(B, rows, cols * c), _REF_LANE)
        a = jnp.pad(a, ((0, Bp - B), (0, 0), (0, 0)))
        return jnp.any(a.reshape(nb, _REF_BLOCK_B, rows, -1, _REF_LANE),
                       axis=(1, 4))

    xt = row_tiles(x.astype(bool), c_in, h, w)            # (nb, h, nkc)
    et = row_tiles(en.astype(bool), layer.c_out, hp, wp)  # (nb, hp, nnh)
    if not sparse_skip:
        return jnp.zeros((nb,), jnp.int32)
    skipped = jnp.zeros((nb,), jnp.int32)
    for q in range(m_out):
        ys = [y for y in range(q, hp, m_out)][:hs_out]
        e_q = jnp.any(et[:, ys], axis=1)
        e_pre = jnp.concatenate([e_q] * p, axis=1)
        for u in range(p):
            for dy in range(k):
                s = p * q + u + dy
                first = s // m_in
                rows = [s % m_in + m_in * kk
                        for kk in range(first, first + hs_out)
                        if s % m_in + m_in * kk < h]
                x_s = (jnp.any(xt[:, rows], axis=1) if rows
                       else jnp.zeros(xt[:, 0].shape, bool))
                live = jnp.logical_and(x_s[:, :, None], e_pre[:, None, :])
                skipped = skipped + jnp.sum(~live, axis=(1, 2)).astype(
                    jnp.int32)
    return skipped


def weight_pack_ref(w_q):
    """Oracle for ``kernels.fused_snn.pack_weights``.

    The fused kernels keep weights resident as two int8 planes —
    ``hi = w >> 1`` (arithmetic shift) and ``lo = w & 1`` — reconstructed
    per tile as ``w = 2*hi + lo``.  That split is exact for every code in
    the paper's signed 9-bit range [-256, 255] (``quantize_params``'
    output contract) and for nothing wider: hi must fit int8.  Returns
    ``(hi, lo)`` int8 numpy planes, derived independently of the kernel
    module.
    """
    import numpy as np
    w = np.asarray(w_q, np.int64)
    if w.min() < -256 or w.max() > 255:
        raise ValueError("weight codes outside the signed 9-bit range "
                         "[-256, 255] cannot be int8-packed exactly")
    hi = w >> 1
    lo = w - 2 * hi                        # ∈ {0, 1}
    return hi.astype(np.int8), lo.astype(np.int8)


def poisson_encode_ref(pixels_u8: jax.Array, state_u32: jax.Array,
                       num_steps: int):
    """xorshift32-driven Poisson encoding. Returns (spikes u8 (T,...), state)."""

    def step(s, _):
        s = s ^ (s << 13)
        s = s ^ (s >> 17)
        s = s ^ (s << 5)
        r = (s >> 24).astype(jnp.uint8)
        return s, (pixels_u8 > r).astype(jnp.uint8)

    state_f, spikes = jax.lax.scan(step, state_u32, None, length=num_steps)
    return spikes, state_f


def lif_forward_ref(spikes_t: jax.Array, w_q: jax.Array, *, decay_shift: int,
                    v_threshold: int, v_rest: int = 0,
                    v_min: int = -(1 << 20), v_max: int = (1 << 20) - 1,
                    active_pruning: bool = False):
    """T-step integer LIF layer.

    spikes_t: (T, B, N_in) uint8/bool; w_q: (N_in, N_out) int.
    Returns (out_spikes u8 (T,B,N_out), v_trace i32 (T,B,N_out), v_final i32).
    """
    T, B, _ = spikes_t.shape
    n_out = w_q.shape[1]
    v0 = jnp.full((B, n_out), v_rest, jnp.int32)
    en0 = jnp.ones((B, n_out), bool)

    def step(carry, s_t):
        v, en = carry
        cur = jnp.dot(s_t.astype(jnp.int32), w_q.astype(jnp.int32))
        cur = jnp.where(en, cur, 0)
        v_int = jnp.clip(v + cur, v_min, v_max)
        v_leak = v_int - (v_int >> decay_shift)
        fired = jnp.logical_and(v_leak >= v_threshold, en)
        v_new = jnp.where(fired, jnp.int32(v_rest), v_leak)
        v_new = jnp.where(en, v_new, v)
        if active_pruning:
            en = jnp.logical_and(en, jnp.logical_not(fired))
        return (v_new, en), (fired.astype(jnp.uint8), v_new)

    (v_f, _), (spk, vtr) = jax.lax.scan(step, (v0, en0), spikes_t)
    return spk, vtr, v_f


def fused_snn_ref(pixels_u8: jax.Array, state_u32: jax.Array,
                  w_q: jax.Array, *, num_steps: int, decay_shift: int,
                  v_threshold: int, v_rest: int = 0,
                  v_min: int = -(1 << 20), v_max: int = (1 << 20) - 1,
                  active_pruning: bool = False):
    """Oracle for the fused encode→LIF megakernel (fused_snn.py).

    Re-derives the whole window — PRNG, comparator, Σ W·S, leak, fire,
    reset, pruning gate, add counter — in one scan, independently of both
    ``repro.core`` and the staged oracles above.

    Returns (counts i32 (B,N_out), v_trace i32 (T,B,N_out),
             first_spike_t i32 (B,N_out), v_final i32 (B,N_out),
             active_adds i32 (T,B), state u32 (B,N_in)).
    """
    B = pixels_u8.shape[0]
    n_out = w_q.shape[1]
    w = w_q.astype(jnp.int32)
    v0 = jnp.full((B, n_out), v_rest, jnp.int32)
    en0 = jnp.ones((B, n_out), bool)
    cnt0 = jnp.zeros((B, n_out), jnp.int32)
    first0 = jnp.full((B, n_out), num_steps, jnp.int32)

    def step(carry, t):
        s, v, en, cnt, first = carry
        s = s ^ (s << 13)
        s = s ^ (s >> 17)
        s = s ^ (s << 5)
        spk = pixels_u8 > (s >> 24).astype(jnp.uint8)
        cur = jnp.dot(spk.astype(jnp.int32), w)
        cur = jnp.where(en, cur, 0)
        v_int = jnp.clip(v + cur, v_min, v_max)
        v_leak = v_int - (v_int >> decay_shift)
        fired = jnp.logical_and(v_leak >= v_threshold, en)
        v_new = jnp.where(fired, jnp.int32(v_rest), v_leak)
        v_new = jnp.where(en, v_new, v)
        first = jnp.where(jnp.logical_and(fired, first == num_steps),
                          t.astype(jnp.int32), first)
        cnt = cnt + fired.astype(jnp.int32)
        adds = (jnp.sum(spk.astype(jnp.int32), axis=-1)
                * jnp.sum(en.astype(jnp.int32), axis=-1))
        if active_pruning:
            en = jnp.logical_and(en, jnp.logical_not(fired))
        return (s, v_new, en, cnt, first), (v_new, adds)

    (s_f, v_f, _, cnt_f, first_f), (vtr, adds_t) = jax.lax.scan(
        step, (state_u32, v0, en0, cnt0, first0), jnp.arange(num_steps))
    return cnt_f, vtr, first_f, v_f, adds_t, s_f


def fused_snn_stack_ref(pixels_u8: jax.Array, state_u32: jax.Array,
                        weights, *, num_steps: int, chunk_steps: int | None = None,
                        decay_shift: int, v_threshold: int, v_rest: int = 0,
                        v_min: int = -(1 << 20), v_max: int = (1 << 20) - 1,
                        active_pruning: bool = False,
                        sparse_skip: bool | None = None,
                        init: dict | None = None, layers=None):
    """Oracle for the multi-layer resumable megakernel (fused_snn.py).

    Re-derives the whole stack — PRNG, comparator, the per-layer Σ W·S /
    leak / fire / reset / pruning chain, the layer-summed add counter,
    the carried-state semantics, the per-layer peak-membrane accumulator
    AND the telemetry side channel (per-step spike/enable counts per
    lane, skipped tile pairs per block via :func:`tile_skips_ref`) — in
    one scan, independently of ``repro.core``.  ``init`` mirrors the
    kernel's carried state (``v`` / ``en`` / ``v_peak`` per-layer tuples,
    ``counts``, ``first`` with sentinel ``num_steps``, ``steps`` (B,));
    ``chunk_steps`` is how many steps this call executes (default: the
    full window).  ``sparse_skip`` only affects the telemetry tile
    counter (None resolves the same REPRO_SPARSE_SKIP env rule as the
    launcher, so oracle and kernel agree under the CI forcing).
    ``layers`` (``SNNConfig.layers``) adds conv-pool layers
    (:func:`conv_pool_ref`); their tile counts follow the kernel's row
    splits, re-derived by :func:`_conv_splits`.

    Returns a dict shaped like ``kernels.ops.fused_snn_stack_op``'s.
    """
    if chunk_steps is None:
        chunk_steps = num_steps
    if sparse_skip is None:
        sparse_skip = os.environ.get("REPRO_SPARSE_SKIP", "1") != "0"
    B = pixels_u8.shape[0]
    L = len(weights)
    ws = [w.astype(jnp.int32) for w in weights]
    n_out = ws[-1].shape[1]
    if layers is None:
        widths = [w.shape[1] for w in ws]
        kinds = [None] * L
    else:
        widths = [d.c_out * ((d.in_shape[1] - d.kernel + 1) // d.pool)
                  * ((d.in_shape[2] - d.kernel + 1) // d.pool)
                  if hasattr(d, "c_out") else d.n for d in layers]
        splits = iter(_conv_splits(layers))
        kinds = [next(splits) if hasattr(d, "c_out") else None
                 for d in layers]
    vp0 = jnp.iinfo(jnp.int32).min
    if init is None:
        init = {
            "v": tuple(jnp.full((B, n), v_rest, jnp.int32) for n in widths),
            "en": tuple(jnp.ones((B, n), bool) for n in widths),
            "counts": jnp.zeros((B, n_out), jnp.int32),
            "first": jnp.full((B, n_out), num_steps, jnp.int32),
            "steps": jnp.zeros((B,), jnp.int32),
        }
    vp_init = init.get("v_peak")
    if vp_init is None:
        vp_init = tuple(jnp.full((B, n), vp0, jnp.int32) for n in widths)

    def step(carry, _):
        s, vs, ens, vps, cnt, first, steps = carry
        s = s ^ (s << 13)
        s = s ^ (s >> 17)
        s = s ^ (s << 5)
        x = pixels_u8 > (s >> 24).astype(jnp.uint8)
        adds = jnp.zeros((B,), jnp.int32)
        new_vs, new_ens, new_vps = [], [], []
        tel_spk, tel_en, tel_tiles = [], [], []
        for l in range(L):
            en = ens[l]
            n_spk = jnp.sum(x.astype(jnp.int32), axis=-1)
            n_en = jnp.sum(en.astype(jnp.int32), axis=-1)
            if kinds[l] is not None:
                d = layers[l]
                tel_tiles.append(_conv_tile_skips_ref(
                    x, en, d, kinds[l], sparse_skip=sparse_skip))
                cur, field = conv_pool_ref(x, ws[l], d)
                per_x = jnp.sum(en.reshape(field.shape[:1] + (d.c_out,)
                                           + field.shape[1:])
                                .astype(jnp.int32), axis=1)
                adds = adds + jnp.sum(field * per_x, axis=(1, 2))
            else:
                if l and kinds[l - 1] is not None:
                    hs = kinds[l - 1][3]          # the last map: m = 1
                    d = layers[l - 1]
                    c, hp = d.c_out, (d.in_shape[1] - d.kernel + 1) // d.pool
                    wp = (d.in_shape[2] - d.kernel + 1) // d.pool
                    xm = jnp.transpose(x.reshape(B, c, hp, wp), (0, 2, 3, 1))
                    xm = _pad_cols(xm.reshape(B, hp, wp * c), _REF_LANE)
                    xm = jnp.pad(xm, ((0, 0), (0, hs - hp), (0, 0)))
                    tel_tiles.append(tile_skips_ref(
                        xm.reshape(B, -1), en, sparse_skip=sparse_skip))
                else:
                    tel_tiles.append(tile_skips_ref(
                        x, en, sparse_skip=sparse_skip))
                cur = jnp.dot(x.reshape(B, -1).astype(jnp.int32), ws[l])
                adds = adds + n_spk * n_en
            cur = jnp.where(en, cur, 0)
            v_int = jnp.clip(vs[l] + cur, v_min, v_max)
            v_leak = v_int - (v_int >> decay_shift)
            fired = jnp.logical_and(v_leak >= v_threshold, en)
            v_new = jnp.where(fired, jnp.int32(v_rest), v_leak)
            v_new = jnp.where(en, v_new, vs[l])
            tel_spk.append(n_spk)
            tel_en.append(n_en)
            if active_pruning:
                en = jnp.logical_and(en, jnp.logical_not(fired))
            new_vs.append(v_new)
            new_ens.append(en)
            new_vps.append(jnp.maximum(vps[l], v_new))
            x = fired
        cnt = cnt + x.astype(jnp.int32)
        first = jnp.where(jnp.logical_and(x, first == num_steps),
                          steps[:, None], first)
        carry = (s, tuple(new_vs), tuple(new_ens), tuple(new_vps), cnt,
                 first, steps + 1)
        return carry, (new_vs[-1], adds, jnp.stack(tel_spk),
                       jnp.stack(tel_en), jnp.stack(tel_tiles))

    carry0 = (state_u32, tuple(init["v"]), tuple(init["en"]),
              tuple(vp_init), init["counts"], init["first"],
              init["steps"].astype(jnp.int32))
    ((s_f, vs_f, ens_f, vps_f, cnt_f, first_f, steps_f),
     (vtr, adds_t, tspk, ten, ttile)) = \
        jax.lax.scan(step, carry0, None, length=chunk_steps)
    return {
        "spike_counts": cnt_f, "v_trace": vtr, "first_spike_t": first_f,
        "v_final": vs_f[-1], "active_adds": adds_t, "prng_state": s_f,
        "v": vs_f, "en": ens_f, "v_peak": vps_f, "steps": steps_f,
        "telemetry": ChunkTelemetry(n_spk=tspk, n_en=ten,
                                    tiles_skipped=ttile),
    }


def spike_matmul_ref(spikes: jax.Array, w_q: jax.Array) -> jax.Array:
    """Binary-spike × integer-weight contraction with int32 accumulation.

    spikes: (B, N_in) in {0,1}; w_q: (N_in, N_out) int. Returns (B, N_out) i32.
    """
    return jnp.dot(spikes.astype(jnp.int32), w_q.astype(jnp.int32))
