"""Pallas TPU kernel: fused T-step integer LIF layer (integrate→leak→fire→reset).

RTL datapath (paper Fig. 1): Weight-Reg → Adder → Accumulator → shift-based
Decay → Comparator → reset, sequenced by a local FSM over timesteps.

TPU mapping (the hardware-adaptation core of this repro):
  * The weight tile stays **resident in VMEM for all T steps**, as the
    two int8 planes of ``fused_snn.pack_weights``
    — the analogue of the RTL's on-chip BRAM weight bank ("no external
    memory access", paper §V-B).  Spikes stream in; membrane state lives in
    a VMEM scratch accumulator, exactly like the Accumulator register.
  * The synaptic sum Σ W·S with S ∈ {0,1} is two int8 MXU passes with
    int32 accumulation (``fused_snn.spike_dot``) — since one operand is
    binary the effective arithmetic is the paper's "adds only" datapath;
    the energy model (core.energy) accounts it that way.
  * Leak = arithmetic right shift, fire = compare, reset = select: all VPU
    byte-lane ops, fused into the same pipeline stage as the MXU drain.
  * Active pruning is an enable mask in VMEM scratch, gating both the
    current and the state write-back — the clock-gate bit of §III-D.

Grid: (B/bB, N_out/bN); contraction dim N_in is kept whole in VMEM (the
SNN-scale layers the paper targets fit comfortably: 896×128×2 int8 = 224 KB).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .fused_snn import spike_dot

__all__ = ["lif_forward_pallas"]

DEFAULT_BLOCK = (8, 128)  # (batch tile, out-neuron tile)


def _lif_kernel(spikes_ref, w_ref, spk_out_ref, vtr_out_ref, vfin_out_ref,
                *, num_steps: int, decay_shift: int, v_threshold: int,
                v_rest: int, v_min: int, v_max: int, active_pruning: bool):
    w = w_ref[...]                          # (2, N_in, bN) int8, resident all T
    bB = spk_out_ref.shape[1]
    bN = spk_out_ref.shape[2]

    v0 = jnp.full((bB, bN), v_rest, jnp.int32)
    en0 = jnp.ones((bB, bN), jnp.int32)     # int32 0/1: no i1 loop carry on TPU

    def body(t, carry):
        v, en_i = carry
        en = en_i != 0
        s_t = spikes_ref[t, :, :] != 0                   # (bB, N_in)
        # Σ W·S — binary operand ⇒ adds-only datapath (MXU int8 passes).
        cur = spike_dot(s_t, w)
        cur = jnp.where(en, cur, 0)                      # pruning clock-gate
        v_int = jnp.clip(v + cur, v_min, v_max)          # saturating Adder
        v_leak = v_int - (v_int >> decay_shift)          # Decay-Reg shift
        fired = jnp.logical_and(v_leak >= v_threshold, en)   # Comparator
        v_new = jnp.where(fired, jnp.int32(v_rest), v_leak)  # hard reset
        v_new = jnp.where(en, v_new, v)                  # frozen when gated
        spk_out_ref[t, :, :] = fired.astype(jnp.uint8)
        vtr_out_ref[t, :, :] = v_new
        if active_pruning:
            en = jnp.logical_and(en, jnp.logical_not(fired))
        return (v_new, en.astype(jnp.int32))

    v_f, _ = jax.lax.fori_loop(0, num_steps, body, (v0, en0))
    vfin_out_ref[...] = v_f


def lif_forward_pallas(spikes_t: jax.Array, w_packed: jax.Array, *,
                       decay_shift: int, v_threshold: int, v_rest: int = 0,
                       v_min: int = -(1 << 20), v_max: int = (1 << 20) - 1,
                       active_pruning: bool = False,
                       block=DEFAULT_BLOCK, interpret: bool = False):
    """spikes_t: (T, B, N_in) u8; w_packed: (2, N_in, N_out) int8 planes.

    Returns (out_spikes u8 (T,B,N_out), v_trace i32 (T,B,N_out), v_final i32 (B,N_out)).
    """
    T, B, n_in = spikes_t.shape
    n_out = w_packed.shape[2]
    bB, bN = block
    grid = (pl.cdiv(B, bB), pl.cdiv(n_out, bN))

    kernel = functools.partial(
        _lif_kernel, num_steps=T, decay_shift=decay_shift,
        v_threshold=v_threshold, v_rest=v_rest, v_min=v_min, v_max=v_max,
        active_pruning=active_pruning)

    spk, vtr, vfin = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            # Full T and full N_in per batch tile; only batch dim is split.
            pl.BlockSpec((T, bB, n_in), lambda i, j: (0, i, 0)),
            pl.BlockSpec((2, n_in, bN), lambda i, j: (0, 0, j)),
        ],
        out_specs=[
            pl.BlockSpec((T, bB, bN), lambda i, j: (0, i, j)),
            pl.BlockSpec((T, bB, bN), lambda i, j: (0, i, j)),
            pl.BlockSpec((bB, bN), lambda i, j: (i, j)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((T, B, n_out), jnp.uint8),
            jax.ShapeDtypeStruct((T, B, n_out), jnp.int32),
            jax.ShapeDtypeStruct((B, n_out), jnp.int32),
        ],
        interpret=interpret,
    )(spikes_t, w_packed)
    return spk, vtr, vfin
