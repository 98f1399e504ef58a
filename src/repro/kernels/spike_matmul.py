"""Pallas TPU kernel: event-driven spike × weight accumulation.

The paper's headline arithmetic claim (Table II): `multiplications = 0` —
the synaptic sum Σᵢ Wᵢ·Sᵢ with binary S is a *masked add*, not a MAC.  This
kernel provides both TPU realisations of that insight:

  * ``mode="masked"`` — the literal RTL datapath: for each input line i,
    `acc += S_i ? W_i : 0` as a VPU select+add over the weight row.  This is
    the faithful model (and the energy-accounting ground truth), efficient
    when spike density is low and N_in is modest.
  * ``mode="mxu"`` — the TPU-native realisation: int8 dot_generals on the
    MXU with int32 accumulation (``fused_snn.spike_dot`` over the packed
    weight planes).  Arithmetically identical (S ∈ {0,1}); this is what a
    production TPU deployment would run at high density.

Both modes take the weights as the two int8 planes of
``fused_snn.pack_weights`` (exact for the signed 9-bit codes).

``ops.spike_matmul`` dispatches between them on expected spike density —
the kernel-level analogue of event-driven vs dense execution.

Grid: (B/bB, N_out/bN, N_in/bK) with K-accumulation across the innermost
grid dimension (output revisited per k-step, standard Pallas matmul idiom).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .fused_snn import spike_dot

__all__ = ["spike_matmul_pallas"]

DEFAULT_BLOCK = (8, 128, 256)  # (bB, bN, bK)


def _spike_mm_kernel(s_ref, w_ref, out_ref, *scratch, mode: str):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    s = s_ref[...].astype(jnp.int32)     # (bB, bK) 0/1

    if mode == "mxu":
        acc = spike_dot(s != 0, w_ref[...])
    else:  # masked: literal select+add datapath, no multiplies
        (w_scr,) = scratch               # (bK, bN) int32 weight rows
        w_scr[...] = 2 * w_ref[0].astype(jnp.int32) + w_ref[1].astype(
            jnp.int32)
        bK = s.shape[1]
        lane = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)

        def body(i, acc):
            # spike i of every lane as a (bB, 1) column (one-hot lane
            # reduction: the chip has no dynamic lane slice) and weight
            # row i from scratch (a dynamic sublane load)
            s_i = jnp.sum(jnp.where(lane == i, s, 0), axis=1, keepdims=True)
            row = w_scr[pl.ds(i, 1), :]              # (1, bN)
            return acc + jnp.where(s_i > 0, row, 0)

        acc = jax.lax.fori_loop(
            0, bK, body, jnp.zeros(out_ref.shape, jnp.int32))

    out_ref[...] += acc


def spike_matmul_pallas(spikes: jax.Array, w_packed: jax.Array, *,
                        mode: str = "mxu", block=DEFAULT_BLOCK,
                        interpret: bool = False) -> jax.Array:
    """spikes: (B, N_in) u8 in {0,1}; w_packed: (2, N_in, N_out) int8
    planes. → (B, N_out) i32."""
    B, n_in = spikes.shape
    n_out = w_packed.shape[2]
    bB, bN, bK = block
    bK = min(bK, n_in)
    grid = (pl.cdiv(B, bB), pl.cdiv(n_out, bN), pl.cdiv(n_in, bK))

    kernel = functools.partial(_spike_mm_kernel, mode=mode)
    scratch = ([pltpu.VMEM((bK, bN), jnp.int32)] if mode == "masked"
               else [])
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bB, bK), lambda i, j, k: (i, k)),
            pl.BlockSpec((2, bK, bN), lambda i, j, k: (0, k, j)),
        ],
        out_specs=pl.BlockSpec((bB, bN), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((B, n_out), jnp.int32),
        scratch_shapes=scratch,
        interpret=interpret,
    )(spikes, w_packed)
