"""Pallas TPU kernel: fused xorshift32 + Poisson spike generation.

RTL block (paper Fig. 2): per-pixel PRNG lane → 8-bit comparator → spike.
TPU mapping: pixels and PRNG states live in VMEM tiles; the whole T-step
window is generated in one kernel launch so the PRNG state never round-trips
to HBM — the analogue of the RTL's free-running LFSR bank.  All ops are VPU
bitwise/compare ops; there is no MXU work, so the kernel is purely
memory-bound on the spike output: bytes_out = T·B·N, which is exactly the
event-stream bandwidth of the hardware encoder.

Block layout: grid over (B/bB, N/bN); each instance holds a (bB, bN) uint32
state tile in VMEM and emits a (T, bB, bN) uint8 spike tile.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

__all__ = ["poisson_encode_pallas"]

# TPU-native tile: 8 sublanes × 128 lanes; uint8 spikes pack (32, 128) tiles
# but (8,128) keeps the index math simple and still vector-aligned.
DEFAULT_BLOCK = (8, 128)


def _encode_kernel(pixels_ref, state_ref, spikes_ref, state_out_ref, *,
                   num_steps: int):
    """One (bB, bN) tile: run T xorshift steps, emit spikes per step."""
    px = pixels_ref[...].astype(jnp.int32)   # (bB, bN): no 8-bit compare on TPU
    s0 = state_ref[...]               # (bB, bN) uint32

    def body(t, s):
        # xorshift32: x ^= x<<13; x ^= x>>17; x ^= x<<5  (mod 2^32)
        s = s ^ (s << 13)
        s = s ^ (s >> 17)
        s = s ^ (s << 5)
        r = (s >> 24).astype(jnp.int32)          # comparator draws top byte
        spikes_ref[t, :, :] = (px > r).astype(jnp.uint8)
        return s

    s_final = jax.lax.fori_loop(0, num_steps, body, s0)
    state_out_ref[...] = s_final


def poisson_encode_pallas(pixels_u8: jax.Array, state_u32: jax.Array,
                          num_steps: int, *, block=DEFAULT_BLOCK,
                          interpret: bool = False):
    """pixels/state: (B, N). Returns (spikes u8 (T, B, N), state u32 (B, N))."""
    B, N = pixels_u8.shape
    bB, bN = block
    grid = (pl.cdiv(B, bB), pl.cdiv(N, bN))

    kernel = functools.partial(_encode_kernel, num_steps=num_steps)
    spikes, state_out = pl.pallas_call(
        kernel,
        name="poisson_encode",
        grid=grid,
        in_specs=[
            pl.BlockSpec((bB, bN), lambda i, j: (i, j)),
            pl.BlockSpec((bB, bN), lambda i, j: (i, j)),
        ],
        out_specs=[
            pl.BlockSpec((num_steps, bB, bN), lambda i, j: (0, i, j)),
            pl.BlockSpec((bB, bN), lambda i, j: (i, j)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((num_steps, B, N), jnp.uint8),
            jax.ShapeDtypeStruct((B, N), jnp.uint32),
        ],
        interpret=interpret,
    )(pixels_u8, state_u32)
    return spikes, state_out
