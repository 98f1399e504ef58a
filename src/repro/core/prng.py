"""Bit-exact 32-bit XOR-shift PRNG (paper §III-C).

The RTL uses a 32-bit xorshift register (Marsaglia 2003, the canonical
13/17/5 triple) to drive the on-chip Poisson encoder.  We reproduce it
bit-exactly with ``jnp.uint32`` ops so that, given the same seed layout, the
JAX model and the SystemVerilog testbench generate identical spike trains.

State layout: one independent 32-bit register per pixel (the RTL instantiates
one PRNG lane per input channel), vectorised as a ``uint32`` array.  Seeds of
zero are remapped (xorshift has a zero fixed point, as does the RTL, which
seeds registers from a non-zero LFSR preload).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

__all__ = [
    "seed_state",
    "seed_state_host",
    "xorshift32_step",
    "xorshift32_sequence",
    "uniform_u8",
]

# Golden constant used by the RTL preloader to displace zero seeds.
_ZERO_SEED_REMAP = np.uint32(0x9E3779B9)  # 2**32 / golden ratio


def seed_state_host(seed: int, shape: tuple[int, ...]) -> np.ndarray:
    """Integer-seeded per-lane uint32 xorshift state, built in host memory.

    Hashed counter seeding matching the RTL's LFSR preload chain: lane
    ``i`` of seed ``s`` is a SplitMix64-style finalizer of
    ``s·φ64 + i·c`` truncated to 32 bits, zeros remapped.  The streaming
    engine writes these rows straight into its host lane tile.
    """
    n = int(np.prod(shape)) if shape else 1
    with np.errstate(over="ignore"):  # intentional mod-2^64 wraparound
        lane = np.arange(n, dtype=np.uint64)
        s = (np.uint64(seed) * np.uint64(0x9E3779B97F4A7C15)
             + lane * np.uint64(0xBF58476D1CE4E5B9))
        # SplitMix64-style finalizer, truncated to 32 bits.
        s ^= s >> np.uint64(30)
        s *= np.uint64(0xBF58476D1CE4E5B9)
        s ^= s >> np.uint64(27)
        s *= np.uint64(0x94D049BB133111EB)
        s ^= s >> np.uint64(31)
    state = (s & np.uint64(0xFFFFFFFF)).astype(np.uint32).reshape(shape)
    return np.where(state == 0, _ZERO_SEED_REMAP, state)


def seed_state(key_or_int, shape: tuple[int, ...]) -> jax.Array:
    """Build a per-lane uint32 xorshift state array.

    Accepts either a python int (:func:`seed_state_host`, placed on the
    device) or a ``jax.random`` key (used by the training paths, where
    bit-compatibility with RTL is not required).
    """
    if isinstance(key_or_int, (int, np.integer)):
        return jnp.asarray(seed_state_host(key_or_int, shape))
    # jax key path
    bits = jax.random.bits(key_or_int, shape, dtype=jnp.uint32)
    return jnp.where(bits == 0, jnp.uint32(_ZERO_SEED_REMAP), bits)


def xorshift32_step(state: jax.Array) -> jax.Array:
    """One xorshift32 update: x ^= x<<13; x ^= x>>17; x ^= x<<5 (mod 2^32)."""
    if state.dtype != jnp.uint32:
        raise TypeError(f"xorshift32 state must be uint32, got {state.dtype}")
    x = state
    x = x ^ (x << 13)
    x = x ^ (x >> 17)
    x = x ^ (x << 5)
    return x


def xorshift32_sequence(state: jax.Array, num_steps: int) -> tuple[jax.Array, jax.Array]:
    """Run ``num_steps`` updates; returns (final_state, stacked outputs [T, ...])."""

    def body(s, _):
        s = xorshift32_step(s)
        return s, s

    final, seq = jax.lax.scan(body, state, None, length=num_steps)
    return final, seq


def uniform_u8(state: jax.Array) -> jax.Array:
    """Map a 32-bit state to the 8-bit comparison value used by the encoder.

    The RTL compares pixel intensity (0..255) against the PRNG's top byte —
    taking the high bits is standard practice because xorshift's low bits are
    weaker.  Returns uint8 in [0, 255].
    """
    return (state >> 24).astype(jnp.uint8)
