"""Per-kernel validation: Pallas (interpret mode) vs the pure-jnp oracles.

Each kernel is swept over shapes/dtypes and asserted EXACTLY equal to
ref.py (all three kernels are integer/bitwise datapaths — no tolerance)."""

import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import fused_snn, ops, ref
from repro.core import prng


def _pixels_state(rng, b, n):
    px = jnp.asarray(rng.integers(0, 256, (b, n), dtype=np.uint8))
    st = prng.seed_state(1234, (b, n))
    return px, st


@pytest.mark.parametrize("b,n,t", [
    (1, 784, 5), (8, 784, 20), (3, 100, 7), (16, 128, 1), (5, 1, 3),
])
def test_poisson_encode_matches_ref(rng, b, n, t):
    px, st = _pixels_state(rng, b, n)
    got_s, got_st = ops.poisson_encode_op(px, st, t, interpret=True)
    want_s, want_st = ref.poisson_encode_ref(px, st, t)
    np.testing.assert_array_equal(np.asarray(got_s), np.asarray(want_s))
    np.testing.assert_array_equal(np.asarray(got_st), np.asarray(want_st))


@pytest.mark.parametrize("b,n_in,n_out,t,shift,prune", [
    (4, 784, 10, 20, 4, False),
    (4, 784, 10, 20, 4, True),
    (2, 64, 128, 8, 2, False),
    (1, 32, 10, 5, 6, True),
    (9, 100, 200, 3, 4, False),
])
def test_lif_forward_matches_ref(rng, b, n_in, n_out, t, shift, prune):
    spikes = jnp.asarray(rng.integers(0, 2, (t, b, n_in), dtype=np.uint8))
    w = jnp.asarray(rng.integers(-128, 128, (n_in, n_out), dtype=np.int16))
    got = ops.lif_forward_op(spikes, w, decay_shift=shift, v_threshold=128,
                             active_pruning=prune, interpret=True)
    want = ref.lif_forward_ref(spikes, w, decay_shift=shift, v_threshold=128,
                               active_pruning=prune)
    for g, we in zip(got, want):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(we))


@pytest.mark.parametrize("density", [0.0, 0.1, 0.9])
@pytest.mark.parametrize("b,n_in,n_out", [
    (8, 784, 10), (4, 256, 384), (1, 300, 7), (16, 64, 128),
])
def test_spike_dot_matches_ref(rng, density, b, n_in, n_out):
    """The stack kernel's contraction (two int8 passes over the packed
    planes) == the int32 oracle over the full signed 9-bit code range."""
    spikes = jnp.asarray((rng.random((b, n_in)) < density).astype(np.uint8))
    w = jnp.asarray(rng.integers(-256, 256, (n_in, n_out)), jnp.int16)
    got = fused_snn.spike_dot(spikes, fused_snn.pack_weights(w))
    want = ref.spike_matmul_ref(spikes, w)
    assert got.dtype == jnp.int32
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("k", [784, 2048])
def test_spike_dot_exact_at_code_extremes(k):
    """All-ones spikes against the extreme codes: every row sums k copies
    of -256 (hi plane -128, lo 0) or +255 (hi 127, lo 1), where an
    inexact hi/lo split or a narrow accumulator would show."""
    spikes = jnp.ones((8, k), jnp.uint8)
    for code in (-256, 255):
        w = jnp.full((k, 128), code, jnp.int16)
        got = fused_snn.spike_dot(spikes, fused_snn.pack_weights(w))
        np.testing.assert_array_equal(np.asarray(got),
                                      np.full((8, 128), code * k, np.int32))
        np.testing.assert_array_equal(
            np.asarray(got), np.asarray(ref.spike_matmul_ref(spikes, w)))


def test_lif_kernel_weight_dtypes(rng):
    spikes = jnp.asarray(rng.integers(0, 2, (6, 4, 96), dtype=np.uint8))
    for dt in (np.int8, np.int16):
        w = jnp.asarray(rng.integers(-100, 100, (96, 24), dtype=dt))
        got = ops.lif_forward_op(spikes, w, decay_shift=3, v_threshold=64,
                                 interpret=True)
        want = ref.lif_forward_ref(spikes, w, decay_shift=3, v_threshold=64)
        np.testing.assert_array_equal(np.asarray(got[0]), np.asarray(want[0]))
