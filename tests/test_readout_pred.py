"""readout_pred edge cases.

The helper is the single source of truth for predictions across
snn_apply_int, the streaming engine's gate/harvest paths and the fused
kernel's in-kernel mirror — previously its corner semantics were only
exercised indirectly through the engine e2e test.  Contracts:

  * ``count`` with all-zero registers degenerates to argmax-of-zeros
    (class 0) — callers that must not act on it guard with their own
    has-spike check (the engine's gate does exactly that);
  * ``first_spike`` ties break lowest-index-wins, matching jnp.argmax and
    the kernel's iota+min implementation;
  * any spiked class outranks every membrane-only class (the two score
    tiers), which is the count/first-spike tiebreak the active-pruning
    config relies on (a pruned neuron fires at most once, so counts alone
    cannot rank spiked classes — arrival order must).
"""

import dataclasses

import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.snn_mnist import SNN_CONFIG_PRUNED
from repro.core import prng, snn
from repro.core.snn import readout_pred

T = 20
SENT = T  # first-spike sentinel: "never spiked"


def _first(*ts):
    return jnp.asarray([list(ts)], jnp.int32)


def test_count_all_zero_registers_is_class_zero():
    counts = jnp.zeros((3, 5), jnp.int32)
    first = jnp.full((3, 5), SENT, jnp.int32)
    v = jnp.asarray(np.arange(15).reshape(3, 5), jnp.int32)
    pred = readout_pred(counts, first, v, "count", T)
    assert (np.asarray(pred) == 0).all()


def test_first_spike_all_zero_counts_falls_back_to_membrane():
    counts = jnp.zeros((1, 4), jnp.int32)
    first = jnp.full((1, 4), SENT, jnp.int32)
    v = jnp.asarray([[5, -3, 9, 2]], jnp.int32)
    assert int(readout_pred(counts, first, v, "first_spike", T)[0]) == 2


def test_first_spike_membrane_tiebreak_lowest_index():
    counts = jnp.zeros((1, 4), jnp.int32)
    first = jnp.full((1, 4), SENT, jnp.int32)
    v = jnp.asarray([[5, 9, 9, 2]], jnp.int32)
    assert int(readout_pred(counts, first, v, "first_spike", T)[0]) == 1


def test_first_spike_tie_lowest_index_wins():
    counts = jnp.asarray([[0, 1, 1, 0]], jnp.int32)
    first = _first(SENT, 3, 3, SENT)
    v = jnp.asarray([[0, 0, 10_000, 0]], jnp.int32)  # membrane must not rank
    assert int(readout_pred(counts, first, v, "first_spike", T)[0]) == 1


def test_first_spike_earliest_beats_higher_count():
    counts = jnp.asarray([[0, 1, 7, 0]], jnp.int32)
    first = _first(SENT, 2, 9, SENT)
    v = jnp.zeros((1, 4), jnp.int32)
    assert int(readout_pred(counts, first, v, "first_spike", T)[0]) == 1


def test_spiked_class_outranks_any_membrane():
    """Two score tiers: a last-step spike beats a near-threshold silent
    class, for any realistic window length."""
    counts = jnp.asarray([[0, 0, 0, 1]], jnp.int32)
    first = _first(SENT, SENT, SENT, T - 1)
    v = jnp.asarray([[(1 << 24) - 2, 127, 0, -5]], jnp.int32)
    assert int(readout_pred(counts, first, v, "first_spike", T)[0]) == 3


def test_count_vs_first_spike_tiebreak_on_pruned_config():
    """Active pruning clamps every register to {0, 1}: the count readout
    degenerates to lowest-index-of-the-spiked-set while the pruned
    config's first_spike readout ranks by arrival — the exact divergence
    the paper's §III-D readout swap exists for."""
    counts = jnp.asarray([[1, 1, 1, 0]], jnp.int32)
    first = _first(5, 2, 9, SENT)
    v = jnp.zeros((1, 4), jnp.int32)
    assert int(readout_pred(counts, first, v, "count", T)[0]) == 0
    assert SNN_CONFIG_PRUNED.readout == "first_spike"
    assert int(readout_pred(counts, first, v,
                            SNN_CONFIG_PRUNED.readout, T)[0]) == 1


def test_membrane_peak_tiebreak_lowest_index():
    """The streamed membrane path ranks by the carried peak accumulator:
    ties break lowest-index-wins (jnp.argmax), matching the gated
    kernel's iota+min mirror."""
    counts = jnp.zeros((1, 4), jnp.int32)
    first = jnp.full((1, 4), SENT, jnp.int32)
    v_final = jnp.asarray([[0, 0, 0, 99]], jnp.int32)   # must not rank
    v_peak = jnp.asarray([[3, 9, 9, 3]], jnp.int32)
    assert int(readout_pred(counts, first, v_final, "membrane", T,
                            v_peak=v_peak)[0]) == 1


def test_membrane_pred_follows_peak_not_final_or_trace_sum():
    """Peak semantics: a class whose membrane spiked high once and decayed
    outranks a class that ends higher (v_final) or integrates higher —
    and the v_peak accumulator path agrees with the v_trace path."""
    v_trace = jnp.asarray([[[0, 50], [100, 60], [0, 70]]], jnp.int32)
    v_trace = jnp.swapaxes(v_trace, 0, 1)              # (T=3, B=1, 2)
    counts = jnp.zeros((1, 2), jnp.int32)
    first = jnp.full((1, 2), SENT, jnp.int32)
    v_final = jnp.asarray([[0, 70]], jnp.int32)
    from_trace = readout_pred(counts, first, v_final, "membrane", T,
                              v_trace=v_trace)
    from_peak = readout_pred(counts, first, v_final, "membrane", T,
                             v_peak=jnp.max(v_trace, axis=0))
    assert int(from_trace[0]) == int(from_peak[0]) == 0


def test_membrane_chunked_peak_matches_one_shot_pred(rng):
    """The carried v_peak of a chunked window reproduces the one-shot
    membrane prediction — the streamed path of the readout contract."""
    cfg = dataclasses.replace(SNN_CONFIG_PRUNED, layer_sizes=(24, 8),
                              num_steps=10, readout="membrane",
                              active_pruning=False)
    params_q = {"layers": [{
        "w_q": jnp.asarray(rng.integers(-200, 200, (24, 8)), jnp.int16),
        "scale": jnp.float32(1.0)}]}
    px = jnp.asarray(rng.integers(0, 256, (5, 24), dtype=np.uint8))
    state0 = prng.seed_state(41, px.shape)
    one_shot = snn.snn_apply_int(params_q, px, state0, cfg,
                                 backend="reference")
    ws = snn.snn_window_init(params_q, state0, cfg)
    for chunk in (4, 3, 3):
        ws, _ = snn.snn_window_chunk(params_q, px, ws, cfg,
                                     chunk_steps=chunk, backend="reference")
    streamed = readout_pred(ws.counts, ws.first, ws.v[-1], "membrane",
                            cfg.num_steps, v_peak=ws.v_peak[-1])
    np.testing.assert_array_equal(np.asarray(streamed),
                                  np.asarray(one_shot["pred"]))


def test_pruned_engine_counts_are_saturated(rng):
    """End-to-end guard for the tiebreak above: under the pruned config
    every neuron fires at most once, so the registers really are 0/1 and
    first-spike times are the only ranking signal among spiked classes."""
    cfg = dataclasses.replace(SNN_CONFIG_PRUNED, layer_sizes=(16, 6),
                              num_steps=12)
    params_q = {"layers": [{
        "w_q": jnp.asarray(rng.integers(-64, 256, (16, 6)), jnp.int16),
        "scale": jnp.float32(1.0)}]}
    px = jnp.asarray(rng.integers(64, 256, (4, 16), dtype=np.uint8))
    out = snn.snn_apply_int(params_q, px, prng.seed_state(9, px.shape),
                            cfg, backend="reference")
    counts = np.asarray(out["spike_counts"])
    first = np.asarray(out["first_spike_t"])
    assert counts.max() <= 1 and counts.max() == 1
    np.testing.assert_array_equal(
        np.asarray(out["pred"]),
        np.asarray(readout_pred(out["spike_counts"], out["first_spike_t"],
                                out["v_final"], cfg.readout,
                                cfg.num_steps)))
    # spiked ⇔ a real first-spike time; silent ⇔ sentinel
    assert ((first < cfg.num_steps) == (counts == 1)).all()


_I32 = np.iinfo(np.int32)


def _rows(counts, first, v):
    return (np.asarray(counts, np.int32), np.asarray(first, np.int32),
            np.asarray(v, np.int32))


def _random_rows(seed, b=6, n=10):
    r = np.random.default_rng(seed)
    counts = r.integers(0, 3, (b, n)) * (r.random((b, n)) < 0.4)
    first = np.where(counts > 0, r.integers(0, T, (b, n)), SENT)
    return _rows(counts, first, r.integers(-300, 300, (b, n)))


# (counts, first, v): v stands for both v_final and v_peak; each readout
# ranks the one it reads.
_HOST_CASES = {
    "all_zero": _rows(np.zeros((2, 10)), np.full((2, 10), SENT),
                      np.zeros((2, 10))),
    "all_zero_v_peak_sentinel": _rows(np.zeros((1, 10)),
                                      np.full((1, 10), SENT),
                                      np.full((1, 10), _I32.min)),
    "count_tie": _rows([[0, 2, 2, 1]], [[SENT, 1, 4, 6]], [[0, 0, 9, 9]]),
    "first_spike_tie": _rows([[0, 1, 1, 0]], [[SENT, 3, 3, SENT]],
                             [[0, 0, 10_000, 0]]),
    "membrane_tie": _rows([[0, 0, 0, 0]], [[SENT] * 4], [[5, 9, 9, 2]]),
    "spiked_outranks_membrane": _rows([[0, 0, 0, 1]],
                                      [[SENT, SENT, SENT, T - 1]],
                                      [[(1 << 24) - 2, 127, 0, -5]]),
    "int32_max_membranes": _rows([[0, 0, 1, 0]], [[SENT, SENT, T - 1, SENT]],
                                 [[_I32.max, _I32.max, 0, _I32.min]]),
    "int32_max_silent": _rows(np.zeros((1, 4)), np.full((1, 4), SENT),
                              [[_I32.min, _I32.max, _I32.max, _I32.min]]),
    "int32_min_silent": _rows(np.zeros((1, 4)), np.full((1, 4), SENT),
                              [[_I32.min, _I32.min, _I32.min + 1, _I32.min]]),
    "clip_ties_above_large": _rows(np.zeros((1, 4)), np.full((1, 4), SENT),
                                   [[1 << 24, (1 << 24) + 7, _I32.max, 3]]),
    "random_0": _random_rows(0),
    "random_1": _random_rows(1),
    "random_2": _random_rows(2),
}


@pytest.mark.parametrize("readout", ["count", "first_spike", "membrane"])
@pytest.mark.parametrize("case", sorted(_HOST_CASES))
def test_host_readout_matches_jnp(case, readout):
    """``xp=np`` (the engine's harvest) ranks host rows exactly as the jnp
    default: the count argmax, the two first-spike tiers with their clip,
    the peak-membrane argmax, lowest index on every tie; and whole rows as
    single lanes."""
    counts, first, v = _HOST_CASES[case]
    want = np.asarray(readout_pred(jnp.asarray(counts), jnp.asarray(first),
                                   jnp.asarray(v), readout, T,
                                   v_peak=jnp.asarray(v)))
    got = readout_pred(counts, first, v, readout, T, v_peak=v, xp=np)
    assert type(got) is np.ndarray
    np.testing.assert_array_equal(got, want)
    for i in range(counts.shape[0]):
        lane = readout_pred(counts[i], first[i], v[i], readout, T,
                            v_peak=v[i], xp=np)
        assert isinstance(lane, np.integer) and int(lane) == int(want[i])
