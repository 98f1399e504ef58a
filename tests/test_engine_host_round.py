"""The engine round's per-request work stays in host memory.

Contracts under test:
  * with a tile read back, ``SNNStreamEngine._harvest`` of the retired
    lanes and ``_admit_into`` of queued requests into their slots move
    nothing between host and device (``jax.transfer_guard``): the
    readout ranks the numpy rows and the PRNG lanes are seeded in numpy;
  * a full ``run()`` gives the same results (pred, counts, steps, adds)
    as an engine that ranks each retired lane with ``jnp`` and seeds each
    admission through ``seed_state`` on the device, for every readout.
"""

import dataclasses
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.snn_mnist import SNN_CONFIG
from repro.core import prng
from repro.core.snn import readout_pred
from repro.serve import SNNStreamEngine, snn_engine

SIZES = (24, 12, 10)
N_REQ = 40
SEED = 3000000019


def _engine(readout, engine_cls=SNNStreamEngine, patience=1):
    rng = np.random.default_rng(4)
    params_q = {"layers": [
        {"w_q": jnp.asarray(rng.integers(-256, 256, (a, b)), jnp.int16),
         "scale": jnp.float32(1.0)}
        for a, b in zip(SIZES[:-1], SIZES[1:])]}
    cfg = dataclasses.replace(SNN_CONFIG, layer_sizes=SIZES, num_steps=10,
                              readout=readout)
    eng = engine_cls(params_q, cfg, batch_size=8, chunk_steps=3,
                     patience=patience, seed=SEED)
    for im in rng.integers(0, 256, (N_REQ, SIZES[0]), dtype=np.uint8):
        eng.submit(im)
    return eng


@pytest.mark.parametrize("readout", ["count", "first_spike", "membrane"])
def test_harvest_and_admission_move_nothing_to_or_from_the_device(readout):
    eng = _engine(readout)
    for _ in range(20):
        eng.step()
        occupied = np.array([r is not None for r in eng.lane_req])
        st = eng._read_tile()
        finished = occupied & ~st.active
        if finished.any() and len(eng.queue) >= finished.sum():
            break
    assert finished.any() and eng.queue
    slots = np.nonzero(finished)[0].tolist()
    with jax.transfer_guard("disallow_explicit"):
        done = eng._harvest(st, finished)
        for slot in slots:
            eng._admit_into(st, slot)
    assert len(done) == len(slots)
    for rid in done:
        assert 0 <= eng.results[rid].pred < SIZES[-1]
    for slot in slots:
        rid = eng.lane_req[slot]
        np.testing.assert_array_equal(
            st.rng[slot], np.asarray(prng.seed_state(SEED + rid,
                                                     (SIZES[0],))))
        assert st.active[slot] and st.steps[slot] == 0


class _DeviceRoundTripEngine(SNNStreamEngine):
    """Each retired lane ranked by ``jnp`` on the device."""

    def _host_pred(self, counts, first, v_last, v_peak):
        return int(readout_pred(counts, first, v_last, self.cfg.readout,
                                self.cfg.num_steps, v_peak=v_peak))


def _as_tuples(results):
    return {rid: (r.pred, r.spike_counts.tolist(), r.steps, r.adds,
                  r.early_exit) for rid, r in results.items()}


@pytest.mark.parametrize("patience", [1, 10_000])
@pytest.mark.parametrize("readout", ["count", "first_spike", "membrane"])
def test_run_matches_the_device_round_trip_path(monkeypatch, readout,
                                                patience):
    host = _as_tuples(_engine(readout, patience=patience).run())
    monkeypatch.setattr(snn_engine, "prng_mod", types.SimpleNamespace(
        seed_state_host=lambda s, shape: np.asarray(
            prng.seed_state(s, shape))))
    device = _as_tuples(
        _engine(readout, _DeviceRoundTripEngine, patience).run())
    assert sorted(host) == list(range(N_REQ))
    assert host == device
