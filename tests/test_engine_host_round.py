"""The engine round's per-request work stays in host memory.

Contracts under test:
  * with a tile read back, ``SNNStreamEngine._harvest`` of the retired
    lanes and ``_admit_into`` of queued requests into their slots move
    nothing between host and device (``jax.transfer_guard``): the
    readout ranks the numpy rows and the PRNG lanes are seeded in numpy;
  * a full ``run()`` gives the same results (pred, counts, steps, adds)
    as an engine that ranks each retired lane with ``jnp`` and seeds each
    admission through ``seed_state`` on the device, for every readout;
  * the tile crosses as one packed slab each way (``LaneTileCodec``):
    ``_read_tile`` makes one device-to-host copy and ``_upload`` one
    host-to-device put, where the leaf-by-leaf path makes one per leaf;
    and ``run()`` across a weight rollout gives the same results
    (weight version included) as that leaf-by-leaf path, for every
    readout and for a conv-pool stack.
"""

import dataclasses
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.snn_mnist import SNN_CONFIG
from repro.core import prng
from repro.core.layers import ConvPool, Dense, weight_shapes
from repro.core.snn import readout_pred
from repro.serve import SNNStreamEngine, snn_engine

SIZES = (24, 12, 10)
N_REQ = 40
SEED = 3000000019


# 9x9 pixels, a 4x4 conv to 3 channels, 2x2-pooled (27 neurons), FC 27->5
CONV = (ConvPool(3, (1, 9, 9), 4, 2), Dense(5))


def _params(rng, cfg):
    return {"layers": [
        {"w_q": jnp.asarray(rng.integers(-256, 256, s), jnp.int16),
         "scale": jnp.float32(1.0)}
        for s in weight_shapes(cfg.n_in, cfg.layer_descs)]}


def _engine(readout, engine_cls=SNNStreamEngine, patience=1, layers=None):
    rng = np.random.default_rng(4)
    if layers is None:
        cfg = dataclasses.replace(SNN_CONFIG, layer_sizes=SIZES,
                                  num_steps=10, readout=readout)
    else:
        cfg = dataclasses.replace(SNN_CONFIG, layers=layers, num_steps=10,
                                  readout=readout, qat=False)
    eng = engine_cls(_params(rng, cfg), cfg, batch_size=8, chunk_steps=3,
                     patience=patience, seed=SEED)
    for im in rng.integers(0, 256, (N_REQ, cfg.n_in), dtype=np.uint8):
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


class _LeafByLeafEngine(SNNStreamEngine):
    """The tile crosses, and is reordered, one ``LaneState`` leaf at a
    time."""

    def _read_tile(self):
        return jax.tree.map(np.array, self.lanes)

    def _reorder(self, st, order):
        return jax.tree.map(lambda a: a[order], st)

    def _upload(self, st):
        return jax.tree.map(jnp.asarray, st)


@pytest.mark.parametrize("engine_cls", [SNNStreamEngine, _LeafByLeafEngine])
def test_the_tile_crosses_once_each_way(monkeypatch, capfd, engine_cls):
    eng = _engine("count", engine_cls)
    for _ in range(3):
        eng.step()
    jax.block_until_ready(eng.lanes)
    n_leaves = len(jax.tree.leaves(eng.lanes))
    want = 1 if engine_cls is SNNStreamEngine else n_leaves
    copies = []
    to_numpy = np.array

    def spy(a, *args, **kwargs):
        if isinstance(a, jax.Array):
            copies.append(a.shape)
        return to_numpy(a, *args, **kwargs)

    with monkeypatch.context() as m:
        m.setattr(np, "array", spy)
        st = eng._read_tile()
    assert len(copies) == want
    capfd.readouterr()
    with jax.transfer_guard_host_to_device("log_explicit"):
        jax.block_until_ready(eng._upload(st))
    assert capfd.readouterr().err.count("host-to-device transfer") == want


def _as_full_tuples(results):
    return {rid: (r.pred, r.spike_counts.tolist(), r.steps, r.adds,
                  r.early_exit, r.weight_version)
            for rid, r in results.items()}


def _run_across_a_rollout(engine_cls, readout, layers):
    eng = _engine(readout, engine_cls, layers=layers)
    for _ in range(3):
        eng.step()
    eng.begin_rollout(_params(np.random.default_rng(5), eng.cfg))
    return _as_full_tuples(eng.run())


@pytest.mark.parametrize("readout, layers", [
    ("count", None), ("first_spike", None), ("membrane", None),
    ("count", CONV)], ids=["count", "first_spike", "membrane", "conv"])
def test_run_matches_the_leaf_by_leaf_crossing(readout, layers):
    packed = _run_across_a_rollout(SNNStreamEngine, readout, layers)
    leafwise = _run_across_a_rollout(_LeafByLeafEngine, readout, layers)
    assert sorted(packed) == list(range(N_REQ))
    assert {r[-1] for r in packed.values()} == {0, 1}
    assert packed == leafwise
