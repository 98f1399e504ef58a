"""A run with the timed path broken underneath has to come out with
``correct`` false: the harness runs as on the chip, its look for a chip
aside, while the program's chunk, its answer or its per-chip blocks are
broken."""

import json
import os

import jax
import pytest

from helpers import add_entries, copy_bench, run, write_json
from repro.serve import snn_engine


def _unchanged(real):
    def chunk(lanes, weights, **kw):
        _, tel = real(lanes, weights, **kw)
        return lanes, tel
    return chunk


def _keep_rows(new, old, lo):
    return jax.tree.map(lambda n, o: n.at[lo:].set(o[lo:]), new, old)


def _first_chip_only(real_make):
    def make(mesh, axis_name, *args, **kw):
        fn = real_make(mesh, axis_name, *args, **kw)

        def chunk(lanes, weights):
            out, tel = fn(lanes, weights)
            per_chip = lanes.active.shape[0] // mesh.shape[axis_name]
            return _keep_rows(out, lanes, per_chip), tel
        return chunk
    return make


def _wrong_answer(real):
    def host_pred(self, counts, first, v_last, v_peak):
        return (real(self, counts, first, v_last, v_peak) + 1) % self.n_out
    return host_pred


def test_sound_run_is_correct():
    line, checks = run("paper.digits.backlog")
    assert line["correct"] is True, checks


# Half of the batch left out is no fault this cell can show: the engine
# packs live lanes to the front of its tile each round, so a lane frozen
# for a round resumes in a served row and its answer comes out exact, only
# later (tests below: a chip's whole block left out is caught).
@pytest.mark.parametrize("fault", ["state_unchanged", "answer_altered"])
def test_fault_is_not_correct(monkeypatch, fault):
    if fault == "state_unchanged":
        monkeypatch.setattr(snn_engine, "stream_chunk",
                            _unchanged(snn_engine.stream_chunk))
    else:
        monkeypatch.setattr(snn_engine.SNNStreamEngine, "_host_pred",
                            _wrong_answer(snn_engine.SNNStreamEngine._host_pred))
    line, checks = run("paper.digits.backlog", seconds=0.3)
    assert line["correct"] is False, checks
    assert line["failed"] > 0


def test_four_chip_sound_and_blocks_left_out(monkeypatch, tmp_path):
    """A configuration on a 4x1 data mesh, added to a copy, runs through
    the sharded engine on four virtual devices and is correct; a chip's
    lane block that never comes back is caught."""
    root = copy_bench(tmp_path)
    configs = os.path.join(root, "chipbench", "configs")
    with open(os.path.join(configs, "snn-paper-784x10.json")) as f:
        cfg = json.load(f)
    cfg.update(name="snn-paper-784x10-dp4", mesh={"data": 4, "model": 1},
               overlap=True)
    write_json(os.path.join(configs, "snn-paper-784x10-dp4.json"), cfg)
    add_entries(
        root,
        configs=[{"name": "snn-paper-784x10-dp4", "source": "test",
                  "file": "chipbench/configs/snn-paper-784x10-dp4.json",
                  "reduced": [], "why": "test"}],
        workloads=[{"name": "paper-dp4.digits.backlog",
                    "config": "snn-paper-784x10-dp4",
                    "traffic": "digits.backlog", "chips": 4, "why": "test"}])
    line, checks = run("paper-dp4.digits.backlog", seconds=0.3, root=root)
    assert line["correct"] is True, checks
    assert line["attempted"] > 0
    # a data mesh has no collective: the exchange that can be lost is
    # each chip's lane block coming back from its chunk
    monkeypatch.setattr(snn_engine, "make_sharded_stream_chunk",
                        _first_chip_only(snn_engine.make_sharded_stream_chunk))
    line, checks = run("paper-dp4.digits.backlog", seconds=0.3, root=root)
    assert line["correct"] is False, checks
