"""work.py and the dense network's work counts against counts made by
hand."""

import pytest

import work
from networks import dense


def _call(cfg, lanes_busy, chunk_steps):
    return work.stack_call(dense.macs_per_lane_step(cfg),
                           dense.weight_bytes(cfg),
                           dense.lane_state_bytes(cfg), lanes_busy,
                           chunk_steps)


def test_paper_stack_by_hand():
    cfg = {"layer_sizes": [784, 10]}
    assert dense.macs_per_lane_step(cfg) == 7840
    assert dense.weight_bytes(cfg) == 2 * 7840
    # 784 px * (1 B pixel + 4 B PRNG) + 10 neurons * 9 B + 10 classes * 8 B
    # + five int32 scalars + the active flag
    assert dense.lane_state_bytes(cfg) == 3920 + 90 + 80 + 21
    c = _call(cfg, lanes_busy=64, chunk_steps=4)
    assert c["ops"] == 2 * 64 * 7840 * 4
    assert c["bytes"] == 2 * 7840 + 2 * 64 * 4111


def test_wide_stack_by_hand():
    cfg = {"layer_sizes": [784, 2048, 2048, 10]}
    syn = 784 * 2048 + 2048 * 2048 + 2048 * 10
    assert dense.macs_per_lane_step(cfg) == syn == 5_820_416
    lane = 784 * 5 + (2048 + 2048 + 10) * 9 + 10 * 8 + 21
    assert dense.lane_state_bytes(cfg) == lane
    c = _call(cfg, lanes_busy=32, chunk_steps=4)
    assert c["ops"] == 2 * 32 * syn * 4
    assert c["bytes"] == 2 * syn + 2 * 32 * lane


def test_least_time_names_its_bound():
    peak = {"int8_ops_per_s": 100.0, "hbm_bytes_per_s": 10.0}
    assert work.least_time_s({"ops": 500.0, "bytes": 20.0}, peak) == (
        5.0, "compute")
    assert work.least_time_s({"ops": 100.0, "bytes": 20.0}, peak) == (
        2.0, "memory")


def test_unknown_device_is_an_error():
    assert work.peaks_for("TPU v5 lite")["int8_ops_per_s"] == 393e12
    with pytest.raises(KeyError):
        work.peaks_for("cpu")
