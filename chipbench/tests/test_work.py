"""work.py against counts made by hand."""

import pytest

import work


def test_paper_stack_by_hand():
    sizes = [784, 10]
    assert work.synapses(sizes) == 7840
    # 784 px * (1 B pixel + 4 B PRNG) + 10 neurons * 9 B + 10 classes * 8 B
    # + five int32 scalars + the active flag
    assert work.lane_state_bytes(sizes) == 3920 + 90 + 80 + 21
    c = work.stack_call(sizes, lanes_busy=64, chunk_steps=4)
    assert c["ops"] == 2 * 64 * 7840 * 4
    assert c["bytes"] == 2 * 7840 + 2 * 64 * 4111


def test_wide_stack_by_hand():
    sizes = [784, 2048, 2048, 10]
    syn = 784 * 2048 + 2048 * 2048 + 2048 * 10
    assert work.synapses(sizes) == syn == 5_820_416
    lane = 784 * 5 + (2048 + 2048 + 10) * 9 + 10 * 8 + 21
    assert work.lane_state_bytes(sizes) == lane
    c = work.stack_call(sizes, lanes_busy=32, chunk_steps=4)
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
