"""Operations and bytes of one call, from shapes and configuration alone.

The counts are the work the algorithm needs, not what an implementation
happens to do: no tile skipping, packing or padding enters them, so they
stay the same whatever kernel computes the call.
"""

from __future__ import annotations

import json
import os

WEIGHT_CODE_BYTES = 2        # a signed 9-bit code held in 2 bytes


def synapses(layer_sizes) -> int:
    """sum_l K_l * N_l: multiply-accumulates of one lane in one step."""
    return sum(int(k) * int(n) for k, n in zip(layer_sizes[:-1],
                                               layer_sizes[1:]))


def lane_state_bytes(layer_sizes) -> int:
    """Bytes of one lane's carried window state at unpadded widths: uint8
    pixels and uint32 PRNG lanes per input; per neuron an int32 membrane,
    an int32 running peak and a bool enable; int32 spike counts and
    first-spike times per class; int32 gate memory, streak, steps, adds
    and weight version, and a bool active flag."""
    n_in, n_out = int(layer_sizes[0]), int(layer_sizes[-1])
    neurons = sum(int(n) for n in layer_sizes[1:])
    return n_in * (1 + 4) + neurons * (4 + 4 + 1) + n_out * (4 + 4) \
        + 5 * 4 + 1


def stack_call(layer_sizes, lanes_busy: float, chunk_steps: int) -> dict:
    """One launch of the fused stack kernel over ``chunk_steps`` steps.

    Operations: 2 x lanes busy x sum K*N x chunk steps (a multiply and an
    add per synapse).  Bytes: one read of every weight code at 2 B, and
    the busy lanes' state read and written once.
    """
    syn = synapses(layer_sizes)
    return {"ops": 2.0 * lanes_busy * syn * chunk_steps,
            "bytes": float(WEIGHT_CODE_BYTES * syn
                           + 2 * lanes_busy * lane_state_bytes(layer_sizes))}


def least_time_s(call: dict, peak: dict) -> tuple[float, str]:
    """The larger of ops over the int8 peak and bytes over HBM bandwidth,
    and which of the two bounds the call."""
    t_ops = call["ops"] / float(peak["int8_ops_per_s"])
    t_mem = call["bytes"] / float(peak["hbm_bytes_per_s"])
    return (t_ops, "compute") if t_ops >= t_mem else (t_mem, "memory")


def peaks_for(device_kind: str) -> dict:
    """The chip's published peaks; a device not in the table is an error."""
    with open(os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           "peaks.json")) as f:
        table = json.load(f)
    if device_kind not in table:
        raise KeyError(f"device kind {device_kind!r} has no entry in "
                       f"peaks.json ({sorted(table)}); add its published "
                       f"peaks before measuring on it")
    return table[device_kind]
