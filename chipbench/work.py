"""Operations and bytes of one call, from counts of the network's work
(``networks/<network>.py``: multiply-accumulates a lane step, weight
bytes, lane-state bytes), and the chip's peaks.

The counts are the work the algorithm needs, not what an implementation
happens to do: no tile skipping, packing or padding enters them, so they
stay the same whatever kernel computes the call.
"""

from __future__ import annotations

import json
import os


def stack_call(macs: int, weight_bytes: int, lane_bytes: int,
               lanes_busy: float, chunk_steps: int) -> dict:
    """One launch of the fused stack kernel over ``chunk_steps`` steps.

    Operations: 2 x lanes busy x ``macs`` (one lane's multiply-accumulates
    a step) x chunk steps (a multiply and an add each).  Bytes: one read
    of every weight code, and the busy lanes' state (``lane_bytes`` each)
    read and written once.
    """
    return {"ops": 2.0 * lanes_busy * macs * chunk_steps,
            "bytes": float(weight_bytes + 2 * lanes_busy * lane_bytes)}


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
