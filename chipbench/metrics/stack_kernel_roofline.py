"""The fused stack kernel's share of its roofline, in %.

The least time of the window's launches (``work.py``: the larger of
int8 operations over the int8 peak and bytes over HBM bandwidth, from
the network's multiply-accumulates, weight bytes and lane-state bytes at
the mean lanes in flight per chip) over the summed device time of the
kernel's own events in the trace.  The launches are the chunks the
engine dispatched in the window, not the events matched, so an op
matched twice can only lower the share.

The kernel is the one Pallas custom call (``tpu_custom_call``) of the
jitted launcher ``fused_snn_stack_op``: ``_stack_kernel``.  The launcher's
padding and weight packing are ops of their own and are not its time.
Which ops matched, and which bound applies, go into the run's notes.
"""

import trace_reduce
import work

LAUNCHER = "fused_snn_stack_op"


def is_stack_kernel(name: str, text: str) -> bool:
    """The op is a custom call, and it is ``_stack_kernel`` by name or
    the one custom call of the launcher.  On a v5e the op's name is its
    whole HLO instruction, ``%fused_snn_stack_op.1 = (...)
    custom-call(...), custom_call_target="tpu_custom_call"``."""
    full = f"{name} {text}"
    call = "custom-call" in full or "tpu_custom_call" in full
    return call and ("_stack_kernel" in full
                     or name.lstrip("%").startswith(LAUNCHER + ".")
                     or f"jit({LAUNCHER})/pallas_call" in full)


def read(run):
    if (run.trace is None or run.peaks is None or not run.launches
            or run.window.step_calls == 0):
        return None
    secs, events, ops = trace_reduce.kernel_time(run.trace, is_stack_kernel)
    if secs <= 0:
        return None
    devices = run.config["mesh"]["data"] * run.config["mesh"]["model"]
    lanes = run.window.busy_lanes / run.window.step_calls / devices
    net, cfg = run.network, run.config
    call = work.stack_call(net.macs_per_lane_step(cfg), net.weight_bytes(cfg),
                           net.lane_state_bytes(cfg), lanes, run.chunk_steps)
    least, bound = work.least_time_s(call, run.peaks)
    run.notes.append(
        f"stack_kernel_roofline: ops {ops}: {events} events for "
        f"{run.launches} launches, {secs:.6f} s of device time, least "
        f"time {least * 1e6:.3f} us a launch, {bound}-bound "
        f"(ops {call['ops']:.0f}, bytes {call['bytes']:.0f})")
    return 100.0 * run.launches * least / secs
