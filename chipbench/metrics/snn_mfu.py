"""Whole-step share of the chips' int8 peak, in %: the dense-equivalent
operations of the requests retired in the window (their window steps x
2 x the network's multiply-accumulates a lane step at the published
widths) over window seconds x chips x peak."""


def read(run):
    if run.peaks is None or run.window.seconds <= 0:
        return None
    ops = (2.0 * run.network.macs_per_lane_step(run.config)
           * sum(run.retired_steps.values()))
    peak = float(run.peaks["int8_ops_per_s"]) * run.chips
    return 100.0 * ops / (run.window.seconds * peak)
