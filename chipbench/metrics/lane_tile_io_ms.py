"""Whole lane-tile transfers per ``step()``: the summed ``snn.readback``
(device to host) and ``snn.upload`` (host to device) spans over the
``snn.step`` spans inside the window."""

import program_spans


def read(run):
    return program_spans.per_step_ms(run, "snn.readback", "snn.upload")
