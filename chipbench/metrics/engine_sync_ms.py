"""Host time blocked on the previous chunk, per ``step()``: the summed
``snn.sync`` spans (the readback of the lanes' active mask) over the
``snn.step`` spans inside the window."""

import program_spans


def read(run):
    return program_spans.per_step_ms(run, "snn.sync")
