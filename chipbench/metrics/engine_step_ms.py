"""Mean duration of the engine's ``step()``: the program's ``snn.step``
spans inside the window (host clock, ``repro.serve.spans``)."""

import program_spans


def read(run):
    got = program_spans.in_window(run)
    if not got or not got.get("snn.step"):
        return None
    return program_spans.ms(got["snn.step"]) / len(got["snn.step"])
