"""Host time to harvest one retired request: the summed ``snn.harvest``
spans inside the window over the requests they retired (their ``n``)."""

import program_spans


def read(run):
    return program_spans.per_request_ms(run, "snn.harvest")
