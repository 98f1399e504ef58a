"""Host time to admit one request into a lane (its PRNG seeding
included): the summed ``snn.admit`` spans inside the window over the
requests they admitted (their ``n``)."""

import program_spans


def read(run):
    return program_spans.per_request_ms(run, "snn.admit")
