"""Requests retired in the window over the window's seconds (host clock).
Defined for traffic at capacity (backlog), where the queue never runs dry."""


def read(run):
    if run.window.seconds <= 0:
        return None
    return run.window.retired_in_window / run.window.seconds
