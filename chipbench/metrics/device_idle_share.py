"""1 - the union of device op intervals over the traced window, averaged
over the cell's chips (profiler trace)."""


def read(run):
    t = run.trace
    if t is None or t.window_s <= 0:
        return None
    return 1.0 - t.busy_s / t.window_s
