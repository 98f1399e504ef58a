"""Streaming engine round at capacity: the window's milliseconds over its
step() calls (host clock), so the harness's top-up and record time between
rounds is in it."""


def read(run):
    w = run.window
    if w.step_calls == 0:
        return None
    return w.seconds * 1e3 / w.step_calls
