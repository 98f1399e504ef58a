"""Share of the lane-steps dispatched that served a request: the window
steps of the requests retired in the window over step() calls x lanes x
chunk steps.  Lanes idle after a retirement until the next round, empty
lanes and steps past a retirement inside a chunk are the rest."""


def read(run):
    w = run.window
    if w.step_calls == 0:
        return None
    dispatched = w.step_calls * run.lanes_total * run.chunk_steps
    return sum(run.retired_steps.values()) / dispatched
