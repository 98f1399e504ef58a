"""The program's own spans (``repro.serve.spans``) that lie wholly inside
a run's measured window, for the metric readers built on them.

There is nothing to read, and a reader returns None, where the run has no
device trace (a run on the CPU, as for ``device_idle_share``), where the
program records no spans (a tree without ``repro.serve.spans``), or where
the recorder's ring dropped spans inside the window.
"""


def names() -> tuple:
    """The names of the program's spans, or none on a tree without the
    recorder."""
    try:
        from repro.serve import spans
    except ImportError:
        return ()
    return tuple(spans.NAMES)


def in_window(run) -> dict | None:
    """Span name -> the spans of that name inside ``run``'s window, or
    None if there is nothing to read."""
    if run.trace is None:
        return None
    try:
        from repro.serve import spans
    except ImportError:
        return None
    rec = spans.recorded()
    t0, t1 = run.window.t0 * 1e9, run.window.t1 * 1e9
    if rec.dropped and rec.dropped_end_ns >= t0:
        return None
    out: dict = {}
    for s in rec.spans:
        if t0 <= s.start_ns and s.end_ns <= t1:
            out.setdefault(s.name, []).append(s)
    return out


def ms(spans) -> float:
    """Summed duration of ``spans`` in milliseconds."""
    return sum(s.end_ns - s.start_ns for s in spans) * 1e-6


def per_step_ms(run, *names) -> float | None:
    """Summed milliseconds of the spans ``names`` over ``snn.step`` spans."""
    got = in_window(run)
    if not got or not got.get("snn.step"):
        return None
    return sum(ms(got.get(n, [])) for n in names) / len(got["snn.step"])


def per_request_ms(run, name) -> float | None:
    """Summed milliseconds of the spans ``name`` over their requests
    (their ``n`` counts)."""
    got = in_window(run)
    if not got or not got.get(name):
        return None
    n = sum(s.counts.get("n", 0) for s in got[name])
    return ms(got[name]) / n if n else None
