"""Host spans of the serving engine's phases, recorded while profiling.

A span is recorded only while a JAX profiler session is active
(``jax.profiler.trace``, ``start_trace`` or a profiler server); with none,
``span()`` costs one check and returns a no-op context.  While recording,
each span goes two places: a ``jax.profiler.TraceAnnotation`` of the same
name with its counts as metadata, so it lands in the trace on the device
ops' clock; and a bounded ring of :class:`Span` entries on the
``time.perf_counter_ns`` clock, one a process like the profiler session,
read by :func:`recorded`.  ``parent`` is the name of the span open around
it on the same thread.  Counts are given as keywords or set on the dict
the ``with`` statement yields (``None`` when not recording).
"""

from __future__ import annotations

import contextlib
import threading
import time
from collections import deque
from typing import NamedTuple

from jax._src.lib import _profiler
from jax.profiler import TraceAnnotation

__all__ = ["NAMES", "CAPACITY", "Span", "Recorded", "enabled", "span",
           "recorded"]

# the streaming engine's phases (serve.snn_engine)
NAMES = ("snn.step", "snn.sync", "snn.readback", "snn.harvest", "snn.admit",
         "snn.upload", "snn.dispatch")
CAPACITY = 1 << 17


class Span(NamedTuple):
    name: str
    start_ns: int
    end_ns: int
    parent: str | None
    counts: dict


class Recorded(NamedTuple):
    spans: tuple
    dropped: int               # spans pushed out of the full ring
    dropped_end_ns: int        # end of the latest span pushed out, or -1


_ring: deque = deque(maxlen=CAPACITY)
_lock = threading.Lock()
_drops = [0, -1]               # count, end_ns of the latest drop
_open = threading.local()


# True while a profiler session is active
enabled = _profiler.TraceMe.is_enabled
_OFF = contextlib.nullcontext()


def _xplane(counts: dict) -> dict:
    """Counts as trace metadata: a sequence becomes a space-separated
    string, and an empty one is left out, as the trace would drop it."""
    out = {}
    for k, v in counts.items():
        if isinstance(v, (tuple, list)):
            v = " ".join(str(x) for x in v)
        if v != "":
            out[k] = v
    return out


class _On:
    __slots__ = ("name", "counts", "parent", "start", "note")

    def __init__(self, name: str, counts: dict):
        self.name = name
        self.counts = counts

    def __enter__(self) -> dict:
        stack = getattr(_open, "stack", None)
        if stack is None:
            stack = _open.stack = []
        self.parent = stack[-1] if stack else None
        stack.append(self.name)
        self.note = TraceAnnotation(self.name)
        self.note.__enter__()
        self.start = time.perf_counter_ns()
        return self.counts

    def __exit__(self, *exc) -> bool:
        end = time.perf_counter_ns()
        if self.counts:
            self.note.set_metadata(**_xplane(self.counts))
        self.note.__exit__(*exc)
        _open.stack.pop()
        entry = Span(self.name, self.start, end, self.parent, self.counts)
        with _lock:
            if len(_ring) == CAPACITY:
                _drops[0] += 1
                _drops[1] = max(_drops[1], _ring[0].end_ns)
            _ring.append(entry)
        return False


def span(name: str, **counts):
    """A context manager that records ``name`` while profiling; it yields
    the span's counts, which the body may add to, or ``None`` when not
    recording."""
    return _On(name, counts) if enabled() else _OFF


def recorded() -> Recorded:
    """Every span in the ring, oldest end first, with the drop count."""
    with _lock:
        return Recorded(tuple(_ring), _drops[0], _drops[1])
