"""From a profiler trace to device busy time, kernel time and idle gaps.

The reduction works on plain interval lists, so it can be checked on a
synthetic trace: each device is a list of ``(op name, start ns, end ns)``
events, the HLO text that the profiler attached to each op name is in
``op_text``, and the harness's own host spans are a list of ``(span
name, start ns, end ns)``, to which the program's own spans may be
added (``repro.serve.spans.NAMES``, nested inside ``engine.step``).
``from_xplane`` fills them from the ``.xplane.pb`` file that
``jax.profiler`` writes.

- The window is the first harness span's start to the last one's end
  (``SPANS`` only: the program's spans never move it).
- Busy time of a device is the length of the union of its op intervals,
  clipped to the window; ``busy_s`` is its mean over the devices.
- An idle gap is a stretch of the window that no op covers.  A gap is
  cut at every span's start and end inside it, and each piece is put
  down to the innermost span open over it, the program's included
  ("none" when no span is open).
"""

from __future__ import annotations

import bisect
import glob
import itertools
import os
import re
from typing import NamedTuple

# the harness's host spans (jax.profiler.TraceAnnotation names)
SPANS = ("gen.submit", "gen.wait", "engine.step", "bench.record")
DEVICE_OPS_LINE = "XLA Ops"
DEVICE_PLANE = re.compile(r"^/device:([A-Z]+):(\d+)")
LABEL_STATS = ("long_name", "hlo_op", "tf_op", "name")


class Trace(NamedTuple):
    devices: dict          # device name -> [(op, start_ns, end_ns)]
    spans: list            # [(span, start_ns, end_ns)]
    op_text: dict = {}     # op -> the HLO text the profiler attached


class Summary(NamedTuple):
    window_s: float
    busy_s: float                 # mean over devices
    n_devices: int
    op_s: dict                    # op name -> seconds, mean over devices
    op_calls: dict                # op name -> events, summed over devices
    idle_by_span: dict            # span name -> idle seconds, mean over devices
    op_text: dict                 # op name -> its HLO text


def union(intervals) -> list:
    """Merge ``(start, end)`` intervals into disjoint sorted ones."""
    out: list = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return [tuple(iv) for iv in out]


def _gaps(merged, lo, hi) -> list:
    gaps, t = [], lo
    for s, e in merged:
        if s > t:
            gaps.append((t, s))
        t = max(t, e)
    if t < hi:
        gaps.append((t, hi))
    return gaps


class _Spans:
    """Spans sorted by start, the outer of two that start together
    first, for the innermost one open at a time."""

    def __init__(self, spans):
        self.spans = sorted(spans, key=lambda x: (x[1], -x[2]))
        self.starts = [s for _, s, _ in self.spans]
        # the latest end among the spans up to each one
        self.reach = list(itertools.accumulate(
            (e for _, _, e in self.spans), max))
        self.edges = sorted({t for _, s, e in self.spans for t in (s, e)})

    def at(self, t) -> str:
        """The innermost span open at ``t``: the latest-starting one that
        covers it."""
        j = bisect.bisect_right(self.starts, t) - 1
        while j >= 0 and self.reach[j] > t:
            name, s, e = self.spans[j]
            if s <= t < e:
                return name
            j -= 1
        return "none"

    def pieces(self, lo, hi):
        """``(lo, hi)`` cut at every span edge inside it."""
        i = bisect.bisect_right(self.edges, lo)
        k = bisect.bisect_left(self.edges, hi)
        cuts = [lo] + self.edges[i:k] + [hi]
        return zip(cuts[:-1], cuts[1:])


def reduce(trace: Trace) -> Summary:
    own = [sp for sp in trace.spans if sp[0] in SPANS]
    if not own:
        raise ValueError("the trace holds none of the harness's spans")
    if not trace.devices:
        raise ValueError("the trace holds no device plane")
    lo = min(s for _, s, _ in own)
    hi = max(e for _, _, e in own)
    spans = _Spans(trace.spans)
    n = len(trace.devices)
    busy = 0.0
    op_s: dict = {}
    op_calls: dict = {}
    idle: dict = {}
    for events in trace.devices.values():
        clipped = [(name, max(s, lo), min(e, hi)) for name, s, e in events]
        clipped = [c for c in clipped if c[2] > c[1]]
        merged = union((s, e) for _, s, e in clipped)
        busy += sum(e - s for s, e in merged)
        for name, s, e in clipped:
            op_s[name] = op_s.get(name, 0.0) + (e - s)
            op_calls[name] = op_calls.get(name, 0) + 1
        for gap in _gaps(merged, lo, hi):
            for s, e in spans.pieces(*gap):
                who = spans.at((s + e) / 2)
                idle[who] = idle.get(who, 0.0) + (e - s)
    return Summary(window_s=(hi - lo) * 1e-9, busy_s=busy / n * 1e-9,
                   n_devices=n,
                   op_s={k: v / n * 1e-9 for k, v in op_s.items()},
                   op_calls=op_calls,
                   idle_by_span={k: v / n * 1e-9 for k, v in idle.items()},
                   op_text=dict(trace.op_text))


def kernel_time(summary: Summary, match) -> tuple[float, int, list]:
    """Seconds (summed over devices) and events of the ops for which
    ``match(op name, its HLO text)`` holds, and those ops' names."""
    ops = [k for k in summary.op_s if match(k, summary.op_text.get(k, ""))]
    secs = sum(summary.op_s[k] for k in ops)
    calls = sum(summary.op_calls[k] for k in ops)
    return secs * summary.n_devices, calls, ops


def breakdown(summary: Summary, top: int = 10, name_chars: int = 160) -> dict:
    """The ``top`` device ops by time and idle gaps by host span.  On a
    TPU an op's name is its whole HLO instruction; the start of it, with
    the op's own name and its shapes, is kept."""
    ops = sorted(summary.op_s.items(), key=lambda kv: -kv[1])[:top]
    gaps = sorted(summary.idle_by_span.items(), key=lambda kv: -kv[1])[:top]
    return {"device_ops": [[k[:name_chars], v] for k, v in ops],
            "idle_gaps": [[k, v] for k, v in gaps]}


def _text(event) -> str:
    """The HLO text the profiler attaches to an op: the instruction (with
    its custom-call target, for a kernel) and the JAX op path."""
    return " ".join(str(v) for k, v in event.stats if k in LABEL_STATS)


def from_xplane(log_dir: str, devices, spans=SPANS) -> Trace:
    """Read the ``.xplane.pb`` that ``jax.profiler`` wrote under
    ``log_dir``: the op events of the accelerator planes of ``devices``
    (JAX device ids; the chips the cell uses, not every chip the host
    holds), and the host spans named in ``spans`` from the host planes."""
    from jax.profiler import ProfileData
    files = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(files) != 1:
        raise ValueError(f"expected one .xplane.pb under {log_dir}, "
                         f"found {len(files)}")
    pd = ProfileData.from_file(files[0])
    wanted_ids = set(devices)
    planes, host = {}, []
    wanted = set(spans)
    keys: dict = {}             # (event name, text) -> op name
    op_text: dict = {}          # op name -> text
    seen = set()
    for plane in pd.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m and m.group(1) != "CPU" and int(m.group(2)) in wanted_ids:
            seen.add(int(m.group(2)))
            events = []
            for line in plane.lines:
                if line.name != DEVICE_OPS_LINE:
                    continue
                for e in line.events:
                    text = _text(e)
                    op = keys.get((e.name, text))
                    if op is None:
                        # an op name that two programs share, with another
                        # instruction behind it, is kept apart
                        op = e.name if e.name not in op_text \
                            else f"{e.name}#{len(keys)}"
                        keys[(e.name, text)] = op
                        op_text[op] = text
                    events.append((op, e.start_ns, e.end_ns))
            planes[plane.name] = events
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend((e.name, e.start_ns, e.end_ns)
                            for e in line.events if e.name in wanted)
    for i in wanted_ids - seen:          # a chip that ran nothing is idle
        planes[f"/device:{i}"] = []
    return Trace(devices=planes, spans=host, op_text=op_text)
