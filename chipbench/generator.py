"""The one traffic generator: reads a traffic mix's parameters and drives
an engine through a measured window.

A mix is a data file ``traffic/<name>.json`` that names an arrival
process and an input kind, each with its parameters:

    {"arrivals": {"kind": "backlog", "queue_per_lane": 1},
     "inputs": {"kind": "digits", "pool": 256, "pool_seed": 0}}

Each kind is a file of its own, found by name: ``arrivals/<kind>.py``
defines ``Schedule(params, rng, seconds, lanes_total)`` with
``take(t, queue_depth)``, the due times (seconds into the window) of the
requests to submit at ``t``, and ``next_due()``, when the next one is
due; ``inputs/<kind>.py`` defines ``pool(params)``, a fixed (n, 784)
uint8 pool of images.  The run's seed orders the pool and seeds the
arrival process.  A new mix of existing kinds is a data file alone; a
new kind is a new file, and nothing here changes.
"""

from __future__ import annotations

import importlib.util
import os
import time

import numpy as np
from jax.profiler import TraceAnnotation

HERE = os.path.dirname(os.path.abspath(__file__))


def plugin(bench_dir: str, kind: str, name: str):
    """The module ``<bench_dir>/<kind>/<name>.py``."""
    path = os.path.join(bench_dir, kind, name + ".py")
    if not os.path.exists(path):
        raise KeyError(f"no {kind} named {name!r}: {path} does not exist")
    spec = importlib.util.spec_from_file_location(
        f"chipbench_{kind}_" + name.replace(".", "_").replace("-", "_"),
        path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class Window:
    """What one measured window saw, on the host clock."""

    def __init__(self):
        self.t0 = self.t1 = 0.0
        self.due: dict = {}             # rid -> due time, every rid due in it
        self.retired_at: dict = {}              # rid -> step() return time
        self.retired_in_window = 0
        self.step_calls = 0
        self.step_s = 0.0                       # host seconds inside step()
        self.busy_lanes = 0             # lanes in flight after each step()
        self.lateness: list = []                # submit - due, seconds
        self.queue_samples: list = []           # (t - t0, queue depth)

    @property
    def seconds(self) -> float:
        return self.t1 - self.t0


class Traffic:
    def __init__(self, mix: dict, seed: int, lanes_total: int,
                 bench_dir: str = HERE):
        self.mix = mix
        self.lanes_total = lanes_total
        self.arrivals = plugin(bench_dir, "arrivals",
                               mix["arrivals"]["kind"])
        self.pixels = np.asarray(plugin(bench_dir, "inputs",
                                        mix["inputs"]["kind"]).pool(
            mix["inputs"]), np.uint8)
        self.rng = np.random.default_rng(seed)
        self.order = self.rng.permutation(len(self.pixels))
        self.sent = 0                       # requests submitted so far
        self.rid_to_index: dict = {}        # rid -> pool index
        self.warm_rids: list = []           # requests served by warm_up

    def _submit(self, eng) -> int:
        idx = int(self.order[self.sent % len(self.order)])
        rid = eng.submit(self.pixels[idx])
        self.rid_to_index[rid] = idx
        self.sent += 1
        return rid

    def warm_up(self, eng) -> None:
        """One full tile of requests, served to the end: compiles the
        cell's one chunk shape and touches every host path of a round."""
        self.warm_rids = [self._submit(eng) for _ in range(self.lanes_total)]
        eng.run()

    @staticmethod
    def _record(done, w: Window, t: float, in_window: bool):
        for rid in done:
            w.retired_at[rid] = t
        if in_window:
            w.retired_in_window += len(done)

    def run(self, eng, seconds: float, clock=time.perf_counter) -> Window:
        w = Window()
        sched = self.arrivals.Schedule(self.mix["arrivals"], self.rng,
                                       seconds, self.lanes_total)
        w.t0 = w.t1 = clock()
        t_end = w.t0 + seconds
        next_sample = 0.0
        while True:
            with TraceAnnotation("gen.submit"):
                now = clock()
                if now >= t_end:
                    break
                depth = eng.load_summary().queue_depth
                for due in sched.take(now - w.t0, depth):
                    w.due[self._submit(eng)] = w.t0 + due
                    w.lateness.append(clock() - w.t0 - due)
                if now - w.t0 >= next_sample:
                    w.queue_samples.append((now - w.t0, depth))
                    next_sample += seconds / 10
            if not eng.pending:
                with TraceAnnotation("gen.wait"):
                    wake = min(w.t0 + sched.next_due(), t_end)
                    while clock() < wake:
                        time.sleep(min(max(wake - clock(), 0.0), 2e-4))
                continue
            with TraceAnnotation("engine.step"):
                t_step = clock()
                done = eng.step()
            t = clock()
            with TraceAnnotation("bench.record"):
                w.step_calls += 1
                w.step_s += t - t_step
                w.busy_lanes += eng.load_summary().lanes_busy
                self._record(done, w, t, True)
                w.t1 = t
        return w

    def drain(self, eng, w: Window, rounds_per_request: int,
              clock=time.perf_counter, limit_s: float = 60.0) -> None:
        """After the window: no new requests; serve what is queued or in
        flight, still recording when each retires, until nothing is
        pending, a minute has passed, or twice the rounds that a sound
        engine needs have run (``rounds_per_request`` rounds for each
        tile-full of pending requests, one more tile for those in lanes)."""
        tiles = -(-eng.pending // self.lanes_total) + 1
        rounds = 2 * tiles * rounds_per_request
        t_end = clock() + limit_s
        while eng.pending and rounds and clock() < t_end:
            done = eng.step()
            self._record(done, w, clock(), False)
            rounds -= 1
