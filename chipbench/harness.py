"""One run of one cell: set-up, a measured window, the check of what the
window served against the network's reference, and the cell's metrics.

Everything that belongs to one configuration, traffic mix or metric is a
file of its own, found by the name that ``BENCHMARK.json`` gives it:

    BENCHMARK.json                 cells, metrics and configurations
    chipbench/configs/<config>.json
    chipbench/traffic/<mix>.json   parameters for ``generator.py``
    chipbench/arrivals/<kind>.py   an arrival process a mix names
    chipbench/inputs/<kind>.py     an input kind a mix names
    chipbench/metrics/<metric>.py  ``read(run) -> float | None``
    chipbench/networks/<network>.py
                                   the network a configuration names
                                   (``"network"``): its reference,
                                   weights, control, program parameters
                                   and work counts (``networks/dense.py``
                                   lists the interface)

A cell is added by adding such files and entries; no file here changes.
"""

from __future__ import annotations

import gc
import json
import os
import shutil
import tempfile
import time
from dataclasses import dataclass, field

import numpy as np

import program_spans
import trace_reduce
import work
from generator import HERE, Traffic, Window, plugin

CHECKS_LIMIT = 0             # every compared number must read exactly 0
SAMPLE = 4096                # served requests compared per run, at most
TRACE_WINDOW_S = 5.0         # the traced window of a --trace 1 run


class Bench:
    """``BENCHMARK.json`` and the files it names, under ``root``."""

    def __init__(self, root: str):
        self.root = root
        self.dir = os.path.join(root, "chipbench")
        with open(os.path.join(root, "BENCHMARK.json")) as f:
            self.spec = json.load(f)

    @staticmethod
    def _named(entries, name, what):
        for e in entries:
            if e["name"] == name:
                return e
        raise KeyError(f"no {what} named {name!r} in BENCHMARK.json")

    def workload(self, name: str) -> dict:
        return self._named(self.spec["workloads"], name, "workload")

    def config(self, name: str) -> dict:
        entry = self._named(self.spec["configs"], name, "config")
        with open(os.path.join(self.root, entry["file"])) as f:
            return json.load(f)

    def traffic(self, name: str) -> dict:
        with open(os.path.join(self.dir, "traffic", name + ".json")) as f:
            return json.load(f)

    def end_to_end(self, cell: str) -> list:
        return [m for m in self.spec["end_to_end"]
                if cell in m.get("workloads", [cell])]

    def per_layer(self, cell: str) -> list:
        e2e = {m["name"] for m in self.end_to_end(cell)}
        return [m for m in self.spec["per_layer"]
                if (cell in m["workloads"] if "workloads" in m
                    else m["moves"] in e2e)]

    def reader(self, metric: str):
        return plugin(self.dir, "metrics", metric).read


@dataclass
class Run:
    """What a metric reader sees of one run."""

    cell: str
    config: dict
    traffic: dict
    chips: int
    lanes_total: int
    chunk_steps: int
    setup_s: float
    window: Window
    network: object                # the module networks/<network>.py
    retired_steps: dict            # rid -> window steps, retired in window
    spec_stats: dict | None        # sharded engine: used / wasted deltas
    launches: int | None           # stack-kernel launches in the window,
                                   # summed over chips; None if unknown
    trace: trace_reduce.Summary | None = None
    peaks: dict | None = None
    notes: list = field(default_factory=list)

    @property
    def latencies_s(self) -> np.ndarray:
        w = self.window
        return np.array([w.retired_at[r] - w.due[r] for r in w.due
                         if r in w.retired_at])


def network(cfg: dict, bench_dir: str = HERE):
    """The module ``<bench_dir>/networks/<network>.py`` of the network
    that the configuration names."""
    return plugin(bench_dir, "networks", cfg["network"])


def build_engine(cfg: dict, weights, seed: int, bench_dir: str = HERE):
    """The program's streaming engine for this deployment: one device, or
    the sharded engine over a (data x model) mesh of the first chips."""
    import jax
    from repro.serve import ShardedSNNStreamEngine, SNNStreamEngine
    params_q, snn = network(cfg, bench_dir).program(cfg, weights)
    data, model = cfg["mesh"]["data"], cfg["mesh"]["model"]
    if data * model == 1:
        return SNNStreamEngine(params_q, snn,
                               batch_size=cfg["lanes_per_device"],
                               patience=cfg["patience"], seed=seed,
                               dispatch_cache=False)
    from repro.distributed.sharding import make_2d_device_mesh
    mesh = make_2d_device_mesh(data, model,
                               devices=jax.devices()[:data * model])
    return ShardedSNNStreamEngine(
        params_q, snn, mesh=mesh,
        lanes_per_device=cfg["lanes_per_device"], patience=cfg["patience"],
        seed=seed, overlap=cfg["overlap"], dispatch_cache=False)


class CompileCounter:
    """Counts traces and compiles that start while ``armed``."""

    EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
              "/jax/core/compile/backend_compile_duration")

    def __init__(self):
        import jax
        self.armed = False
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **kw):
        if self.armed and event in self.EVENTS:
            self.count += 1


def memory_peak(devices) -> int:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in devices]
    return int(max(peaks))


def compare(cfg: dict, seed: int, traffic: Traffic, due: list,
            served: dict, bench_dir: str = HERE) -> tuple[dict, int, int]:
    """Served answers of the requests ``due`` against the reference of
    the configuration's network.

    ``served`` maps rid -> (pred, steps, adds, spike counts).  Every rid
    due must have an answer; a sample of them, drawn from the seed, is
    recomputed by the reference.  Returns the checks (name -> (value,
    limit)), the number compared and the number failed.
    """
    due = sorted(due)
    missing = [r for r in due if r not in served]
    have = np.array([r for r in due if r in served], np.int64)
    rng = np.random.default_rng(seed + 1)
    sample = (np.sort(rng.choice(have, SAMPLE, replace=False))
              if len(have) > SAMPLE else have)
    fields = ("pred", "steps", "adds", "counts")
    bad = {k: np.zeros(len(sample), bool) for k in fields}
    if len(sample):
        net = network(cfg, bench_dir)
        pixels = traffic.pixels[[traffic.rid_to_index[int(r)]
                                 for r in sample]]
        ref = net.serve(net.spec_of(cfg), net.make_weights(cfg), pixels,
                        seed + sample)
        for i, k in enumerate(fields):
            got = np.array([served[int(r)][i] for r in sample])
            bad[k] = (got != ref[k]).reshape(len(sample), -1).any(axis=1)
    any_bad = np.logical_or.reduce([bad[k] for k in fields])
    checks = {"missing": (len(missing), CHECKS_LIMIT)}
    for k in fields:
        checks[f"{k}_mismatch"] = (int(bad[k].sum()), CHECKS_LIMIT)
    return checks, len(sample), len(missing) + int(any_bad.sum())


def run_cell(bench: Bench, cell: str, seed: int, seconds: float,
             trace: bool, t_start: float, log=print,
             require_accelerator: bool = True) -> tuple[dict, list]:
    """Run ``cell`` once.  Returns the result line (a dict) and the
    checks' lines, which go last on standard error."""
    import jax
    from jax.profiler import ProfileOptions

    from repro.compile_cache import enable_compile_cache

    wl = bench.workload(cell)
    cfg = bench.config(wl["config"])
    mix = bench.traffic(wl["traffic"])
    chips = int(wl["chips"])
    devices = jax.devices()
    dev = devices[0]
    if require_accelerator:
        if dev.platform == "cpu":
            raise SystemExit("no accelerator: JAX's first device is the CPU")
        if len(devices) < chips:
            raise SystemExit(f"cell {cell} needs {chips} chips, JAX sees "
                             f"{len(devices)}")
    peaks = work.peaks_for(dev.device_kind) if dev.platform != "cpu" \
        else None
    used = devices[:chips]
    t_jax = time.perf_counter()
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    compiles = CompileCounter()

    lanes_total = cfg["lanes_per_device"] * cfg["mesh"]["data"]
    traffic = Traffic(mix, seed, lanes_total, bench.dir)
    t_pool = time.perf_counter()
    net = network(cfg, bench.dir)
    weights = jax.block_until_ready(net.make_weights(cfg))
    t_weights = time.perf_counter()
    eng = build_engine(cfg, weights, seed, bench.dir)
    chunk_steps = int(eng.chunk_steps)
    t_build = time.perf_counter()
    traffic.warm_up(eng)
    t_warm = time.perf_counter()
    log(f"setup parts: jax_init_s={t_jax - t_start:.3f} "
        f"pool_s={t_pool - t_jax:.3f} weights_s={t_weights - t_pool:.3f} "
        f"engine_s={t_build - t_weights:.3f} "
        f"warm_up_s={t_warm - t_build:.3f} backend={eng.backend} "
        f"chunk_steps={chunk_steps} lanes={lanes_total}")

    stats0 = dict(getattr(eng, "stats", {})) or None
    trace_dir = None
    if trace:
        trace_dir = tempfile.mkdtemp(prefix="chipbench-trace-")
        opts = ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
        seconds = min(seconds, TRACE_WINDOW_S)
    compiles.armed = True
    setup_s = time.perf_counter() - t_start
    window = traffic.run(eng, seconds)
    compiles.armed = False
    if trace:
        jax.profiler.stop_trace()
    spec_stats = None
    # one engine with no fault harness armed dispatches exactly one chunk,
    # one stack-kernel launch, per step(); the sharded engine's speculative
    # launches are not counted here
    launches = window.step_calls
    if stats0 is not None:
        spec_stats = {k: eng.stats[k] - stats0[k] for k in stats0}
        launches = None
    rounds = -(-cfg["num_steps"] // chunk_steps) + 1
    traffic.drain(eng, window, rounds)
    mem = memory_peak(used)
    results = eng.results
    # the warm-up's requests are held to the reference too: a lane that
    # never finishes them would otherwise go unseen
    due = list(traffic.warm_rids) + list(window.due)
    served = {r: (int(results[r].pred), int(results[r].steps),
                  int(results[r].adds), np.asarray(results[r].spike_counts))
              for r in due if r in results}
    retired_steps = {r: served[r][1] for r in window.retired_at
                     if r in served and window.retired_at[r] <= window.t1}
    del eng, results, weights
    gc.collect()

    checks, compared, failed = compare(cfg, seed, traffic, due, served,
                                       bench.dir)
    lat = window.lateness
    log(f"window: seconds={window.seconds:.3f} step_calls="
        f"{window.step_calls} due={len(window.due)} "
        f"retired_in_window={window.retired_in_window} compared={compared}"
        f" compiles_in_window={compiles.count}")
    if lat:
        log(f"generator lateness: p50_ms={np.median(lat) * 1e3:.4f} "
            f"p99_ms={np.percentile(lat, 99) * 1e3:.4f} "
            f"max_ms={max(lat) * 1e3:.4f}")
    if window.queue_samples:
        log("queue depth over the window: " + " ".join(
            f"{t:.1f}s:{q}" for t, q in window.queue_samples))
    if spec_stats is not None:
        log(f"sharded engine in the window: {spec_stats}")

    run = Run(cell=cell, config=cfg, traffic=mix, chips=chips,
              lanes_total=lanes_total, chunk_steps=chunk_steps,
              setup_s=setup_s, window=window, network=net,
              retired_steps=retired_steps,
              spec_stats=spec_stats, launches=launches, peaks=peaks)
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices), "memory_peak_bytes": mem}
    out = {}
    if trace:
        t = time.perf_counter()
        if dev.platform != "cpu":
            run.trace = trace_reduce.reduce(trace_reduce.from_xplane(
                trace_dir, devices=[d.id for d in used],
                spans=trace_reduce.SPANS + program_spans.names()))
            device["busy_s"] = run.trace.busy_s
            device["window_s"] = run.trace.window_s
            out["breakdown"] = trace_reduce.breakdown(run.trace)
        shutil.rmtree(trace_dir, ignore_errors=True)
        log(f"trace read in {time.perf_counter() - t:.3f} s")
    metrics = {}
    for m in (bench.per_layer(cell) if trace else bench.end_to_end(cell)):
        value = bench.reader(m["name"])(run)
        if value is None:
            log(f"metric {m['name']}: nothing to read in this run")
        else:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    for note in run.notes:
        log(note)
    correct = compared > 0 and all(v <= lim for v, lim in checks.values())
    line = {"correct": correct, "attempted": len(window.due),
            "failed": failed, "metrics": metrics, "device": device}
    line.update(out)
    line["checks"] = {k: {"value": v, "limit": lim}
                      for k, (v, lim) in checks.items()}
    check_lines = [f"check {k}: {v} (limit {lim})"
                   for k, (v, lim) in checks.items()]
    return line, check_lines

