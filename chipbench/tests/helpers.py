"""Shared pieces of the CPU checks: a run of a cell without a chip, and a
copy of the benchmark in a temporary directory that a test may extend."""

import json
import os
import shutil
import time

import harness

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def run(cell, seed=2_147_483_659, seconds=0.5, trace=False, root=ROOT,
        keep=None):
    """One run of ``cell`` on the CPU: the harness with its look for a
    chip skipped.  Returns (result line, check lines); with ``keep``, a
    dict, also puts the run's ``traffic`` and measured ``window`` there."""
    def go():
        return harness.run_cell(harness.Bench(root), cell, seed, seconds,
                                trace, time.perf_counter(),
                                log=lambda m: None,
                                require_accelerator=False)
    if keep is None:
        return go()

    class Kept(harness.Traffic):
        def run(self, eng, seconds, **kw):
            keep["traffic"] = self
            keep["window"] = super().run(eng, seconds, **kw)
            return keep["window"]

    real, harness.Traffic = harness.Traffic, Kept
    try:
        return go()
    finally:
        harness.Traffic = real


def copy_bench(dst) -> str:
    """BENCHMARK.json and chipbench/ copied under ``dst``; returns the root."""
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), dst)
    shutil.copytree(os.path.join(ROOT, "chipbench"),
                    os.path.join(dst, "chipbench"),
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    return str(dst)


def add_entries(root, **lists):
    """Append entries to BENCHMARK.json's lists under ``root``."""
    path = os.path.join(root, "BENCHMARK.json")
    with open(path) as f:
        spec = json.load(f)
    for key, entries in lists.items():
        spec[key].extend(entries)
    with open(path, "w") as f:
        json.dump(spec, f, indent=1)


def write_json(path, obj):
    with open(path, "w") as f:
        json.dump(obj, f, indent=1)
