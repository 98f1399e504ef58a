"""Benchmark harness: one module per paper table/figure + the roofline
report.  Prints ``name,us_per_call,derived`` CSV lines; artifacts land in
results/bench/ AND — so the perf trajectory survives the gitignored
results/ dir — every fresh ``BENCH_*.json`` is mirrored to the repo root,
where it is committed and diffed by CI (benchmarks/check_tracked.py).

  PYTHONPATH=src python -m benchmarks.run            # everything
  PYTHONPATH=src python -m benchmarks.run --only fig5,roofline
  PYTHONPATH=src python -m benchmarks.run --only fused --tiny   # CI smoke
"""

from __future__ import annotations

import argparse
import glob
import os
import shutil
import sys
import time
import traceback

SUITES = {
    "table1": ("bench_input_stats", "Table I — stochastic input current"),
    "table2": ("bench_ann_vs_snn", "Table II — ANN vs SNN"),
    "fig4": ("bench_membrane", "Fig 4 — membrane trace"),
    "fig5": ("bench_accuracy", "Fig 5/6 — accuracy vs timesteps"),
    "fig7": ("bench_efficiency", "Fig 7 — efficiency score"),
    "fig8": ("bench_robustness", "Fig 8 — robustness"),
    "engine": ("bench_engine", "SNN engine throughput (JAX/kernels)"),
    "engine_sharded": ("bench_engine_sharded",
                       "Sharded streaming engine (lane mesh + overlap)"),
    "router": ("bench_router",
               "Serving tier (routing, shedding, weight rollout)"),
    "faults": ("bench_faults",
               "Fault tolerance (failover latency, ladder, accounting)"),
    "model_sharded": ("bench_model_sharded",
                      "Model-axis sharding (2-D data×model mesh)"),
    "fused": ("bench_fused", "Fused vs staged encode→LIF (time + bytes)"),
    "autotune": ("bench_autotune",
                 "Wall-clock autotuner + persisted dispatch cache"),
    "roofline": ("roofline", "Roofline terms from the dry-run"),
}

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH_DIR = os.path.join(REPO_ROOT, "results", "bench")


def _mirror_fresh_artifacts(since: float) -> list[str]:
    """Copy BENCH_*.json files (re)written after ``since`` to the repo
    root, where they are git-tracked — results/ itself is gitignored."""
    copied = []
    for p in sorted(glob.glob(os.path.join(BENCH_DIR, "BENCH_*.json"))):
        if os.path.getmtime(p) >= since:
            shutil.copy(p, os.path.join(REPO_ROOT, os.path.basename(p)))
            copied.append(os.path.basename(p))
    return copied


def main(argv=None) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help="comma-separated suite names")
    ap.add_argument("--tiny", action="store_true",
                    help="shrink problem sizes (CI kernel-regression smoke)")
    args = ap.parse_args(argv)
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    if args.tiny:
        os.environ["REPRO_BENCH_TINY"] = "1"
    want = args.only.split(",") if args.only else list(SUITES)

    failures = []
    for name in want:
        mod_name, desc = SUITES[name]
        print(f"# === {name}: {desc} ===", flush=True)
        t0 = time.time()
        try:
            mod = __import__(f"benchmarks.{mod_name}", fromlist=["run"])
            mod.run()
            copied = _mirror_fresh_artifacts(t0)
            if copied:
                print(f"# tracked artifact copies at repo root: "
                      f"{', '.join(copied)}", flush=True)
            print(f"# {name} done in {time.time()-t0:.1f}s", flush=True)
        except Exception as e:  # noqa: BLE001
            failures.append((name, e))
            traceback.print_exc()
    if failures:
        print(f"# {len(failures)} suite(s) failed: "
              f"{[n for n, _ in failures]}")
        sys.exit(1)
    print("# all benchmark suites passed")


if __name__ == "__main__":
    main()
