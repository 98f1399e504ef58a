"""Run one cell of the chip benchmark once, as its own process.

    python chipbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

With ``--trace 0`` the result's metrics are the cell's end-to-end
metrics; with ``--trace 1`` the window runs under the profiler and the
metrics are the cell's per-layer ones.  The last line of standard output
is the result as one JSON object; the last lines of standard error are
the numbers that decided ``correct``, each beside its limit.  Without an
accelerator, or with fewer chips than the cell asks for, the run exits
nonzero and prints no result.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, os.path.join(ROOT, "src"))

# The program runs as shipped: no REPRO_* override (fault plans,
# tile-skip or dispatch settings) may steer it.
for _k in [k for k in os.environ if k.startswith("REPRO_")]:
    del os.environ[_k]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if args.seed < 0 or args.seconds <= 0:
        ap.error("--seed must be >= 0 and --seconds > 0")

    import harness

    def log(msg):
        print(msg, file=sys.stderr, flush=True)

    line, checks = harness.run_cell(
        harness.Bench(ROOT), args.workload, args.seed, args.seconds,
        bool(args.trace), T_START, log=log)
    for c in checks:
        log(c)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
