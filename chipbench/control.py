"""Readings that the correctness limits are set from, on the chip.

    python chipbench/control.py --workload <cell> --seeds 1,2,3 --seconds 2

In one process, for each seed: the program serves a short window of the
cell's own traffic at the cell's own size, and the comparison reads its
numbers (the lower readings).  Then the control, the reference at the
next precision below the configuration's (int4 weight codes), is put in
the program's place for the same requests and read the same way (the
upper readings).  One JSON line per seed.
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
for _k in [k for k in os.environ if k.startswith("REPRO_")]:
    del os.environ[_k]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=2.0)
    args = ap.parse_args(argv)

    import jax
    import numpy as np

    import harness
    import reference
    from generator import Traffic
    from repro.compile_cache import enable_compile_cache

    if jax.devices()[0].platform == "cpu":
        raise SystemExit("no accelerator: JAX's first device is the CPU")
    enable_compile_cache()
    bench = harness.Bench(ROOT)
    wl = bench.workload(args.workload)
    cfg = bench.config(wl["config"])
    mix = bench.traffic(wl["traffic"])
    lanes = cfg["lanes_per_device"] * cfg["mesh"]["data"]
    spec = reference.spec_of(cfg)
    weights = reference.make_weights(cfg)
    ctl_weights = reference.control_weights(weights)
    for seed in (int(s) for s in args.seeds.split(",")):
        traffic = Traffic(mix, seed, lanes, bench.dir)
        eng = harness.build_engine(cfg, weights, seed)
        traffic.warm_up(eng)
        window = traffic.run(eng, args.seconds)
        steps = -(-cfg["num_steps"] // int(eng.chunk_steps)) + 1
        traffic.drain(eng, window, steps)
        due = list(traffic.warm_rids) + list(window.due)
        res = eng.results
        served = {r: (int(res[r].pred), int(res[r].steps), int(res[r].adds),
                      np.asarray(res[r].spike_counts))
                  for r in due if r in res}
        del eng, res
        prog, n_prog, _ = harness.compare(cfg, seed, traffic, due, served)
        rids = np.array(sorted(due))
        px = traffic.pixels[[traffic.rid_to_index[int(r)] for r in rids]]
        out = reference.serve(spec, ctl_weights, px, seed + rids)
        ctl_served = {int(r): (out["pred"][i], out["steps"][i],
                               out["adds"][i], out["counts"][i])
                      for i, r in enumerate(rids)}
        ctl, n_ctl, _ = harness.compare(cfg, seed, traffic, due, ctl_served)
        print(json.dumps({
            "workload": args.workload, "seed": seed, "due": len(due),
            "program": {k: v for k, (v, _) in prog.items()},
            "program_compared": n_prog,
            "control": {k: v for k, (v, _) in ctl.items()},
            "control_compared": n_ctl}), flush=True)
    print(f"total_s={time.perf_counter() - T_START:.1f}", file=sys.stderr)
    return 0


if __name__ == "__main__":
    sys.exit(main())
