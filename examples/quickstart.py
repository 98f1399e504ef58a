"""Quickstart: the paper's system in ~60 lines.

Trains the Poisson-encoded LIF classifier (784→10) with surrogate
gradients, quantizes to the 9-bit fixed-point codes the RTL uses, runs the
bit-exact integer engine, and prints the Fig-4-style membrane trace plus
accuracy-vs-timesteps (Fig 5).

  PYTHONPATH=src python examples/quickstart.py
"""

import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import jax.numpy as jnp
import numpy as np

from repro.compile_cache import enable_compile_cache
from repro.configs.snn_mnist import SNN_CONFIG
from repro.core import prng, snn
from repro.core.train_snn import int_accuracy, train_bptt
from repro.data import digits


def main():
    print("1) dataset (procedural MNIST stand-in)")
    ds = digits.make_dataset(n_train=3000, n_test=500, seed=0)

    print("2) surrogate-gradient BPTT training (QAT, ~1 min on CPU)")
    params = train_bptt(SNN_CONFIG, ds, steps=600, log_every=200)

    print("3) quantize to 9-bit fixed-point codes (the RTL's weight format)")
    params_q = snn.quantize_params(params, SNN_CONFIG)
    w = np.asarray(params_q["layers"][0]["w_q"])
    print(f"   codes in [{w.min()}, {w.max()}], "
          f"{w.size * 9 / 8 / 1024:.1f} KB at 9 bits")

    print("4) bit-exact integer inference (Poisson encoder + LIF core)")
    for T in (5, 10, 20):
        acc, aux = int_accuracy(params_q, SNN_CONFIG, ds.x_test, ds.y_test,
                                num_steps=T)
        print(f"   T={T:2d}: accuracy {acc:.3f}   "
              f"adds/image {aux['adds_per_img']:.0f} (zero multiplies)")

    print("5) single-neuron membrane trace (paper Fig. 4)")
    i = int(np.where(ds.y_test == 3)[0][0])
    px = jnp.asarray((ds.x_test[i:i + 1] * 255).astype(np.uint8))
    out = snn.snn_apply_int(params_q, px, prng.seed_state(1, px.shape),
                            SNN_CONFIG)
    vt = np.asarray(out["v_trace"])[:, 0, :]
    v = vt[:, vt.var(axis=0).argmax()]   # most dynamic neuron for display
    blocks = " ▁▂▃▄▅▆▇█"
    lo, hi = v.min(), max(v.max(), 1)
    print("   V(t):", "".join(
        blocks[int((x - lo) / (hi - lo + 1e-9) * 8)] for x in v),
        f" (threshold {SNN_CONFIG.lif.v_threshold}, hard reset on fire)")


if __name__ == "__main__":
    enable_compile_cache()
    main()
