"""Train a ~100M-param LM for a few hundred steps (end-to-end driver for
the LM side of the framework): reduced llama3 config scaled up to ~100M,
synthetic structured token stream, full production train_step (grad accum,
clipping, checkpointing, straggler detection).

  PYTHONPATH=src python examples/lm_train_smoke.py --steps 300
"""

import argparse
import dataclasses
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

from repro.compile_cache import enable_compile_cache
from repro.configs import get_config
from repro.configs.base import reduced
from repro.launch.train import train


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=300)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--ckpt-dir", default="/tmp/repro_lm_smoke")
    args = ap.parse_args()

    # ~100M params: 8 layers, d=512, 8 heads, vocab 8192
    cfg = dataclasses.replace(
        reduced(get_config("llama3-8b"), layers=8, d_model=512, vocab=8192),
        num_heads=8, num_kv_heads=4, head_dim=64, d_ff=2048,
        name="llama3-100m",
    )
    n = cfg.param_count()
    print(f"model: {cfg.name}, {n/1e6:.1f}M params")

    def hook(rec):
        if rec["step"] % 25 == 0 or rec["step"] <= 3:
            print(f"step {rec['step']:4d}  loss {rec['loss']:.4f}  "
                  f"acc {rec['acc']:.3f}  {rec['wall_s']*1e3:.0f} ms")

    # train() resolves the arch by name; pass the custom cfg via registry
    from repro.configs.registry import register
    register(cfg)
    final, hist = train(cfg.name, steps=args.steps, batch=args.batch,
                        seq=args.seq, reduced=False,
                        ckpt_dir=args.ckpt_dir, ckpt_every=100,
                        lr=3e-4, microbatches=2, metrics_hook=hook)
    first, last = hist[0]["loss"], hist[-1]["loss"]
    print(f"\nloss {first:.3f} → {last:.3f} "
          f"({'improved' if last < first else 'NO IMPROVEMENT'})")
    assert last < first, "training must reduce loss"


if __name__ == "__main__":
    enable_compile_cache()
    main()
