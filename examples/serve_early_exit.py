"""Early-exit serving (the paper's active pruning at the request level).

Three demos:
  1. SNN classification with per-image early exit: an image whose running
     prediction has been stable for `patience` timesteps stops consuming
     timesteps — the latency/energy histogram is the paper's Fig 6/7 story.
  2. Batched STREAMING SNN serving (serve/snn_engine.py): requests queue
     into a fixed batch tile, retire via the same stability gate mid-window,
     and compaction admits waiting images into the freed lanes — the
     continuous-batching view of the same energy win.
  3. LM serving with the same gate: a reduced qwen3 decodes a batch and
     retires stable sequences (serve/early_exit.py).

  PYTHONPATH=src python examples/serve_early_exit.py
"""

import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import jax
import jax.numpy as jnp
import numpy as np

from repro.compile_cache import enable_compile_cache
from repro.configs.snn_mnist import SNN_CONFIG
from repro.core import encoding, lif as lif_mod, prng
from repro.core.pruning import stability_early_exit
from repro.core.train_snn import fit_or_load


def snn_demo(T: int = 20, patience: int = 3):
    print("== SNN early exit (paper Fig 6/7) ==")
    params, params_q, ds = fit_or_load()
    x, y = ds.x_test[:2000], ds.y_test[:2000]
    px = jnp.asarray((x * 255).astype(np.uint8))
    spikes, _ = encoding.poisson_encode_hw(px, prng.seed_state(11, px.shape), T)
    res = lif_mod.run_lif_int(spikes, params_q["layers"][0]["w_q"],
                              SNN_CONFIG.lif)
    cum = np.cumsum(np.asarray(res["spikes"]).astype(np.int32), 0)
    pred_t = jnp.asarray(cum.argmax(-1))
    t_exit = np.asarray(stability_early_exit(pred_t, patience=patience))
    acc = (np.asarray(pred_t[-1]) == y).mean()

    hist, _ = np.histogram(t_exit, bins=np.arange(1, T + 2))
    print(f"accuracy @T={T}: {acc:.3f}")
    print(f"exit timestep: mean {t_exit.mean():.1f}, "
          f"p50 {np.percentile(t_exit, 50):.0f}, "
          f"p90 {np.percentile(t_exit, 90):.0f} (of {T})")
    print(f"timesteps saved by early exit: "
          f"{100 * (1 - t_exit.mean() / T):.0f}%")
    print("exit histogram:", hist.tolist())


def stream_demo(n_requests: int = 64, batch: int = 8, patience: int = 3):
    print("\n== batched streaming SNN serving (continuous batching) ==")
    from repro.serve import SNNStreamEngine

    params, params_q, ds = fit_or_load()
    eng = SNNStreamEngine(params_q, SNN_CONFIG, batch_size=batch,
                          chunk_steps=4, patience=patience, seed=11)
    imgs = (ds.x_test[:n_requests] * 255).astype(np.uint8)
    ids = [eng.submit(im) for im in imgs]
    results = eng.run()
    preds = np.array([results[i].pred for i in ids])
    steps = np.array([results[i].steps for i in ids])
    adds = np.array([results[i].adds for i in ids])
    early = np.array([results[i].early_exit for i in ids])
    acc = (preds == ds.y_test[:n_requests]).mean()
    T = SNN_CONFIG.num_steps
    print(f"{n_requests} requests through {batch} lanes: acc {acc:.3f}")
    print(f"window steps: mean {steps.mean():.1f}/{T} "
          f"({100 * (1 - steps.mean() / T):.0f}% saved), "
          f"{early.mean() * 100:.0f}% early-exited")
    print(f"synaptic adds/request: mean {adds.mean():.0f} "
          f"(retired lanes stop accumulating)")


def lm_demo():
    print("\n== LM early-exit serving (reduced qwen3) ==")
    from repro.configs import get_reduced
    from repro.models import lm_init
    from repro.serve import generate, stability_gate

    cfg = get_reduced("qwen3-4b")
    key = jax.random.PRNGKey(0)
    params = lm_init(key, cfg)
    B = 8
    prompts = {"tokens": jax.random.randint(key, (B, 16), 0, cfg.vocab_size)}
    toks, active = generate(params, prompts, cfg, steps=16, max_len=48,
                            early_exit_fn=stability_gate(B, patience=2))
    active = np.asarray(active)
    print(f"active sequences per decode step: {active.tolist()}")
    print(f"sequence-steps used: {active.sum()}/{B * 16} "
          f"({100 * (1 - active.sum() / (B * 16)):.0f}% saved)")


if __name__ == "__main__":
    enable_compile_cache()
    snn_demo()
    stream_demo()
    lm_demo()
