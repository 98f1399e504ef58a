"""Distribution substrate tests.

The multi-device cases run in a SUBPROCESS with
--xla_force_host_platform_device_count=8 so the rest of the suite keeps
seeing the single real CPU device (per the assignment's dry-run rules)."""

import json
import os
import subprocess
import sys
import textwrap

import jax

from repro.configs import get_config
from repro.distributed.partition import _is_spec_leaf, param_specs
from repro.launch.specs import abstract_params

SRC = os.path.join(os.path.dirname(__file__), "..", "src")

# The mesh-based subprocess tests build Auto-axis meshes with
# jax.make_mesh and must run (not skip).


def run_sub(code: str) -> str:
    env = dict(os.environ,
               XLA_FLAGS="--xla_force_host_platform_device_count=8",
               PYTHONPATH=SRC)
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                         capture_output=True, text=True, env=env,
                         timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    return out.stdout


def test_param_specs_cover_all_leaves_with_correct_rank():
    """Every param leaf gets a spec tuple with one entry per dim."""
    for arch in ("qwen3-4b", "jamba-v0.1-52b", "whisper-small",
                 "arctic-480b", "mamba2-1.3b"):
        cfg = get_config(arch)
        params = abstract_params(cfg)
        specs = param_specs(cfg, params)
        flat_p = jax.tree_util.tree_leaves(params)
        flat_s = jax.tree_util.tree_leaves(specs, is_leaf=_is_spec_leaf)
        assert len(flat_p) == len(flat_s)
        for p, s in zip(flat_p, flat_s):
            assert len(s) == len(p.shape), (p.shape, s)


def test_full_config_tp_divisibility():
    """Every model-sharded dim of every full config divides the TP=16 axis."""
    for arch in ("qwen3-4b", "nemotron-4-340b", "gemma2-9b", "llama3-8b",
                 "mamba2-1.3b", "jamba-v0.1-52b", "whisper-small",
                 "dbrx-132b", "arctic-480b", "llava-next-34b"):
        cfg = get_config(arch)
        params = abstract_params(cfg)
        specs = param_specs(cfg, params)
        flat = jax.tree_util.tree_flatten_with_path(
            specs, is_leaf=_is_spec_leaf)[0]
        pflat = jax.tree_util.tree_leaves(params)
        for (path, spec), leaf in zip(flat, pflat):
            for dim, ax in zip(leaf.shape, spec):
                if ax in ("heads", "mlp", "vocab", "experts"):
                    assert dim % 16 == 0, (arch, path, leaf.shape, spec)


def test_sharded_train_step_matches_single_device():
    """8-device pjit train step == single-device train step (same math)."""
    out = run_sub("""
        import jax, jax.numpy as jnp, numpy as np, json
        from repro.configs import get_reduced
        from repro.distributed.partition import (batch_specs, to_shardings,
                                                 train_state_specs)
        from jax.sharding import AxisType
        from repro.distributed.sharding import make_rules, use_rules
        from repro.train import TrainSettings, init_state
        from repro.train.step import make_train_step

        cfg = get_reduced("qwen3-4b")
        s = TrainSettings(num_microbatches=2)
        key = jax.random.PRNGKey(0)
        toks = jax.random.randint(key, (8, 17), 0, cfg.vocab_size)
        batch = {"tokens": toks[:, :-1], "labels": toks[:, 1:]}

        state = init_state(key, cfg, s)
        ref, mref = jax.jit(make_train_step(cfg, s))(state, batch)

        mesh = jax.make_mesh((2, 4), ("data", "model"), (AxisType.Auto,) * 2)
        rules = make_rules(mesh, fsdp=True)
        with mesh, use_rules(rules):
            st_specs = train_state_specs(cfg, cfg.optimizer, state)
            st_sh = to_shardings(mesh, rules, st_specs, state)
            b_sh = to_shardings(mesh, rules, batch_specs(batch), batch)
            state2 = init_state(key, cfg, s)
            state2 = jax.device_put(state2, st_sh)
            batch2 = jax.device_put(batch, b_sh)
            step = jax.jit(make_train_step(cfg, s),
                           in_shardings=(st_sh, b_sh),
                           out_shardings=(st_sh, None))
            got, mgot = step(state2, batch2)
        err = max(float(jnp.max(jnp.abs(a - b)))
                  for a, b in zip(jax.tree.leaves(ref.params),
                                  jax.tree.leaves(got.params)))
        print(json.dumps({"err": err,
                          "loss_ref": float(mref["loss"]),
                          "loss_got": float(mgot["loss"])}))
    """)
    res = json.loads(out.strip().splitlines()[-1])
    assert abs(res["loss_ref"] - res["loss_got"]) < 1e-4
    assert res["err"] < 5e-3


def test_compressed_psum_int8_error_feedback():
    """int8 EF psum over a 'pod' axis: bounded per-step error, and the
    error-feedback residual keeps the *running average* unbiased."""
    out = run_sub("""
        import jax, jax.numpy as jnp, numpy as np, json
        from functools import partial
        from jax.sharding import PartitionSpec as P
        from jax.sharding import AxisType
        from repro.optim import compression

        mesh = jax.make_mesh((8,), ("pod",), (AxisType.Auto,))
        grads = {"w": jnp.asarray(
            np.random.default_rng(0).normal(0, 1, (8, 64, 32)).astype(np.float32))}

        @partial(jax.shard_map, mesh=mesh, check_vma=False,
                 in_specs=(P("pod"), P("pod")), out_specs=(P("pod"), P("pod")))
        def step(g, err):
            gl = {"w": g[0]}
            st = compression.CompressionState(error={"w": err[0]})
            out, new_st = compression.compressed_psum(gl, st, "pod")
            return out["w"][None], new_st.error["w"][None]

        err = jnp.zeros_like(grads["w"])
        exact = jnp.mean(grads["w"], axis=0)
        total_comp = 0.0
        for it in range(8):
            comp, err = step(grads["w"], err)
            total_comp = total_comp + comp[0]
        # per-step error bounded by quantization step
        amax = float(jnp.max(jnp.abs(grads["w"])))
        step_err = float(jnp.max(jnp.abs(comp[0] - exact)))
        # running average converges (error feedback keeps it unbiased)
        avg_err = float(jnp.max(jnp.abs(total_comp / 8 - exact)))
        print(json.dumps({"step_err": step_err, "avg_err": avg_err,
                          "scale": amax / 127.0}))
    """)
    res = json.loads(out.strip().splitlines()[-1])
    assert res["step_err"] <= 2.5 * res["scale"]
    assert res["avg_err"] <= res["step_err"] / 2 + res["scale"] * 0.2


def test_shard_helper_drops_nondivisible_axes():
    from repro.distributed.sharding import ShardingRules
    rules = ShardingRules({"batch": "data", "mlp": "model"},
                          {"data": 16, "model": 16})
    spec = rules.spec_for_shape((1, 7, 32), "batch", None, "mlp")
    assert spec == jax.sharding.PartitionSpec(None, None, "model")
