"""Network ``dense``: a fully connected LIF stack, ``"layer_sizes": [784,
..., n_out]`` in the configuration.

Every network file gives the harness, ``control.py`` and the metric
readers the same interface:

    spec_of(cfg)                 the hashable spec the reference compiles for
    make_weights(cfg)            weight codes, made on the device from
                                 ``weight_seed``
    control_weights(weights)     the same weights one precision below
    serve(spec, weights, pixels, seeds)
                                 the plain ``jax.numpy`` reference
                                 (``reference.serve``): pred, steps,
                                 counts, adds
    program(cfg, weights)        (params_q, SNNConfig) for the program's
                                 engine; the only function that imports
                                 the program
    macs_per_lane_step(cfg)      multiply-accumulates of one lane in one step
    weight_bytes(cfg)            bytes of the weight codes
    lane_state_bytes(cfg)        bytes of one lane's carried state

Here layer l's currents are ``x @ W_l`` over (K_l, N_l) int16 codes, and
its adds are the spikes in times the enabled neurons.
"""

from __future__ import annotations

from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

import reference

WEIGHT_CODE_BYTES = 2        # a signed 9-bit code held in 2 bytes


class Spec(NamedTuple):
    layer_sizes: tuple
    dp: reference.Datapath


def spec_of(cfg: dict) -> Spec:
    return Spec(layer_sizes=tuple(int(n) for n in cfg["layer_sizes"]),
                dp=reference.datapath_of(cfg))


@partial(jax.jit, static_argnames=("sizes", "weight_bits"))
def _weights(key, *, sizes: tuple, weight_bits: int):
    lo, hi = -(1 << weight_bits), (1 << weight_bits) - 1
    out = []
    for fan_in, fan_out in zip(sizes[:-1], sizes[1:]):
        key, sub = jax.random.split(key)
        w = jax.random.normal(sub, (fan_in, fan_out), jnp.float32)
        # codes of about N(0, 1) * 256 / sqrt(fan_in): the program's own
        # init scale (2 / sqrt(fan_in)) times its threshold gain (128)
        w = jnp.round(w * (256.0 / np.sqrt(fan_in)))
        out.append(jnp.clip(w, lo, hi).astype(jnp.int16))
    return tuple(out)


def make_weights(cfg: dict) -> tuple:
    """Signed ``weight_bits + 1``-bit weight codes (int16), per layer, made
    on the device in one call from the configuration's weight seed."""
    return _weights(jax.random.PRNGKey(int(cfg["weight_seed"])),
                    sizes=tuple(cfg["layer_sizes"]),
                    weight_bits=int(cfg["weight_bits"]))


@jax.jit
def control_weights(weights: tuple) -> tuple:
    """The weights on a 4-bit grid at the same scale (int4 codes times a
    per-layer step): the control's precision, one below the 8-bit codes
    the configuration states."""
    out = []
    for w in weights:
        w = w.astype(jnp.float32)
        step = jnp.maximum(jnp.max(jnp.abs(w)) / 7.0, 1.0)
        out.append((jnp.clip(jnp.round(w / step), -8, 7) * step)
                   .round().astype(jnp.int16))
    return tuple(out)


def _layer(l, x, w, en):
    adds = (jnp.sum(x, axis=-1, dtype=jnp.int32)
            * jnp.sum(en, axis=-1, dtype=jnp.int32))
    cur = jnp.dot(x.astype(jnp.int32), w, preferred_element_type=jnp.int32)
    return cur, adds


def serve(spec: Spec, weights: tuple, pixels: np.ndarray, seeds: np.ndarray,
          block: int = 4096) -> dict:
    shapes = tuple((n,) for n in spec.layer_sizes[1:])
    return reference.serve(spec.dp, shapes, _layer, weights, pixels, seeds,
                           block)


def program(cfg: dict, weights: tuple):
    """The program's parameters and configuration for this stack."""
    from repro.core.lif import LIFConfig
    from repro.core.snn import SNNConfig
    lif = cfg["lif"]
    snn = SNNConfig(
        layer_sizes=tuple(cfg["layer_sizes"]), num_steps=cfg["num_steps"],
        lif=LIFConfig(decay_shift=lif["decay_shift"],
                      v_threshold=lif["v_threshold"], v_rest=lif["v_rest"],
                      v_min=lif["v_min"], v_max=lif["v_max"]),
        weight_bits=cfg["weight_bits"], readout=cfg["readout"],
        active_pruning=cfg["active_pruning"], backend="auto")
    return {"layers": [{"w_q": w} for w in weights]}, snn


def macs_per_lane_step(cfg: dict) -> int:
    """sum_l K_l * N_l."""
    sizes = cfg["layer_sizes"]
    return sum(int(k) * int(n) for k, n in zip(sizes[:-1], sizes[1:]))


def weight_bytes(cfg: dict) -> int:
    """Every weight code at 2 B."""
    return WEIGHT_CODE_BYTES * macs_per_lane_step(cfg)


def lane_state_bytes(cfg: dict) -> int:
    """Bytes of one lane's carried window state at unpadded widths: uint8
    pixels and uint32 PRNG lanes per input; per neuron an int32 membrane,
    an int32 running peak and a bool enable; int32 spike counts and
    first-spike times per class; int32 gate memory, streak, steps, adds
    and weight version, and a bool active flag."""
    sizes = cfg["layer_sizes"]
    n_in, n_out = int(sizes[0]), int(sizes[-1])
    neurons = sum(int(n) for n in sizes[1:])
    return n_in * (1 + 4) + neurons * (4 + 4 + 1) + n_out * (4 + 4) \
        + 5 * 4 + 1
