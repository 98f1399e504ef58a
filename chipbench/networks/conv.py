"""Network ``conv``: conv-pool LIF layers and then dense ones, as
``"layers"`` in the configuration (``networks/dense.py`` lists the
interface every network file gives):

    {"kind": "conv_pool", "c_out": C, "in_shape": [C_in, H, W],
     "kernel": k, "pool": p}     a valid, stride-1, bias-free k x k conv
                                 of the (C_in, H, W) spike map by (C,
                                 C_in, k, k) codes, then a p x p max-pool
                                 of the currents: (C, Hp, Wp) neurons
    {"kind": "dense", "n": N}    x @ W over (K, N) codes, K the (C, H, W)
                                 flatten of the map before it

    a[c,i,j] = sum_{c',dy,dx} W[c,c',dy,dx] s[c',i+dy,j+dx]
    I[c,y,x] = max_{u,v < p} a[c, p*y+u, p*x+v]

A conv-pool layer's adds: over its enabled neurons, over their p x p
pre-pool positions, the spikes in that position's k x k x C_in field; a
dense layer's, the spikes in times the enabled neurons.  The conv runs in
float32 at the highest precision, which is exact here: every operand is
an integer code or a 0/1 spike, and every partial sum an integer below
2**18 in magnitude.
"""

from __future__ import annotations

from functools import cache, partial
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

import reference

WEIGHT_CODE_BYTES = 2        # a signed 9-bit code held in 2 bytes
HIGHEST = jax.lax.Precision.HIGHEST


class Conv(NamedTuple):
    c_out: int
    in_shape: tuple
    kernel: int
    pool: int

    @property
    def conv_hw(self):
        _, h, w = self.in_shape
        return h - self.kernel + 1, w - self.kernel + 1

    @property
    def out_shape(self):
        ho, wo = self.conv_hw
        return (self.c_out, ho // self.pool, wo // self.pool)


class Spec(NamedTuple):
    n_in: int
    layers: tuple            # Conv, or the int width of a dense layer
    dp: reference.Datapath


def _layers(cfg: dict) -> tuple:
    out = []
    for e in cfg["layers"]:
        if e["kind"] == "conv_pool":
            out.append(Conv(int(e["c_out"]), tuple(int(d) for d in
                                                   e["in_shape"]),
                            int(e["kernel"]), int(e["pool"])))
        else:
            out.append(int(e["n"]))
    return tuple(out)


def spec_of(cfg: dict) -> Spec:
    layers = _layers(cfg)
    return Spec(n_in=int(np.prod(layers[0].in_shape)), layers=layers,
                dp=reference.datapath_of(cfg))


def _shapes(spec: Spec) -> tuple:
    """Each layer's weight shape and neuron shape."""
    out, fan_in = [], spec.n_in
    for d in spec.layers:
        if isinstance(d, Conv):
            out.append(((d.c_out, d.in_shape[0], d.kernel, d.kernel),
                        d.out_shape))
            fan_in = int(np.prod(d.out_shape))
        else:
            out.append(((fan_in, d), (d,)))
            fan_in = d
    return tuple(out)


@partial(jax.jit, static_argnames=("shapes", "weight_bits"))
def _weights(key, *, shapes: tuple, weight_bits: int):
    lo, hi = -(1 << weight_bits), (1 << weight_bits) - 1
    out = []
    for shape in shapes:
        key, sub = jax.random.split(key)
        fan_in = int(np.prod(shape[1:])) if len(shape) == 4 else shape[0]
        w = jax.random.normal(sub, shape, jnp.float32)
        w = jnp.round(w * (256.0 / np.sqrt(fan_in)))
        out.append(jnp.clip(w, lo, hi).astype(jnp.int16))
    return tuple(out)


def make_weights(cfg: dict) -> tuple:
    """Signed ``weight_bits + 1``-bit codes (int16) per layer, PyTorch
    layouts, of N(0, 1) * 256 / sqrt(fan_in) (fan_in C_in * k * k for a
    conv), made on the device in one call from the weight seed."""
    return _weights(jax.random.PRNGKey(int(cfg["weight_seed"])),
                    shapes=tuple(w for w, _ in _shapes(spec_of(cfg))),
                    weight_bits=int(cfg["weight_bits"]))


@jax.jit
def control_weights(weights: tuple) -> tuple:
    """The weights on a 4-bit grid at the same scale (int4 codes times a
    per-layer step), one precision below the configuration's 8-bit codes."""
    out = []
    for w in weights:
        w = w.astype(jnp.float32)
        step = jnp.maximum(jnp.max(jnp.abs(w)) / 7.0, 1.0)
        out.append((jnp.clip(jnp.round(w / step), -8, 7) * step)
                   .round().astype(jnp.int16))
    return tuple(out)


def _conv(x, w):
    return jax.lax.conv_general_dilated(
        x, w, window_strides=(1, 1), padding="VALID", precision=HIGHEST,
        preferred_element_type=jnp.float32)


def _pool(a, d: Conv, op):
    rows, c = a.shape[:2]
    _, hp, wp = d.out_shape
    return op(a.reshape(rows, c, hp, d.pool, wp, d.pool), axis=(3, 5))


@cache
def _layer_fn(spec: Spec):
    """The ``layer(l, x, w, en)`` of ``reference.serve`` for this stack,
    one function a spec so the reference compiles once."""
    def layer(l, x, w, en):
        d = spec.layers[l]
        rows = x.shape[0]
        if not isinstance(d, Conv):
            x = x.reshape(rows, -1)
            adds = (jnp.sum(x, axis=-1, dtype=jnp.int32)
                    * jnp.sum(en, axis=-1, dtype=jnp.int32))
            cur = jnp.dot(x.astype(jnp.int32), w,
                          preferred_element_type=jnp.int32)
            return cur, adds
        xs = x.reshape((rows,) + d.in_shape).astype(jnp.float32)
        cur = _pool(_conv(xs, w.astype(jnp.float32)), d, jnp.max)
        ones = jnp.ones((1, d.in_shape[0], d.kernel, d.kernel), jnp.float32)
        field = _pool(_conv(xs, ones), d, jnp.sum)       # (rows, 1, Hp, Wp)
        n_en = jnp.sum(en, axis=1, dtype=jnp.int32)      # (rows, Hp, Wp)
        adds = jnp.sum(field[:, 0].astype(jnp.int32) * n_en, axis=(1, 2))
        return cur.astype(jnp.int32), adds

    return layer


def serve(spec: Spec, weights: tuple, pixels: np.ndarray, seeds: np.ndarray,
          block: int = 1024) -> dict:
    shapes = tuple(s for _, s in _shapes(spec))
    return reference.serve(spec.dp, shapes, _layer_fn(spec), weights,
                           pixels, seeds, block)


def program(cfg: dict, weights: tuple):
    """The program's parameters and configuration for this stack."""
    from repro.core.layers import layers_from_wire
    from repro.core.lif import LIFConfig
    from repro.core.snn import SNNConfig
    lif = cfg["lif"]
    snn = SNNConfig(
        layers=layers_from_wire(cfg["layers"]), num_steps=cfg["num_steps"],
        lif=LIFConfig(decay_shift=lif["decay_shift"],
                      v_threshold=lif["v_threshold"], v_rest=lif["v_rest"],
                      v_min=lif["v_min"], v_max=lif["v_max"]),
        weight_bits=cfg["weight_bits"], readout=cfg["readout"],
        active_pruning=cfg["active_pruning"], backend="auto")
    return {"layers": [{"w_q": w} for w in weights]}, snn


def macs_per_lane_step(cfg: dict) -> int:
    """A conv-pool layer: every pre-pool output (C x Ho x Wo) times its
    C_in x k x k taps; a dense layer: K x N."""
    total = 0
    for (w, _), d in zip(_shapes(spec_of(cfg)), spec_of(cfg).layers):
        if isinstance(d, Conv):
            ho, wo = d.conv_hw
            total += int(np.prod(w)) * ho * wo
        else:
            total += int(np.prod(w))
    return total


def weight_bytes(cfg: dict) -> int:
    """Every weight code at 2 B."""
    return WEIGHT_CODE_BYTES * sum(int(np.prod(w))
                                   for w, _ in _shapes(spec_of(cfg)))


def lane_state_bytes(cfg: dict) -> int:
    """The dense stack's formula (``networks/dense.py``) over every
    layer's neurons."""
    spec = spec_of(cfg)
    neurons = [int(np.prod(s)) for _, s in _shapes(spec)]
    return spec.n_in * (1 + 4) + sum(neurons) * (4 + 4 + 1) \
        + neurons[-1] * (4 + 4) + 5 * 4 + 1
