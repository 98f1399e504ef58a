"""Layer descriptors: what each LIF layer of a stack computes its currents
from.

A stack is its input (``SNNConfig.layer_sizes[0]`` pixels) followed by one
descriptor per LIF layer:

  ``Dense(n)``          I = x @ W over (n_in, n) weight codes; executed
                        adds: the input spikes times the enabled neurons.
  ``ConvPool(c_out, in_shape, kernel, pool)``
                        a valid, stride-1, bias-free convolution of the
                        (C_in, H, W) spike map by (c_out, C_in, k, k) codes,
                        then a pool x pool max-pool of the currents:

                          a[c,i,j] = sum_{c',dy,dx} W[c,c',dy,dx] s[c',i+dy,j+dx]
                          I[c,y,x] = max_{u,v < pool} a[c, pool*y+u, pool*x+v]

                        executed adds: over the enabled neurons (c, y, x),
                        over their pool x pool pre-pool positions, the
                        spikes in that position's k x k x C_in field.

Neuron rows stay flat everywhere outside the kernel: a conv layer's
neurons are its (C, H, W) map in PyTorch order, f = (c*H + y)*W + x, and a
dense layer after it reads that flatten.  Conv layers form a prefix of the
stack, and the last layer is dense (its neurons are the classes).
"""

from __future__ import annotations

from dataclasses import dataclass
from math import prod

import jax
import jax.numpy as jnp

__all__ = ["Dense", "ConvPool", "dense_layers", "has_conv", "stack_sizes",
           "validate_stack", "weight_shapes", "layers_to_wire",
           "layers_from_wire", "conv_pool_current", "conv_pool_adds"]


@dataclass(frozen=True)
class Dense:
    n: int

    @property
    def neurons(self) -> int:
        return int(self.n)


@dataclass(frozen=True)
class ConvPool:
    c_out: int
    in_shape: tuple          # (C_in, H, W) of the input spike map
    kernel: int = 5
    pool: int = 2

    def __post_init__(self):
        object.__setattr__(self, "in_shape",
                           tuple(int(d) for d in self.in_shape))
        c_in, h, w = self.in_shape
        k, p = int(self.kernel), int(self.pool)
        if min(c_in, h, w, k, p, int(self.c_out)) < 1 or h < k or w < k:
            raise ValueError(f"conv-pool layer {self} has an empty map")
        if (h - k + 1) % p or (w - k + 1) % p:
            raise ValueError(
                f"conv-pool layer {self}: the {h - k + 1}x{w - k + 1} conv "
                f"map does not tile into {p}x{p} pools")

    @property
    def conv_hw(self) -> tuple[int, int]:
        _, h, w = self.in_shape
        return h - self.kernel + 1, w - self.kernel + 1

    @property
    def out_shape(self) -> tuple[int, int, int]:
        ho, wo = self.conv_hw
        return (int(self.c_out), ho // self.pool, wo // self.pool)

    @property
    def neurons(self) -> int:
        return prod(self.out_shape)


def dense_layers(layer_sizes) -> tuple:
    """The descriptors of a fully connected stack ``layer_sizes``."""
    return tuple(Dense(int(n)) for n in layer_sizes[1:])


def has_conv(layers) -> bool:
    return layers is not None and any(isinstance(d, ConvPool)
                                      for d in layers)


def stack_sizes(n_in: int, layers) -> tuple[int, ...]:
    """Flat neuron counts, input first: ``SNNConfig.layer_sizes``."""
    return (int(n_in),) + tuple(d.neurons for d in layers)


def validate_stack(n_in: int, layers) -> None:
    """Raise unless ``layers`` chain from ``n_in`` inputs: conv-pool layers
    a prefix, each reading the previous map, the last layer dense."""
    if not layers:
        raise ValueError("the stack has no layers")
    for i, d in enumerate(layers):
        if isinstance(d, ConvPool):
            if i and not isinstance(layers[i - 1], ConvPool):
                raise ValueError(f"layer {i}: a conv-pool layer after a "
                                 f"dense one is not supported")
            fed = layers[i - 1].out_shape if i else n_in
            if (d.in_shape if i else prod(d.in_shape)) != fed:
                raise ValueError(f"layer {i}: in_shape {d.in_shape} does "
                                 f"not match its input {fed}")
        elif not isinstance(d, Dense):
            raise ValueError(f"layer {i}: unknown descriptor {d!r}")
    if not isinstance(layers[-1], Dense):
        raise ValueError("the last layer must be dense: its neurons are "
                         "the classes")


def weight_shapes(n_in: int, layers) -> tuple:
    """Each layer's weight-code shape: (K, N) dense, (C_out, C_in, k, k)
    conv-pool (PyTorch layouts)."""
    out, fan_in = [], int(n_in)
    for d in layers:
        if isinstance(d, ConvPool):
            out.append((d.c_out, d.in_shape[0], d.kernel, d.kernel))
        else:
            out.append((fan_in, d.n))
        fan_in = d.neurons
    return tuple(out)


def layers_to_wire(layers) -> list | None:
    if layers is None:
        return None
    return [{"kind": "dense", "n": d.n} if isinstance(d, Dense) else
            {"kind": "conv_pool", "c_out": d.c_out,
             "in_shape": list(d.in_shape), "kernel": d.kernel,
             "pool": d.pool} for d in layers]


def layers_from_wire(d) -> tuple | None:
    if d is None:
        return None
    kinds = {"dense": Dense, "conv_pool": ConvPool}
    out = []
    for e in d:
        e = dict(e)
        kind = e.pop("kind")
        if kind not in kinds:
            raise ValueError(f"unknown layer kind {kind!r}")
        out.append(kinds[kind](**e))
    return tuple(out)


def _patches(x: jax.Array, layer: ConvPool) -> jax.Array:
    """(B, C_in*H*W) spikes -> (B, C_in, k*k, Ho, Wo) int32 fields."""
    c_in, h, w = layer.in_shape
    k = layer.kernel
    ho, wo = layer.conv_hw
    xs = x.reshape(x.shape[0], c_in, h, w).astype(jnp.int32)
    return jnp.stack([xs[:, :, dy:dy + ho, dx:dx + wo]
                      for dy in range(k) for dx in range(k)], axis=2)


def conv_pool_current(x: jax.Array, w_q: jax.Array, layer: ConvPool,
                      dot_impl: str = "int32") -> jax.Array:
    """Max-pooled conv currents (B, C_out*Hp*Wp) int32 of 0/1 spikes ``x``
    (B, C_in*H*W) against codes ``w_q`` (C_out, C_in, k, k).  Exact:
    integer accumulation, or f32 for ``dot_impl="f32"``, exact too since
    every partial sum is an integer below 2**24."""
    p = _patches(x, layer)
    c_out, c_in, k, _ = w_q.shape
    wk = w_q.reshape(c_out, c_in, k * k)
    if dot_impl == "f32":
        a = jnp.einsum("bipyx,oip->boyx", p.astype(jnp.float32),
                       wk.astype(jnp.float32),
                       precision=jax.lax.Precision.HIGHEST,
                       preferred_element_type=jnp.float32).astype(jnp.int32)
    else:
        a = jnp.einsum("bipyx,oip->boyx", p, wk.astype(jnp.int32),
                       preferred_element_type=jnp.int32)
    _, hp, wp = layer.out_shape
    q = layer.pool
    a = a.reshape(a.shape[0], c_out, hp, q, wp, q)
    return jnp.max(a, axis=(3, 5)).reshape(a.shape[0], -1)


def conv_pool_adds(x: jax.Array, en: jax.Array,
                   layer: ConvPool) -> jax.Array:
    """Executed adds (B,) int32: over the enabled neurons, over their pool
    x pool pre-pool positions, the spikes in each position's field."""
    field = jnp.sum(_patches(x, layer), axis=(1, 2))        # (B, Ho, Wo)
    c_out, hp, wp = layer.out_shape
    q = layer.pool
    per_pool = jnp.sum(field.reshape(-1, hp, q, wp, q), axis=(2, 4))
    n_en = jnp.sum(en.reshape(-1, c_out, hp, wp).astype(jnp.int32), axis=1)
    return jnp.sum(per_pool * n_en, axis=(1, 2))
