"""The benchmark's plain ``jax.numpy`` reference of one served request.

A request is a 784-pixel uint8 image and its PRNG seed.  Its window is
the paper's integer datapath, written out step by step with no kernel,
no lanes and no chunking:

    Poisson encoder   xorshift32 lane per pixel; spike iff pixel > top byte
    LIF layer l       I = sum_i W_i * S_i (int32), clip, V - (V >> shift),
                      fire at V >= threshold, hard reset, optional pruning
    readout           count | first_spike | membrane
    stability gate    retire once the prediction repeated ``patience``
                      times after the first output spike, or at T

This file imports nothing of the program under test and reads nothing it
made: the weights are built here from the configuration's weight seed,
and the PRNG lanes from the request's seed.  ``control_weights`` gives
the same weights at the next precision below the configuration's (int4
for its 8-bit weights), which is the control that the comparison must
reject.
"""

from __future__ import annotations

from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

V_PEAK_INIT = np.iinfo(np.int32).min


class Spec(NamedTuple):
    """The hashable part of a configuration that the window depends on."""

    layer_sizes: tuple
    num_steps: int
    decay_shift: int
    v_threshold: int
    v_rest: int
    v_min: int
    v_max: int
    readout: str
    active_pruning: bool
    patience: int


def spec_of(cfg: dict) -> Spec:
    lif = cfg["lif"]
    return Spec(layer_sizes=tuple(cfg["layer_sizes"]),
                num_steps=int(cfg["num_steps"]),
                decay_shift=int(lif["decay_shift"]),
                v_threshold=int(lif["v_threshold"]),
                v_rest=int(lif["v_rest"]), v_min=int(lif["v_min"]),
                v_max=int(lif["v_max"]), readout=cfg["readout"],
                active_pruning=bool(cfg["active_pruning"]),
                patience=int(cfg["patience"]))


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


def seed_state(seed: int, n: int) -> np.ndarray:
    """The xorshift32 lanes of one request: a SplitMix64-style hash of the
    seed and the lane index, truncated to 32 bits, zero remapped."""
    with np.errstate(over="ignore"):
        lane = np.arange(n, dtype=np.uint64)
        s = (np.uint64(seed) * np.uint64(0x9E3779B97F4A7C15)
             + lane * np.uint64(0xBF58476D1CE4E5B9))
        s ^= s >> np.uint64(30)
        s *= np.uint64(0xBF58476D1CE4E5B9)
        s ^= s >> np.uint64(27)
        s *= np.uint64(0x94D049BB133111EB)
        s ^= s >> np.uint64(31)
    state = (s & np.uint64(0xFFFFFFFF)).astype(np.uint32)
    return np.where(state == 0, np.uint32(0x9E3779B9), state)


def _readout(spec: Spec, counts, first, v_last, v_peak_last):
    if spec.readout == "count":
        return jnp.argmax(counts, axis=-1)
    if spec.readout == "membrane":
        return jnp.argmax(v_peak_last, axis=-1)
    if spec.readout == "first_spike":
        large = jnp.int32(1 << 24)
        score = jnp.where(counts > 0, large + (spec.num_steps - first),
                          jnp.clip(v_last, -large + 1, large - 1))
        return jnp.argmax(score, axis=-1)
    raise ValueError(f"unknown readout {spec.readout!r}")


@partial(jax.jit, static_argnames=("spec",))
def _window(px, rng, weights, *, spec: Spec):
    rows = px.shape[0]
    T = spec.num_steps
    n_out = spec.layer_sizes[-1]
    px32 = px.astype(jnp.int32)
    ws = tuple(w.astype(jnp.int32) for w in weights)
    zeros = jnp.zeros((rows,), jnp.int32)
    carry = dict(
        rng=rng,
        v=tuple(jnp.full((rows, n), spec.v_rest, jnp.int32)
                for n in spec.layer_sizes[1:]),
        en=tuple(jnp.ones((rows, n), bool) for n in spec.layer_sizes[1:]),
        peak=tuple(jnp.full((rows, n), V_PEAK_INIT, jnp.int32)
                   for n in spec.layer_sizes[1:]),
        counts=jnp.zeros((rows, n_out), jnp.int32),
        first=jnp.full((rows, n_out), T, jnp.int32),
        prev=jnp.full((rows,), -1, jnp.int32), streak=zeros, steps=zeros,
        adds=zeros, active=jnp.ones((rows,), bool))

    def step(_, c):
        act = c["active"]
        rng = c["rng"] ^ (c["rng"] << 13)
        rng = rng ^ (rng >> 17)
        rng = rng ^ (rng << 5)
        x = px32 > (rng >> 24).astype(jnp.int32)
        adds = zeros
        vs, ens = [], []
        for v, en, w in zip(c["v"], c["en"], ws):
            adds = adds + (jnp.sum(x, axis=-1, dtype=jnp.int32)
                           * jnp.sum(en, axis=-1, dtype=jnp.int32))
            cur = jnp.dot(x.astype(jnp.int32), w,
                          preferred_element_type=jnp.int32)
            cur = jnp.where(en, cur, 0)
            vi = jnp.clip(v + cur, spec.v_min, spec.v_max)
            vl = vi - (vi >> spec.decay_shift)
            fired = vl >= spec.v_threshold
            v_new = jnp.where(en, jnp.where(fired, spec.v_rest, vl), v)
            fired = jnp.logical_and(fired, en)
            if spec.active_pruning:
                en = jnp.logical_and(en, jnp.logical_not(fired))
            vs.append(v_new)
            ens.append(en)
            x = fired
        counts = c["counts"] + x.astype(jnp.int32)
        first = jnp.where(jnp.logical_and(x, c["first"] == T),
                          c["steps"][:, None], c["first"])
        peak = tuple(jnp.maximum(p, v) for p, v in zip(c["peak"], vs))
        has_spike = jnp.max(counts, axis=-1) > 0
        pred = _readout(spec, counts, first, vs[-1], peak[-1]).astype(
            jnp.int32)
        streak = jnp.where(pred == c["prev"], c["streak"] + 1, 0)
        done = jnp.logical_and(streak >= spec.patience, has_spike)
        prev = jnp.where(has_spike, pred, -1)
        streak = jnp.where(has_spike, streak, 0)
        steps = c["steps"] + act.astype(jnp.int32)
        still = jnp.logical_and(jnp.logical_and(act, jnp.logical_not(done)),
                                steps < T)

        def keep(new, old):
            return jnp.where(act.reshape((-1,) + (1,) * (new.ndim - 1)),
                             new, old)

        return dict(
            rng=keep(rng, c["rng"]),
            v=tuple(keep(n, o) for n, o in zip(vs, c["v"])),
            en=tuple(keep(n, o) for n, o in zip(ens, c["en"])),
            peak=tuple(keep(n, o) for n, o in zip(peak, c["peak"])),
            counts=keep(counts, c["counts"]), first=keep(first, c["first"]),
            prev=keep(prev, c["prev"]), streak=keep(streak, c["streak"]),
            steps=steps, adds=c["adds"] + jnp.where(act, adds, 0),
            active=jnp.where(act, still, c["active"]))

    c = jax.lax.fori_loop(0, T, step, carry)
    pred = _readout(spec, c["counts"], c["first"], c["v"][-1],
                    c["peak"][-1]).astype(jnp.int32)
    return pred, c["steps"], c["counts"], c["adds"]


def serve(spec: Spec, weights: tuple, pixels: np.ndarray,
          seeds: np.ndarray, block: int = 4096) -> dict:
    """Reference results of the requests (``pixels[i]``, ``seeds[i]``),
    computed ``block`` rows at a time.  Returns numpy arrays ``pred``
    (R,), ``steps`` (R,), ``counts`` (R, n_out) and ``adds`` (R,)."""
    n_in = spec.layer_sizes[0]
    parts = []
    for lo in range(0, len(pixels), block):
        px = np.asarray(pixels[lo:lo + block], np.uint8)
        rng = np.stack([seed_state(int(s), n_in)
                        for s in seeds[lo:lo + block]])
        pad = block - len(px) if len(pixels) > block else 0
        if pad:          # one compiled shape for every block
            px = np.concatenate([px, np.zeros((pad, n_in), np.uint8)])
            rng = np.concatenate([rng, np.ones((pad, n_in), np.uint32)])
        out = _window(jnp.asarray(px), jnp.asarray(rng), weights, spec=spec)
        parts.append([np.asarray(a)[:block - pad] for a in out])
    keys = ("pred", "steps", "counts", "adds")
    return {k: np.concatenate([p[i] for p in parts])
            for i, k in enumerate(keys)}
