"""The plain ``jax.numpy`` datapath that every network's reference shares.

A request is a 784-pixel uint8 image and its PRNG seed.  Its window is
the paper's integer datapath, written out step by step with no kernel,
no lanes and no chunking:

    Poisson encoder   xorshift32 lane per pixel; spike iff pixel > top byte
    LIF layer l       I = the network's currents from layer l-1's spikes
                      (int32), clip, V - (V >> shift), fire at
                      V >= threshold, hard reset, optional pruning
    readout           count | first_spike | membrane
    stability gate    retire once the prediction repeated ``patience``
                      times after the first output spike, or at T

What differs between networks is in ``networks/<network>.py``: the
weights, each layer's currents from the previous layer's spikes, the
neuron shapes and the adds.  A network's file calls :func:`serve` here
with those.

Neither this file nor a network's reference imports anything of the
program under test or reads anything it made: the weights are built
from the configuration's weight seed, and the PRNG lanes from the
request's seed.  Only a network's ``program()`` imports the program.
"""

from __future__ import annotations

from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

V_PEAK_INIT = np.iinfo(np.int32).min


class Datapath(NamedTuple):
    """The hashable settings that every network's window shares."""

    num_steps: int
    decay_shift: int
    v_threshold: int
    v_rest: int
    v_min: int
    v_max: int
    readout: str
    active_pruning: bool
    patience: int


def datapath_of(cfg: dict) -> Datapath:
    lif = cfg["lif"]
    return Datapath(num_steps=int(cfg["num_steps"]),
                    decay_shift=int(lif["decay_shift"]),
                    v_threshold=int(lif["v_threshold"]),
                    v_rest=int(lif["v_rest"]), v_min=int(lif["v_min"]),
                    v_max=int(lif["v_max"]), readout=cfg["readout"],
                    active_pruning=bool(cfg["active_pruning"]),
                    patience=int(cfg["patience"]))


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


def _readout(dp: Datapath, counts, first, v_last, v_peak_last):
    if dp.readout == "count":
        return jnp.argmax(counts, axis=-1)
    if dp.readout == "membrane":
        return jnp.argmax(v_peak_last, axis=-1)
    if dp.readout == "first_spike":
        large = jnp.int32(1 << 24)
        score = jnp.where(counts > 0, large + (dp.num_steps - first),
                          jnp.clip(v_last, -large + 1, large - 1))
        return jnp.argmax(score, axis=-1)
    raise ValueError(f"unknown readout {dp.readout!r}")


@partial(jax.jit, static_argnames=("dp", "shapes", "layer"))
def _window(px, rng, weights, *, dp: Datapath, shapes: tuple, layer):
    """The window of ``px.shape[0]`` requests.  ``shapes[l]`` is layer
    l's neuron shape (the last one a vector of classes); ``layer(l, x, w,
    en)`` gives layer l's int32 currents from the previous layer's bool
    spikes ``x`` (the encoder's, shape (rows, pixels), for l = 0) and its
    weights ``w`` widened to int32, and the int32 adds of each row, with
    ``en`` the layer's enables before the step."""
    rows = px.shape[0]
    T = dp.num_steps
    n_out = shapes[-1]
    px32 = px.astype(jnp.int32)
    ws = jax.tree.map(lambda w: w.astype(jnp.int32), weights)
    zeros = jnp.zeros((rows,), jnp.int32)
    carry = dict(
        rng=rng,
        v=tuple(jnp.full((rows, *s), dp.v_rest, jnp.int32) for s in shapes),
        en=tuple(jnp.ones((rows, *s), bool) for s in shapes),
        peak=tuple(jnp.full((rows, *s), V_PEAK_INIT, jnp.int32)
                   for s in shapes),
        counts=jnp.zeros((rows, *n_out), jnp.int32),
        first=jnp.full((rows, *n_out), T, jnp.int32),
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
        for l, (v, en, w) in enumerate(zip(c["v"], c["en"], ws)):
            cur, layer_adds = layer(l, x, w, en)
            adds = adds + layer_adds
            cur = jnp.where(en, cur, 0)
            vi = jnp.clip(v + cur, dp.v_min, dp.v_max)
            vl = vi - (vi >> dp.decay_shift)
            fired = vl >= dp.v_threshold
            v_new = jnp.where(en, jnp.where(fired, dp.v_rest, vl), v)
            fired = jnp.logical_and(fired, en)
            if dp.active_pruning:
                en = jnp.logical_and(en, jnp.logical_not(fired))
            vs.append(v_new)
            ens.append(en)
            x = fired
        counts = c["counts"] + x.astype(jnp.int32)
        first = jnp.where(jnp.logical_and(x, c["first"] == T),
                          c["steps"][:, None], c["first"])
        peak = tuple(jnp.maximum(p, v) for p, v in zip(c["peak"], vs))
        has_spike = jnp.max(counts, axis=-1) > 0
        pred = _readout(dp, counts, first, vs[-1], peak[-1]).astype(
            jnp.int32)
        streak = jnp.where(pred == c["prev"], c["streak"] + 1, 0)
        done = jnp.logical_and(streak >= dp.patience, has_spike)
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
    pred = _readout(dp, c["counts"], c["first"], c["v"][-1],
                    c["peak"][-1]).astype(jnp.int32)
    return pred, c["steps"], c["counts"], c["adds"]


def serve(dp: Datapath, shapes: tuple, layer, weights, pixels: np.ndarray,
          seeds: np.ndarray, block: int = 4096) -> dict:
    """Reference results of the requests (``pixels[i]``, ``seeds[i]``)
    through a network given by its neuron ``shapes`` and ``layer``
    function (see :func:`_window`), computed ``block`` rows at a time.
    Returns numpy arrays ``pred`` (R,), ``steps`` (R,), ``counts`` (R,
    n_out) and ``adds`` (R,)."""
    pixels = np.asarray(pixels, np.uint8)
    n_in = pixels.shape[1]
    parts = []
    for lo in range(0, len(pixels), block):
        px = pixels[lo:lo + block]
        rng = np.stack([seed_state(int(s), n_in)
                        for s in seeds[lo:lo + block]])
        pad = block - len(px) if len(pixels) > block else 0
        if pad:          # one compiled shape for every block
            px = np.concatenate([px, np.zeros((pad, n_in), np.uint8)])
            rng = np.concatenate([rng, np.ones((pad, n_in), np.uint32)])
        out = _window(jnp.asarray(px), jnp.asarray(rng), weights, dp=dp,
                      shapes=shapes, layer=layer)
        parts.append([np.asarray(a)[:block - pad] for a in out])
    keys = ("pred", "steps", "counts", "adds")
    return {k: np.concatenate([p[i] for p in parts])
            for i, k in enumerate(keys)}
