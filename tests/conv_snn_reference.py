"""A plain numpy reference of a Poisson-encoded convolutional SNN, served
the way the streaming engine serves a request, written from the layer
equations alone.  It imports nothing of the program.

Layers are tuples: ``("conv", c_out, (c_in, h, w), k, pool)`` or
``("dense", n)``; weights use PyTorch layouts, (c_out, c_in, k, k) for a
conv and (n_in, n_out) over the (C, H, W) flatten for a dense layer.

    conv-pool   a[c,i,j] = sum_{c',dy,dx} W[c,c',dy,dx] s[c',i+dy,j+dx]
                I[c,y,x] = max_{u,v < pool} a[c, pool*y+u, pool*x+v]
                adds: over enabled (c,y,x), over its pool*pool pre-pool
                positions, the spikes in that position's field
    dense       I = s @ W; adds: input spikes x enabled neurons
    LIF         V = clip(V + where(en, I, 0)); V -= V >> shift;
                fire at V >= threshold (enabled only); hard reset
    readout     count: argmax of the output spike counts
    gate        retire once the prediction repeated ``patience`` times
                after the first output spike, or at T
"""

import numpy as np


def seed_state(seed: int, n: int) -> np.ndarray:
    """xorshift32 lanes of one request: a SplitMix64-style hash of the
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


def _conv_pool(s, w, layer):
    """Pooled currents (R, C_out, Hp, Wp) and adds (R,) before the enable
    mask: returns (currents, per-position field counts pooled)."""
    _, c_out, (c_in, h, wd), k, pool = layer
    ho, wo = h - k + 1, wd - k + 1
    a = np.zeros((s.shape[0], c_out, ho, wo), np.int64)
    field = np.zeros((s.shape[0], ho, wo), np.int64)
    for dy in range(k):
        for dx in range(k):
            win = s[:, :, dy:dy + ho, dx:dx + wo]
            a += np.einsum("rcij,oc->roij", win, w[:, :, dy, dx])
            field += win.sum(axis=1)
    hp, wp = ho // pool, wo // pool
    cur = a.reshape(-1, c_out, hp, pool, wp, pool).max(axis=(3, 5))
    per_pool = field.reshape(-1, hp, pool, wp, pool).sum(axis=(2, 4))
    return cur, per_pool


def serve(layers, weights, pixels, seeds, *, num_steps=20, decay_shift=4,
          v_threshold=128, v_rest=0, v_min=-(1 << 20), v_max=(1 << 20) - 1,
          patience=2):
    """Results of requests (``pixels[r]``, ``seeds[r]``): dict of pred
    (R,), steps (R,), counts (R, n_out) and adds (R,)."""
    pixels = np.asarray(pixels, np.int64)
    R, n_in = pixels.shape
    rng = np.stack([seed_state(int(s), n_in) for s in seeds])
    shapes = []
    for layer in layers:
        if layer[0] == "conv":
            _, c_out, (_, h, wd), k, pool = layer
            shapes.append((c_out, (h - k + 1) // pool, (wd - k + 1) // pool))
        else:
            shapes.append((layer[1],))
    v = [np.full((R,) + sh, v_rest, np.int64) for sh in shapes]
    en = [np.ones((R,) + sh, bool) for sh in shapes]
    n_out = shapes[-1][0]
    counts = np.zeros((R, n_out), np.int64)
    prev = np.full(R, -1)
    streak = np.zeros(R, np.int64)
    steps = np.zeros(R, np.int64)
    adds = np.zeros(R, np.int64)
    active = np.ones(R, bool)
    ws = [np.asarray(w, np.int64) for w in weights]
    for _ in range(num_steps):
        act = active.copy()
        new_rng = rng ^ ((rng << np.uint32(13)) & np.uint32(0xFFFFFFFF))
        new_rng = new_rng ^ (new_rng >> np.uint32(17))
        new_rng = new_rng ^ ((new_rng << np.uint32(5)) & np.uint32(0xFFFFFFFF))
        x = (pixels > (new_rng >> np.uint32(24)).astype(np.int64))
        step_adds = np.zeros(R, np.int64)
        new_v, new_en = [], []
        for layer, w, vl, el in zip(layers, ws, v, en):
            if layer[0] == "conv":
                c_in, h, wd = layer[2]
                cur, per_pool = _conv_pool(
                    x.reshape(R, c_in, h, wd).astype(np.int64), w, layer)
                step_adds += (per_pool * el.sum(axis=1)).sum(axis=(1, 2))
            else:
                xf = x.reshape(R, -1).astype(np.int64)
                cur = xf @ w
                step_adds += xf.sum(axis=1) * el.sum(axis=1)
            cur = np.where(el, cur, 0)
            vi = np.clip(vl + cur, v_min, v_max)
            vk = vi - (vi >> decay_shift)
            fired = (vk >= v_threshold) & el
            new_v.append(np.where(el, np.where(fired, v_rest, vk), vl))
            new_en.append(el)
            x = fired
        fired_out = x.astype(np.int64)
        new_counts = counts + fired_out
        has_spike = new_counts.max(axis=1) > 0
        pred = new_counts.argmax(axis=1)
        new_streak = np.where(pred == prev, streak + 1, 0)
        done = (new_streak >= patience) & has_spike
        new_prev = np.where(has_spike, pred, -1)
        new_streak = np.where(has_spike, new_streak, 0)
        steps = steps + act
        still = act & ~done & (steps < num_steps)
        rng = np.where(act[:, None], new_rng, rng)
        v = [np.where(act.reshape((-1,) + (1,) * (a.ndim - 1)), a, b)
             for a, b in zip(new_v, v)]
        en = [np.where(act.reshape((-1,) + (1,) * (a.ndim - 1)), a, b)
              for a, b in zip(new_en, en)]
        counts = np.where(act[:, None], new_counts, counts)
        prev = np.where(act, new_prev, prev)
        streak = np.where(act, new_streak, streak)
        adds = adds + np.where(act, step_adds, 0)
        active = np.where(act, still, active)
    return {"pred": counts.argmax(axis=1), "steps": steps, "counts": counts,
            "adds": adds}
