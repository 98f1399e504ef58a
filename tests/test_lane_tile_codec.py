"""The lane-tile codec: a ``LaneState`` as one ``(B, W)`` uint32 slab.

Contracts under test:
  * ``unpack(pack(st))`` gives back ``st`` leaf by leaf, dtype and shape
    included, for dense stacks whose widths are not multiples of 4 and for
    a conv-pool stack, on random and on extreme values;
  * the host reads the device's slab as views of the same bytes: every
    leaf of ``views(np.array(pack(st)))`` equals ``st``'s, and the rows
    those views look into go back up to the same ``st``;
  * ``rows`` refuses a tile whose leaves are not all views of one buffer.
"""

import jax
import numpy as np
import pytest

from repro.core.layers import ConvPool, Dense
from repro.core.lif import LIFConfig
from repro.core.snn import SNNConfig
from repro.serve.snn_engine import (_V_PEAK_INIT, LaneState, LaneTileCodec,
                                    _init_lanes)

CONV = SNNConfig(layers=(ConvPool(3, (1, 9, 9), 4, 2), Dense(5)),
                 lif=LIFConfig(decay_shift=4, v_threshold=128, v_rest=0),
                 qat=False).layer_sizes
SIZES = {"dense_24_12_10": (24, 12, 10), "dense_30_7_5": (30, 7, 5),
         "conv_81_27_5": CONV}
B = 6
I32 = np.iinfo(np.int32)


def _tile(sizes, fill, seed=0):
    """A host tile of ``sizes`` filled with ``fill`` values."""
    rng = np.random.default_rng(seed)
    shapes = jax.eval_shape(lambda: _init_lanes(B, sizes, 20, 0))

    def leaf(name, s):
        dt = np.dtype(s.dtype)
        if fill == "random":
            if dt == bool:
                return rng.integers(0, 2, s.shape).astype(bool)
            info = np.iinfo(dt)
            return rng.integers(info.min, info.max, s.shape,
                                dtype=dt, endpoint=True)
        if dt == bool:
            return np.full(s.shape, fill == "high")
        if name == "v_peak":
            return np.full(s.shape, _V_PEAK_INIT, dt)
        if fill == "high":
            return np.full(s.shape, np.iinfo(dt).max, dt)
        # "low": unsigned leaves 0, signed ones negative, down to INT_MIN
        if np.issubdtype(dt, np.unsignedinteger):
            return np.zeros(s.shape, dt)
        return np.resize(np.array([I32.min, -1, -129, -2 ** 20], dt),
                         s.shape)

    return LaneState(**{
        f: (tuple(leaf(f, s) for s in v) if isinstance(v, tuple)
            else leaf(f, v))
        for f, v in shapes._asdict().items()})


def _assert_tiles_equal(got, want):
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        g = np.asarray(g)
        assert g.dtype == w.dtype and g.shape == w.shape
        np.testing.assert_array_equal(g, w)


@pytest.mark.parametrize("fill", ["random", "high", "low"])
@pytest.mark.parametrize("sizes", list(SIZES), ids=list(SIZES))
def test_pack_unpack_and_views_are_exact(sizes, fill):
    codec = LaneTileCodec(SIZES[sizes])
    st = _tile(SIZES[sizes], fill)
    slab = codec.pack(jax.tree.map(jax.numpy.asarray, st))
    assert slab.dtype == np.uint32 and slab.shape == (B, codec.width)
    _assert_tiles_equal(codec.unpack(slab), st)
    rows = np.array(slab)
    host = codec.views(rows)
    _assert_tiles_equal(host, st)
    assert codec.rows(host) is rows
    _assert_tiles_equal(codec.unpack(jax.device_put(rows)), st)


def test_rows_refuses_a_tile_not_read_by_the_codec():
    codec = LaneTileCodec(SIZES["dense_30_7_5"])
    host = codec.views(np.zeros((B, codec.width), np.uint32))
    with pytest.raises(ValueError, match="one \\(B, W\\) uint32 buffer"):
        codec.rows(host._replace(active=host.active.copy()))
    with pytest.raises(ValueError, match="one \\(B, W\\) uint32 buffer"):
        codec.rows(_tile(SIZES["dense_30_7_5"], "random"))
