"""Compile the main path's Pallas kernels for a TPU v5e without a chip.

Interpret mode (every other kernel test) cannot see what Mosaic refuses:
8-bit compares, int32×int32 matmuls, i1 relayouts, scalar stores to
VMEM, blocks that break the (8, 128) tiling.  Each test here lowers a
kernel with ``interpret=False`` against a described ``v5e:2x2`` topology
at the widths the chip smoke test serves and lets the TPU compiler
accept or refuse it.  Nothing runs; a pass is not a chip run.

The topology is described inside a module-scoped fixture (never at
import): only one process may load the TPU library, and under several
test workers the others must still collect the same tests.
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs.snn_mnist import (SNN_CONFIG_CONV, SNN_CONFIG_DEEP,
                                     SNN_CONFIG_WIDE)
from repro.core.layers import weight_shapes
from repro.kernels import fused_snn, ops
from repro.serve.snn_engine import LaneTileCodec, _init_lanes

LANES, CHUNK, T = 64, 4, 20


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def spec(topo):
    one_chip = SingleDeviceSharding(topo.devices[0])

    def make(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    return make


def _stack(spec, sizes, *, streamed=False):
    """The engine's gated, resumable chunk launch at LANES × CHUNK."""
    B, L = LANES, len(sizes) - 1
    ws = tuple(spec((a, b), jnp.int16) for a, b in zip(sizes[:-1], sizes[1:]))
    init = {"v": tuple(spec((B, n), jnp.int32) for n in sizes[1:]),
            "en": tuple(spec((B, n), jnp.bool_) for n in sizes[1:]),
            "v_peak": tuple(spec((B, n), jnp.int32) for n in sizes[1:]),
            "counts": spec((B, sizes[-1]), jnp.int32),
            "first": spec((B, sizes[-1]), jnp.int32),
            "steps": spec((B,), jnp.int32)}
    gate = {"active": spec((B,), jnp.bool_), "prev": spec((B,), jnp.int32),
            "streak": spec((B,), jnp.int32)}
    assert L == len(ws)
    return ops.fused_snn_stack_op.lower(
        spec((B, sizes[0]), jnp.uint8), spec((B, sizes[0]), jnp.uint32), ws,
        num_steps=T, chunk_steps=CHUNK, decay_shift=4, v_threshold=128,
        init=init, gate=gate, patience=2, readout="first_spike",
        active_pruning=True, sparse_skip=True, streamed=streamed,
        interpret=False).compile()


@pytest.mark.parametrize("sizes", [(784, 10), SNN_CONFIG_DEEP.layer_sizes],
                         ids=["paper", "deep"])
def test_fused_stack_compiles(spec, sizes):
    assert _stack(spec, sizes).as_text().count("tpu_custom_call") == 1


def test_conv_fused_stack_compiles(spec):
    """12C5-MP2-64C5-MP2-1024FC10 at its published widths: the conv-pool
    stages inside the one resumable launch, planes packed beforehand."""
    cfg, B = SNN_CONFIG_CONV, LANES
    sizes = cfg.layer_sizes
    ws = tuple(spec(s, jnp.int16) for s in weight_shapes(sizes[0],
                                                          cfg.layers))
    packed = jax.tree.map(lambda a: spec(a.shape, a.dtype), jax.eval_shape(
        lambda w: fused_snn.pack_stack(w, cfg.layers), ws))
    init = {"v": tuple(spec((B, n), jnp.int32) for n in sizes[1:]),
            "en": tuple(spec((B, n), jnp.bool_) for n in sizes[1:]),
            "v_peak": tuple(spec((B, n), jnp.int32) for n in sizes[1:]),
            "counts": spec((B, sizes[-1]), jnp.int32),
            "first": spec((B, sizes[-1]), jnp.int32),
            "steps": spec((B,), jnp.int32)}
    gate = {"active": spec((B,), jnp.bool_), "prev": spec((B,), jnp.int32),
            "streak": spec((B,), jnp.int32)}
    compiled = ops.fused_snn_stack_op.lower(
        spec((B, sizes[0]), jnp.uint8), spec((B, sizes[0]), jnp.uint32), ws,
        num_steps=T, chunk_steps=CHUNK, decay_shift=4, v_threshold=128,
        init=init, gate=gate, patience=2, readout="count", sparse_skip=True,
        interpret=False, layers=cfg.layers, packed=packed).compile()
    assert compiled.as_text().count("tpu_custom_call") == 1


def test_wide_fused_streamed_compiles(spec):
    assert _stack(spec, SNN_CONFIG_WIDE.layer_sizes, streamed=True)


def test_partial_contraction_compiles_at_wide_shard(spec):
    """One device's contraction on a 4-way model axis: 784 → 2048/4."""
    n_out = SNN_CONFIG_WIDE.layer_sizes[1] // 4
    f = jax.jit(lambda x, e, w: ops.partial_contraction_op(
        x, e, w, interpret=False))
    assert f.lower(spec((LANES, 784), jnp.bool_),
                   spec((LANES, n_out), jnp.bool_),
                   spec((784, n_out), jnp.int16)).compile()


def test_staged_encoder_compiles(spec):
    assert ops.poisson_encode_op.lower(
        spec((LANES, 784), jnp.uint8), spec((LANES, 784), jnp.uint32), T,
        interpret=False).compile()


@pytest.mark.parametrize("n_in,n_out", [(784, 10), (2048, 2048)],
                         ids=["paper", "wide"])
def test_staged_lif_compiles(spec, n_in, n_out):
    assert ops.lif_forward_op.lower(
        spec((T, LANES, n_in), jnp.uint8), spec((n_in, n_out), jnp.int16),
        decay_shift=4, v_threshold=128, active_pruning=True,
        interpret=False).compile()


@pytest.mark.parametrize("sizes", [
    (784, 10), SNN_CONFIG_WIDE.layer_sizes, SNN_CONFIG_CONV.layer_sizes,
], ids=["paper", "wide", "conv"])
def test_lane_tile_codec_compiles(spec, sizes):
    """The engine's one-slab tile transfer: pack on the way down, unpack
    on the way up, at the benchmark cells' networks and 64 lanes."""
    codec = LaneTileCodec(tuple(sizes))
    lanes = jax.tree.map(
        lambda a: spec(a.shape, a.dtype),
        jax.eval_shape(lambda: _init_lanes(LANES, tuple(sizes), T, 0)))
    assert LaneTileCodec.pack.lower(codec, lanes).compile()
    assert LaneTileCodec.unpack.lower(
        codec, spec((LANES, codec.width), jnp.uint32), None).compile()
