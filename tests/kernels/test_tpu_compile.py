"""Compile the main path's Pallas kernels with Mosaic for a described v5e.

Nothing runs: each kernel is lowered at the main path's tile shapes for a
TPU v5e that is described, not attached, and the compiled module must hold
the Mosaic kernel (``tpu_custom_call``).  This catches what interpret mode
cannot: block layouts Mosaic refuses, unsupported vector ops, VMEM
overruns.  The topology is described inside a fixture, so collection
never loads the TPU compiler.
"""

import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import ops

K = 131072  # WordCount vocabulary of the chip smoke
D = 2  # one f32 count channel + the counts column


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    # a compile for a described chip cannot be read back from the
    # persistent cache without the chip: keep it out of the cache
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", enabled)


def _compile_text(fn, *shapes):
    return jax.jit(fn).lower(*shapes).compile().as_text()


def _spec(sharding, shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


@pytest.mark.parametrize("op", ["add", "max"])
def test_fold_kernels_compile(one_chip, op):
    """``onehot_fold`` (add) and ``chunk_monoid_fold`` (max) with the
    key-block grid axis: 1024-pair tiles, 4096-key blocks."""
    n = 8 * ops.FOLD_TILE_N

    def fold(keys, vals, acc):
        return ops.chunk_monoid_fold(keys, vals, acc, op, interpret=False)

    text = _compile_text(fold, _spec(one_chip, (n,), jnp.int32),
                         _spec(one_chip, (n, D)), _spec(one_chip, (K, D)))
    assert "tpu_custom_call" in text


def test_segment_reduce_compiles(one_chip):
    """Sorted pairs in 256-pair tiles, scalar-prefetched 4096-key blocks
    (the radix layout of a 16384-pair sort chunk)."""
    n = 24832

    def seg(keys, vals):
        return ops.segment_reduce(keys, vals, K, "add", tile_n=256,
                                  block_k=4096, interpret=False)

    text = _compile_text(seg, _spec(one_chip, (n,), jnp.int32),
                         _spec(one_chip, (n, D)))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("key_space,buckets", [(K, 32), (1 << 20, 64)])
def test_radix_partition_compiles(one_chip, key_space, buckets):
    """The sort flow's radix partition of a 16384-pair chunk, with the
    bucket size the engine derives: 32 buckets at the smoke's vocabulary,
    64 at a million keys."""
    bucket_size = ops.auto_bucket_size(key_space, d=D)
    assert -(-key_space // bucket_size) == buckets
    n = 16384

    def part(keys, vals):
        return ops.radix_partition(keys, vals, key_space,
                                   bucket_size=bucket_size, interpret=False)

    text = _compile_text(part, _spec(one_chip, (n,), jnp.int32),
                         _spec(one_chip, (n, D)))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("platform", ["tpu", "cpu"])
def test_wordcount_batch_fold_lowering_compiles(one_chip, monkeypatch,
                                                platform):
    """The WordCount batch job (16 words a window, K = 131,072) through
    ``MapReduce.run``'s program: with the fold rule set to the TPU the fold
    scatters, 65,536 pairs a chunk, and holds no compare-select-reduce over
    ``[2048, 8192]`` one-hot blocks; with it set to the CPU it keeps them."""
    from repro.core import MapReduce, make_app
    from repro.core import collector as col

    monkeypatch.setattr(col, "fold_platform", lambda: platform)
    app = make_app(
        lambda window, emit: emit(window, jnp.ones_like(window)),
        lambda k, v, c: jnp.sum(v),
        key_space=K, value_aval=jax.ShapeDtypeStruct((), jnp.int32),
        emit_capacity=16, max_values_per_key=16384)
    mr = MapReduce(app, cache=False)
    items = _spec(one_chip, (1 << 14, 16), jnp.int32)
    text = mr.lower(items).compile().as_text()
    # the one-hot of 2,048 pairs against an 8,192-key block, in either layout
    onehot_blocks = re.search(
        r"pred\[(2048,8192|8192,2048)\]\S* compare\(", text) is not None
    if platform == "tpu":
        assert mr.tiling.mode == "scatter" and mr.tiling.chunk_pairs == 65536
        assert "scatter(" in text and not onehot_blocks
    else:
        assert mr.tiling.mode == "additive"
        assert onehot_blocks
