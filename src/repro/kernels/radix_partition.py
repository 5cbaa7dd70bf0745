"""Radix partition for the sort flow: pairs grouped into padded buckets.

A chunk of emitted pairs is partitioned by key into contiguous bucket
regions (bucket ``b`` holds keys in ``[b·bucket_size, (b+1)·bucket_size)``),
each region padded to a multiple of ``pad_align`` slots — the alignment
``segment_reduce`` needs so that every tile of ``pad_align`` pairs falls
inside ONE aligned key block.  The partition is the chunk-local form of
the paper's shuffle: pairs move once, and the reduce consumes presorted
segments instead of scattering per pair.

The partition is two kernels:

1. ``_hist_kernel`` counts the pairs of each bucket;
2. ``_scatter_kernel`` copies each pair's row to its bucket's cursor.

Both walk the pairs one at a time on the scalar unit: the keys of a tile
sit in SMEM (a ``[1, tile_n]`` block of ``[n_tiles, 1, tile_n]``), the
bucket cursors live in an SMEM scratch, and the scatter moves one
``[1, C]`` row per pair with a dynamic sublane offset in VMEM.
Walking the pairs in order makes the partition stable, which the
first-element idiom and the bitwise tests rely on.  (A vector form would
need a gather of the cursors, which Mosaic does not lower.)

A pair is one int32 row: the key in column 0, then the values' f32 bits.
The whole padded output stays VMEM-resident across the grid.

Keys are int32 in ``[0, key_space]``; tile padding carries the sentinel
``key_space``, which maps past the last bucket and so drops into the
trash slot.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.fold import compiler_params


def _bucket(key, bucket_size: int, num_buckets: int):
    """Bucket id of ``key``; ``num_buckets`` means drop."""
    if bucket_size & (bucket_size - 1) == 0:
        b = key >> (bucket_size.bit_length() - 1)
    else:
        b = key // bucket_size
    return jnp.minimum(b, num_buckets)


def _hist_kernel(keys_ref, hist_ref, *, bucket_size: int, num_buckets: int):
    @pl.when(pl.program_id(0) == 0)
    def _init():
        def zero(b, c):
            hist_ref[b] = 0
            return c

        lax.fori_loop(0, num_buckets + 1, zero, 0)

    def count(j, c):
        b = _bucket(keys_ref[0, j], bucket_size, num_buckets)
        hist_ref[b] += 1
        return c

    lax.fori_loop(0, keys_ref.shape[1], count, 0)


def _scatter_kernel(starts_ref, keys_ref, rows_ref, out_ref, cursor_ref, *,
                    bucket_size: int, num_buckets: int, pad_key: int):
    @pl.when(pl.program_id(0) == 0)
    def _init():
        def seed(b, c):
            cursor_ref[b] = starts_ref[b]
            return c

        lax.fori_loop(0, num_buckets, seed, 0)
        cursor_ref[num_buckets] = out_ref.shape[0] - 1  # the trash slot
        lane = lax.broadcasted_iota(jnp.int32, out_ref.shape, 1)
        out_ref[...] = jnp.where(lane == 0, pad_key, 0)

    def move(j, c):
        b = _bucket(keys_ref[0, j], bucket_size, num_buckets)
        dst = cursor_ref[b]
        cursor_ref[b] = dst + jnp.where(b < num_buckets, 1, 0)
        out_ref[pl.ds(dst, 1), :] = rows_ref[pl.ds(j, 1), :]
        return c

    lax.fori_loop(0, keys_ref.shape[1], move, 0)


@functools.partial(jax.jit, static_argnames=(
    "key_space", "bucket_size", "pad_align", "tile_n", "interpret"))
def radix_partition(
    keys: jax.Array,
    values: jax.Array,
    key_space: int,
    *,
    bucket_size: int,
    pad_align: int,
    tile_n: int,
    interpret: bool,
):
    """Partition ``[N]`` keys + ``[N, D]`` values into padded buckets.

    Returns ``(pkeys [Np], pvals [Np, D], starts [B])``: bucket ``b``
    occupies ``pkeys[starts[b] : starts[b] + padded_count[b]]``, every
    region is a ``pad_align`` multiple, pad slots carry the sentinel
    ``key_space`` and the final ``pad_align`` slots are the trash region
    for invalid pairs.  ``tile_n`` (any length) is the pair tile the
    kernels walk per grid step."""
    n, d = values.shape
    num_buckets = -(-key_space // bucket_size)
    tile_n = min(tile_n, max(n, 8))
    pad = (-n) % tile_n
    n_tiles = (n + pad) // tile_n
    keys_p = jnp.pad(keys.astype(jnp.int32), (0, pad),
                     constant_values=key_space)
    bits = lax.bitcast_convert_type(values.astype(jnp.float32), jnp.int32)
    rows = jnp.concatenate([keys_p[:, None],
                            jnp.pad(bits, ((0, pad), (0, 0)))], axis=1)
    key_tiles = keys_p.reshape(n_tiles, 1, tile_n)  # SMEM tiles
    smem_tile = pl.BlockSpec((None, 1, tile_n), lambda i: (i, 0, 0),
                             memory_space=pltpu.SMEM)
    kw = dict(bucket_size=bucket_size, num_buckets=num_buckets)
    hist = pl.pallas_call(
        functools.partial(_hist_kernel, **kw),
        grid=(n_tiles,),
        in_specs=[smem_tile],
        out_specs=pl.BlockSpec(memory_space=pltpu.SMEM),
        out_shape=jax.ShapeDtypeStruct((num_buckets + 1,), jnp.int32),
        compiler_params=compiler_params(),
        interpret=interpret,
    )(key_tiles)[:num_buckets]
    padded = -(-hist // pad_align) * pad_align
    starts = (jnp.cumsum(padded) - padded).astype(jnp.int32)
    out_slots = n + num_buckets * pad_align + pad_align  # + trash
    out_slots += (-out_slots) % pad_align
    c = rows.shape[1]
    out = pl.pallas_call(
        functools.partial(_scatter_kernel, pad_key=key_space, **kw),
        grid=(n_tiles,),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            smem_tile,
            pl.BlockSpec((tile_n, c), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((out_slots, c), lambda i: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((out_slots, c), jnp.int32),
        scratch_shapes=[pltpu.SMEM((num_buckets + 1,), jnp.int32)],
        compiler_params=compiler_params(),
        interpret=interpret,
    )(starts, key_tiles, rows)
    # every dropped slot (pads, trash) reads as THE sentinel downstream
    pkeys = jnp.minimum(out[:, 0], key_space)
    pvals = lax.bitcast_convert_type(out[:, 1:], jnp.float32)
    return pkeys, pvals, starts
