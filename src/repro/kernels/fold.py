"""Pallas fold kernels: the combining collector's holder-table updates.

This is the TPU lowering of the paper's combining collector.  Instead of a
hash-table insert per emitted pair (the JVM mechanism) or an atomic
scatter (the GPU mechanism), each tile of emitted pairs is compared
against a key-block iota on the VPU, and the hits are folded into the
VMEM-resident holder-table block:

* ``add``: ``table += values @ one_hot`` on the MXU;
* ``max`` / ``min``: an identity-masked reduction over the tile, on the
  VPU.

Layouts are chosen so that every block Mosaic sees is 2-D and
lane-aligned:

* keys arrive as ``[n_tiles, R, 128]`` int32 (lane rows of 128 pairs,
  ``tile_n = 128·R``);
* values arrive transposed, ``[D, N]`` f32;
* the holder table is transposed, ``[D, K]`` f32, so the key axis is the
  lane axis and a ``[D, Kb]`` block wastes at most the sublane padding
  of ``D``.

Inside a tile, each lane row of keys is turned into a key column (an
XLU transpose) and compared with a ``[128, Kb]`` lane iota; the one-hot
never leaves VMEM.  Sentinel keys (``>= key_space``) match nothing
inside the key space, and padded table columns are cropped by callers.

Two grids use the same tile fold:

* :func:`block_fold` — unsorted pairs: a key-block grid axis (outer)
  times the pair-tile axis (inner).  Each table block is loaded from the
  carried accumulator once per chunk and written back once.
* :func:`segment_reduce` — key-sorted pairs whose tiles each fall inside
  one aligned key block: scalar prefetch picks the block per tile, so a
  tile only touches its own block.  The accumulator is aliased to the
  output, so blocks no tile visits keep the carried values.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import trace

#: scoped-VMEM limit every kernel of this package is compiled with (v5e
#: has 128 MiB of VMEM per core; Mosaic's default scope is far smaller).
#: Tile sizes are budgeted against half of it, for double buffering.
VMEM_LIMIT_BYTES = 64 * 1024 * 1024

LANES = 128

IDENTITY = {"add": 0.0, "max": -jnp.inf, "min": jnp.inf}


def compiler_params(**kw) -> pltpu.CompilerParams:
    return pltpu.CompilerParams(vmem_limit_bytes=VMEM_LIMIT_BYTES, **kw)


def round_up(x: int, m: int) -> int:
    return -(-x // m) * m


def _fold_tile(keys_ref, vals_ref, out_ref, base, *, op: str):
    """Fold one tile of pairs into the resident ``[D, Kb]`` table block.

    ``keys_ref`` is ``[R, 128]`` (pair ``128·r + j`` at ``[r, j]``),
    ``vals_ref`` is ``[D, 128·R]``, and ``base`` is the first key of the
    block."""
    d, kb = out_ref.shape
    iota = lax.broadcasted_iota(jnp.int32, (LANES, kb), 1) + base
    cols = keys_ref[...].T  # [128, R]: one key column per lane row
    vals = vals_ref[...]
    for r in range(cols.shape[1]):
        hit = cols[:, r:r + 1] == iota  # [128, Kb]
        vr = vals[:, r * LANES:(r + 1) * LANES]  # [D, 128]
        if op == "add":
            out_ref[...] += jnp.dot(vr, hit.astype(jnp.float32),
                                    precision=lax.Precision.HIGHEST,
                                    preferred_element_type=jnp.float32)
            continue
        f = jnp.maximum if op == "max" else jnp.minimum
        vc = vr.T  # [128, D]
        ident = jnp.float32(IDENTITY[op])
        for c in range(d):
            masked = jnp.where(hit, vc[:, c:c + 1], ident)
            red = (masked.max(axis=0, keepdims=True) if op == "max"
                   else masked.min(axis=0, keepdims=True))
            out_ref[c:c + 1, :] = f(out_ref[c:c + 1, :], red)


def _pad_pairs(keys, values, tile_n: int, sentinel: int, op: str):
    """Keys -> ``[n_tiles, R, 128]``; values -> ``[D, N_p]`` (transposed),
    both padded to a tile multiple with sentinel pairs."""
    n = keys.shape[0]
    pad = (-n) % tile_n
    keys_p = jnp.pad(keys.astype(jnp.int32), (0, pad),
                     constant_values=sentinel)
    vals_t = jnp.pad(values.astype(jnp.float32).T, ((0, 0), (0, pad)),
                     constant_values=IDENTITY[op] if op != "add" else 0.0)
    return keys_p.reshape(-1, tile_n // LANES, LANES), vals_t


def _block_fold_kernel(keys_ref, vals_ref, acc_ref, out_ref, *, op: str):
    b = pl.program_id(0)  # outer: key block
    i = pl.program_id(1)  # inner: pair tile

    @pl.when(i == 0)
    def _init():
        out_ref[...] = acc_ref[...]

    _fold_tile(keys_ref, vals_ref, out_ref, b * out_ref.shape[1], op=op)


@functools.partial(jax.jit, static_argnames=("op", "tile_n", "block_k",
                                             "interpret"))
def block_fold(keys: jax.Array, values: jax.Array, acc: jax.Array, op: str,
               *, tile_n: int, block_k: int, interpret: bool) -> jax.Array:
    """Fold unsorted ``[N]`` keys / ``[N, D]`` values into ``acc [K, D]``.

    ``tile_n`` is a multiple of 128 and ``block_k`` a multiple of 128;
    the key space is padded to a ``block_k`` multiple (padded columns are
    cropped).  Returns the ``[K, D]`` f32 table; rows of absent keys keep
    their ``acc`` values."""
    key_space, d = acc.shape
    n_blocks = -(-key_space // block_k)
    pad_k = n_blocks * block_k - key_space
    keys3, vals_t = _pad_pairs(keys, values, tile_n, key_space, op)
    acc_t = jnp.pad(acc.astype(jnp.float32).T, ((0, 0), (0, pad_k)),
                    constant_values=IDENTITY[op])
    rows = tile_n // LANES
    fold = pl.pallas_call(
        functools.partial(_block_fold_kernel, op=op),
        grid=(n_blocks, keys3.shape[0]),
        in_specs=[
            pl.BlockSpec((None, rows, LANES), lambda b, i: (i, 0, 0)),
            pl.BlockSpec((d, tile_n), lambda b, i: (0, i)),
            pl.BlockSpec((d, block_k), lambda b, i: (0, b)),
        ],
        out_specs=pl.BlockSpec((d, block_k), lambda b, i: (0, b)),
        out_shape=jax.ShapeDtypeStruct(acc_t.shape, jnp.float32),
        compiler_params=compiler_params(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )
    with jax.named_scope(trace.FOLD):
        out = fold(keys3, vals_t, acc_t)
    return out[:, :key_space].T


def _segment_kernel(block_ids_ref, keys_ref, vals_ref, acc_ref, out_ref, *,
                    op: str):
    i = pl.program_id(0)
    blk = block_ids_ref[i]
    first_visit = (i == 0) | (blk != block_ids_ref[jnp.maximum(i, 1) - 1])

    @pl.when(first_visit)
    def _init():
        out_ref[...] = acc_ref[...]

    _fold_tile(keys_ref, vals_ref, out_ref, blk * out_ref.shape[1], op=op)


@functools.partial(jax.jit, static_argnames=("op", "tile_n", "block_k",
                                             "interpret"))
def segment_reduce(sorted_keys: jax.Array, sorted_values: jax.Array,
                   acc: jax.Array, op: str, *, tile_n: int, block_k: int,
                   interpret: bool) -> jax.Array:
    """Fold key-sorted pairs into ``acc [K, D]``, one key block per tile.

    Precondition (the callers guarantee it): every tile's keys fall inside
    one aligned ``block_k`` key block, or are sentinels.  Tiles visit
    blocks in non-decreasing order (sortedness), so a block's first visit
    loads it from ``acc`` and later tiles accumulate on top.  Blocks no
    tile visits keep ``acc`` through the input/output alias."""
    key_space, d = acc.shape
    n_blocks = -(-key_space // block_k)
    k_p = n_blocks * block_k
    if tile_n % LANES:
        # a tile narrower than a lane row gets a lane row of its own,
        # filled up with sentinel pairs, so it still folds into one block
        lanes = round_up(tile_n, LANES)
        n_tiles = -(-sorted_keys.shape[0] // tile_n)
        pad = n_tiles * tile_n - sorted_keys.shape[0]
        sorted_keys = jnp.pad(
            jnp.pad(sorted_keys, (0, pad), constant_values=k_p).reshape(
                n_tiles, tile_n), ((0, 0), (0, lanes - tile_n)),
            constant_values=k_p).reshape(-1)
        sorted_values = jnp.pad(
            jnp.pad(sorted_values, ((0, pad), (0, 0))).reshape(
                n_tiles, tile_n, d), ((0, 0), (0, lanes - tile_n), (0, 0))
        ).reshape(-1, d)
        tile_n = lanes
    keys3, vals_t = _pad_pairs(sorted_keys, sorted_values, tile_n, k_p, op)
    acc_t = jnp.pad(acc.astype(jnp.float32).T,
                    ((0, 0), (0, k_p - key_space)),
                    constant_values=IDENTITY[op])
    # scalar prefetch: the key block each tile folds into
    block_ids = jnp.minimum(keys3[:, 0, 0] // block_k,
                            n_blocks - 1).astype(jnp.int32)
    rows = tile_n // LANES
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(keys3.shape[0],),
        in_specs=[
            pl.BlockSpec((None, rows, LANES), lambda i, blk: (i, 0, 0)),
            pl.BlockSpec((d, tile_n), lambda i, blk: (0, i)),
            pl.BlockSpec((d, block_k), lambda i, blk: (0, blk[i])),
        ],
        out_specs=pl.BlockSpec((d, block_k), lambda i, blk: (0, blk[i])),
    )
    out = pl.pallas_call(
        functools.partial(_segment_kernel, op=op),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(acc_t.shape, jnp.float32),
        input_output_aliases={3: 0},
        compiler_params=compiler_params(),
        interpret=interpret,
    )(block_ids, keys3, vals_t, acc_t)
    return out[:, :key_space].T
