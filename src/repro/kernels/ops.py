"""jit'd public wrappers for the Pallas kernels.

Each wrapper validates preconditions, picks tile sizes against the VMEM
budget, and resolves ``interpret``: ``None`` compiles the kernel with
Mosaic on a TPU backend and interprets it anywhere else (the CPU test
suite).  Nothing else switches a kernel to interpret mode on a TPU.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import trace
from repro.kernels import flash_decode as _fd
from repro.kernels import fold as _fold
from repro.kernels import radix_partition as _rp
from repro.roofline import analysis as _roofline

#: bytes of VMEM a kernel's blocks and temporaries are sized against: the
#: scoped limit every kernel is compiled with (``fold.VMEM_LIMIT_BYTES``).
VMEM_BUDGET = _fold.VMEM_LIMIT_BYTES

#: default pair tile of the fold kernels (8 lane rows of 128 pairs).
FOLD_TILE_N = 1024

#: cap of the auto-sized fold key block.  The kernel body is unrolled over
#: the tile's lane rows, so its code (and Mosaic's compile time: ~3 s at
#: 4096 keys x 1024 pairs, ~15 s at 16384 on a described v5e) grows with
#: key block x tile.
MAX_KEY_BLOCK = 4096


def _interpret(interpret: bool | None) -> bool:
    return jax.default_backend() != "tpu" if interpret is None else interpret


def _pow2_floor(x: int) -> int:
    return 1 << (max(int(x), 1).bit_length() - 1)


def _fold_tile_n(tile_n: int, n: int) -> int:
    if tile_n % _fold.LANES:
        raise ValueError(f"tile_n={tile_n} must be a multiple of "
                         f"{_fold.LANES} (pairs move in lane rows)")
    return min(tile_n, _fold.round_up(max(n, 1), _fold.LANES))


def auto_key_block(key_space: int, *, d: int = 1,
                   tile_n: int = FOLD_TILE_N,
                   budget: int = VMEM_BUDGET) -> int:
    """Largest power-of-two key block, at most :data:`MAX_KEY_BLOCK`,
    whose fold working set (``roofline.stream_working_set_bytes``) fits
    half of ``budget``; the other half is headroom for Mosaic's own
    temporaries.  Returns ``key_space`` when the whole table fits in one
    block (no blocking needed)."""
    def fits(kb: int) -> bool:
        return kb <= MAX_KEY_BLOCK and _roofline.stream_working_set_bytes(
            chunk_pairs=tile_n, key_block=kb, d=d,
            tile_n=tile_n) <= budget // 2

    if fits(_fold.round_up(key_space, _fold.LANES)):
        return key_space
    blk = _fold.LANES
    while fits(blk * 2):
        blk *= 2
    return blk


def _fold_block(key_space: int, block_k: int | None, *, d: int,
                tile_n: int) -> int:
    """Resolve a fold kernel's key block to a lane multiple and check its
    working set against the budget."""
    if block_k is None:
        block_k = auto_key_block(key_space, d=d, tile_n=tile_n)
    block_k = _fold.round_up(min(block_k, key_space), _fold.LANES)
    step = _roofline.stream_working_set_bytes(
        chunk_pairs=tile_n, key_block=block_k, d=d, tile_n=tile_n)
    if step > VMEM_BUDGET:
        raise ValueError(
            f"key block {block_k} too large for a VMEM-resident fold "
            f"(needs {step:.0f} bytes/step); shrink block_k")
    return block_k


def chunk_monoid_fold(keys, values, acc, op="add", *, tile_n=FOLD_TILE_N,
                      block_k=None, interpret=None):
    """Fold an UNSORTED pair chunk into the carried ``[K, D]`` table.

    [N] keys, [N, D] values, [K, D] acc -> [K, D] f32; rows of keys absent
    from the chunk keep their ``acc`` values.  Signature matches the
    streaming collector's ``monoid_fold_fn(keys, mat, acc, op)``.
    ``block_k`` is the key-block grid axis: one ``[D, block_k]`` table
    block is VMEM-resident per step (``None`` sizes it against
    :data:`VMEM_BUDGET`)."""
    if values.ndim != 2:
        raise ValueError("values must be [N, D]")
    if acc.shape[1] != values.shape[1]:
        raise ValueError(f"acc shape {acc.shape} != (K, {values.shape[1]})")
    n, d = values.shape
    if n == 0:  # empty chunk: nothing to fold
        return acc.astype(jnp.float32)
    tn = _fold_tile_n(tile_n, n)
    block_k = _fold_block(acc.shape[0], block_k, d=d, tile_n=tn)
    return _fold.block_fold(keys, values, acc, op, tile_n=tn,
                            block_k=block_k, interpret=_interpret(interpret))


def onehot_fold(keys, values, acc, key_space=None, *, tile_n=FOLD_TILE_N,
                block_k=None, interpret=None):
    """Streaming-chunk additive fold: ``acc + one_hot(keys)ᵀ @ values``.

    The MXU form of :func:`chunk_monoid_fold` with ``op="add"``; the
    signature matches the streaming collector's ``fold_fn(keys, mat,
    acc)``."""
    if key_space is not None and acc.shape[0] != key_space:
        raise ValueError(f"acc shape {acc.shape} != ({key_space}, D)")
    return chunk_monoid_fold(keys, values, acc, "add", tile_n=tile_n,
                             block_k=block_k, interpret=interpret)


def onehot_combine(keys, values, key_space, *, tile_n=FOLD_TILE_N,
                   interpret=None):
    """Additive combine via the MXU one-hot fold. [N],[N,D] -> [K,D] f32."""
    if values.ndim != 2:
        raise ValueError("values must be [N, D]")
    acc = jnp.zeros((key_space, values.shape[1]), jnp.float32)
    return onehot_fold(keys, values, acc, tile_n=tile_n, interpret=interpret)


def combine_scatter(keys, values, key_space, op="add", *,
                    tile_n=FOLD_TILE_N, interpret=None):
    """General monoid combine (masked fold from identity). -> [K, D] f32."""
    if values.ndim != 2:
        raise ValueError("values must be [N, D]")
    acc = jnp.full((key_space, values.shape[1]), _fold.IDENTITY[op],
                   jnp.float32)
    return chunk_monoid_fold(keys, values, acc, op, tile_n=tile_n,
                             interpret=interpret)


#: cap of the radix bucket width.  The bucket is the ``segment_reduce``
#: key block, and each of its tiles compares 128 pairs against the whole
#: block per lane row, so the block bounds the reduce's work per pair.
MAX_BUCKET_SIZE = 16384


def auto_bucket_size(key_space: int, *, d: int = 1, pad_align: int = 256,
                     budget: int = VMEM_BUDGET) -> int:
    """Radix bucket width for the sort-flow pipeline.

    About 32 buckets per key space, at most :data:`MAX_BUCKET_SIZE` keys
    each, with a ``[bucket, D]`` table block well inside the VMEM budget.
    Buckets much smaller than ``pad_align`` would drown in per-bucket
    padding, so the floor is a few K of keys, and small key spaces keep a
    single bucket (plain segment reduce, no partition needed)."""
    blk = _pow2_floor(max(key_space // 32, 8 * pad_align))
    blk = min(blk, _pow2_floor(MAX_BUCKET_SIZE))
    while blk > 8 and blk * max(d, 1) * 4 > budget // 8:
        blk //= 2
    return key_space if blk >= key_space else blk


def radix_partition(keys, values, key_space, *, bucket_size=None,
                    pad_align=256, tile_n=256, interpret=None):
    """Radix partition of a pair chunk into padded bucket regions.

    [N] keys + [N, D] values -> (pkeys, pvals, starts); bucket ``b``
    holds keys in ``[b·bucket_size, (b+1)·bucket_size)``, every region a
    ``pad_align`` multiple (sentinel-padded) — the layout ``segment_reduce``
    consumes with ``block_k=bucket_size, tile_n=pad_align``.

    The padded output stays VMEM-resident, so the chunk plus one
    ``pad_align`` region per bucket must fit the VMEM budget; past it this
    raises rather than partition anywhere else."""
    if values.ndim != 2:
        raise ValueError("values must be [N, D]")
    n, d = values.shape
    if bucket_size is None:
        bucket_size = auto_bucket_size(key_space, d=d, pad_align=pad_align)
    num_buckets = -(-key_space // bucket_size)
    out_slots = _fold.round_up(n + num_buckets * pad_align + pad_align,
                               pad_align)
    # the padded output rows stay VMEM-resident (double-buffered), lanes
    # padded to 128: key column + the value columns
    rows_bytes = 2 * out_slots * _fold.round_up(1 + d, _fold.LANES) * 4
    if rows_bytes > VMEM_BUDGET * 3 // 4:
        raise ValueError(
            f"radix partition of {n} pairs x {num_buckets} buckets does not "
            f"fit the VMEM budget; shrink the chunk or grow bucket_size")
    return _rp.radix_partition(
        keys, values, key_space, bucket_size=bucket_size,
        pad_align=pad_align, tile_n=tile_n, interpret=_interpret(interpret))


def sort_segment_fold(keys, values, acc, op="add", *, bucket_size=None,
                      pad_align=256, interpret=None):
    """Sort-flow chunk fold: radix partition + bucket-wise segment reduce,
    merged into the carried ``[K, D]`` f32 accumulator.

    Signature matches the sort collector's ``sort_fold_fn(keys, mat, acc,
    op)``.  The partition guarantees every reduce tile falls inside one
    aligned ``bucket_size`` K-block, so ``segment_reduce`` runs with
    ``block_k=bucket_size`` — presorted segments, no per-pair scatter.
    ``bucket_size=None`` takes :func:`auto_bucket_size`."""
    if values.ndim != 2:
        raise ValueError("values must be [N, D]")
    key_space = acc.shape[0]
    n, d = values.shape
    if n == 0:
        return acc.astype(jnp.float32)
    if bucket_size is None:
        bucket_size = auto_bucket_size(key_space, d=d, pad_align=pad_align)
    with jax.named_scope(trace.PARTITION):
        pkeys, pvals, _ = radix_partition(
            keys, values, key_space, bucket_size=bucket_size,
            pad_align=pad_align, interpret=interpret)
    with jax.named_scope(trace.SEGMENT_REDUCE):
        return _segment_fold(pkeys, pvals, acc, op, tile_n=pad_align,
                             block_k=bucket_size, interpret=interpret)


def _segment_fold(sorted_keys, sorted_values, acc, op, *, tile_n, block_k,
                  interpret):
    key_space = acc.shape[0]
    if block_k >= key_space:
        block_k = _fold.round_up(key_space, _fold.LANES)
    elif block_k % _fold.LANES and _fold.LANES % block_k:
        raise ValueError(f"block_k={block_k} neither divides nor is a "
                         f"multiple of {_fold.LANES}")
    # aligned blocks nest, so a tile inside one block_k block is inside
    # one block of the lane-rounded size too
    block_k = _fold.round_up(block_k, _fold.LANES)
    return _fold.segment_reduce(sorted_keys, sorted_values, acc, op,
                                tile_n=tile_n, block_k=block_k,
                                interpret=_interpret(interpret))


def segment_reduce(sorted_keys, sorted_values, key_space, op="add", *,
                   tile_n=256, block_k=None, interpret=None):
    """Baseline reduce phase over a key-sorted stream. -> [K, D] f32.

    block_k=None lets the wrapper choose: the smallest power-of-two block
    >= the max in-tile key spread (dynamic data -> computed on host if the
    keys are concrete, else full key space).  Absent keys read as the
    monoid identity.
    """
    if block_k is None:
        try:  # concrete keys: exploit sorted locality
            ks = np.asarray(sorted_keys)
            n = ks.shape[0]
            tn = min(tile_n, max(n, 8))
            pad = (-n) % tn
            ksp = np.pad(ks, (0, pad), constant_values=key_space)
            tiles = ksp.reshape(-1, tn)
            valid = tiles < key_space
            spread = 0
            for t, m in zip(tiles, valid):
                if m.any():
                    lo_blk = int(t[m].min())
                    hi_blk = int(t[m].max())
                    spread = max(spread, hi_blk - lo_blk + 1)
            blk = 1 << max(int(np.ceil(np.log2(max(spread, 1)))), 3)
            # aligned blocks: spread fitting a block is necessary AND the
            # tile must not straddle an alignment boundary; double once.
            while blk < key_space:
                ok = all((not m.any()) or
                         (int(t[m].min()) // blk == int(t[m].max()) // blk)
                         for t, m in zip(tiles, valid))
                if ok:
                    break
                blk *= 2
            block_k = min(blk, key_space)
        except jax.errors.TracerArrayConversionError:
            block_k = key_space
    acc = jnp.full((key_space, sorted_values.shape[1]), _fold.IDENTITY[op],
                   jnp.float32)
    return _segment_fold(sorted_keys, sorted_values, acc, op, tile_n=tile_n,
                         block_k=block_k, interpret=interpret)


def flash_decode(q, k, v, kv_len, *, tile_s=512, interpret=None):
    """Single-token GQA decode attention. -> [B, H, D] f32."""
    B, H, D = q.shape
    _, S, Hkv, _ = k.shape
    if H % Hkv:
        raise ValueError("H must be a multiple of Hkv (GQA)")
    # keep K/V tile + holder within VMEM
    while tile_s * D * 4 * 2 + (H // Hkv) * (D + 2) * 4 > VMEM_BUDGET:
        tile_s //= 2
    return _fd.flash_decode(q, k, v, kv_len, tile_s=tile_s,
                            interpret=_interpret(interpret))
