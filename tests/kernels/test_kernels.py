"""Per-kernel shape/dtype sweeps, assert_allclose against the ref.py oracle.

All kernels run in interpret mode on CPU (the kernel bodies execute in
Python; BlockSpec tiling logic is exercised for real).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref

RNG = np.random.default_rng(7)


def _vals(shape, dtype):
    if jnp.issubdtype(dtype, jnp.integer):
        return RNG.integers(-5, 6, size=shape).astype(dtype)
    return RNG.standard_normal(shape).astype(dtype)


@pytest.mark.parametrize("n,d,k", [(16, 8, 5), (100, 16, 37), (1000, 64, 256),
                                   (17, 3, 8), (513, 128, 1024)])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_onehot_combine(n, d, k, dtype):
    dtype = jnp.bfloat16 if dtype == "bfloat16" else jnp.float32
    keys = RNG.integers(0, k + 1, size=n).astype(np.int32)  # incl. sentinel
    vals = jnp.asarray(_vals((n, d), np.float32), dtype)
    got = ops.onehot_combine(jnp.asarray(keys), vals, k)
    want = ref.onehot_combine(jnp.asarray(keys), vals, k)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-2 if dtype == jnp.bfloat16 else 1e-5,
                               atol=1e-2 if dtype == jnp.bfloat16 else 1e-5)


@pytest.mark.parametrize("op", ["add", "max", "min"])
@pytest.mark.parametrize("n,d,k", [(50, 4, 11), (300, 16, 64), (64, 1, 3)])
def test_combine_scatter(op, n, d, k):
    keys = RNG.integers(0, k + 1, size=n).astype(np.int32)
    vals = _vals((n, d), np.float32)
    got = ops.combine_scatter(jnp.asarray(keys), jnp.asarray(vals), k, op)
    want = ref.combine_scatter(jnp.asarray(keys), jnp.asarray(vals), k, op)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("op", ["add", "max"])
@pytest.mark.parametrize("n,d,k,tile", [(200, 8, 512, 64), (1000, 4, 4096, 256),
                                        (64, 16, 64, 32)])
def test_segment_reduce(op, n, d, k, tile):
    keys = np.sort(RNG.integers(0, k, size=n)).astype(np.int32)
    vals = _vals((n, d), np.float32)
    got = ops.segment_reduce(jnp.asarray(keys), jnp.asarray(vals), k, op,
                             tile_n=tile)
    want = ref.segment_reduce(jnp.asarray(keys), jnp.asarray(vals), k, op)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-4)


def test_segment_reduce_skewed_keys():
    """One giant run + many singletons (stresses block-id prefetch)."""
    k = 2048
    keys = np.sort(np.concatenate([np.zeros(500, np.int32),
                                   RNG.integers(0, k, size=100)])).astype(np.int32)
    vals = _vals((600, 8), np.float32)
    got = ops.segment_reduce(jnp.asarray(keys), jnp.asarray(vals), k, "add")
    want = ref.segment_reduce(jnp.asarray(keys), jnp.asarray(vals), k, "add")
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("b,h,hkv,d,s", [
    (2, 8, 2, 64, 300), (1, 4, 4, 32, 128), (3, 16, 4, 128, 1000),
    (1, 8, 1, 64, 256),  # MQA
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_decode(b, h, hkv, d, s, dtype):
    q = jnp.asarray(_vals((b, h, d), np.float32), dtype)
    k = jnp.asarray(_vals((b, s, hkv, d), np.float32) * 0.3, dtype)
    v = jnp.asarray(_vals((b, s, hkv, d), np.float32), dtype)
    kvl = RNG.integers(1, s + 1, size=b).astype(np.int32)
    got = ops.flash_decode(q, k, v, jnp.asarray(kvl), tile_s=128)
    want = np.stack([
        ref.flash_decode(q[i], k[i], v[i], int(kvl[i])) for i in range(b)])
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-4
    np.testing.assert_allclose(np.asarray(got), want, rtol=tol, atol=tol)


def test_flash_decode_matches_monoid():
    """The kernel IS the attention combiner: folding KV tiles with the
    (m, l, acc) monoid gives the same answer as the fused kernel."""
    b, h, hkv, d, s, tile = 1, 2, 1, 16, 64, 16
    q = jnp.asarray(_vals((b, h, d), np.float32))
    k = jnp.asarray(_vals((b, s, hkv, d), np.float32))
    v = jnp.asarray(_vals((b, s, hkv, d), np.float32))
    kernel = ops.flash_decode(q, k, v, jnp.asarray([s], np.int32), tile_s=tile)

    # manual fold over tiles with the monoid
    scale = 1.0 / np.sqrt(d)
    qf = np.asarray(q[0], np.float64) * scale
    kf = np.repeat(np.asarray(k[0], np.float64), h // hkv, axis=1)
    vf = np.repeat(np.asarray(v[0], np.float64), h // hkv, axis=1)
    m = np.full((h,), -np.inf)
    l = np.zeros((h,))
    acc = np.zeros((h, d))
    for t0 in range(0, s, tile):
        logits = np.einsum("hd,thd->ht", qf, kf[t0:t0 + tile])
        m_new = np.maximum(m, logits.max(1))
        alpha = np.exp(m - m_new)
        p = np.exp(logits - m_new[:, None])
        l = l * alpha + p.sum(1)
        acc = acc * alpha[:, None] + np.einsum("ht,thd->hd", p, vf[t0:t0 + tile])
        m = m_new
    want = acc / l[:, None]
    np.testing.assert_allclose(np.asarray(kernel[0]), want, rtol=1e-4,
                               atol=1e-4)


@pytest.mark.parametrize("backend,want", [("tpu", False), ("cpu", True)])
def test_interpret_follows_the_backend_only(monkeypatch, backend, want):
    """Kernels interpret exactly off a TPU; no environment variable
    switches a TPU run to interpret mode."""
    monkeypatch.setenv("JAX_PALLAS_INTERPRET", "1")
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    assert ops._interpret(None) is want
    assert ops._interpret(not want) is (not want)  # explicit choice wins


def test_onehot_vmem_guard():
    """A key space past one VMEM-resident block is split into key blocks;
    an explicit block past the budget still trips the guard."""
    keys = jnp.asarray([0, 5, 2 ** 21 - 1, 2 ** 21], jnp.int32)
    got = np.asarray(ops.onehot_combine(keys, jnp.ones((4, 2)), 2 ** 21))
    assert got.shape == (2 ** 21, 2)
    assert got[[0, 5, 2 ** 21 - 1]].tolist() == [[1, 1]] * 3
    assert got.sum() == 6
    with pytest.raises(ValueError, match="VMEM"):
        ops.chunk_monoid_fold(keys, jnp.ones((4, 256)),
                              jnp.zeros((2 ** 21, 256)), "max",
                              block_k=2 ** 21)


@pytest.mark.parametrize("n,d,k", [(16, 4, 5), (100, 8, 64), (513, 2, 300)])
def test_onehot_fold(n, d, k):
    """Streaming-chunk additive fold accumulates on top of the carry."""
    keys = RNG.integers(0, k + 1, size=n).astype(np.int32)  # incl. sentinel
    vals = jnp.asarray(_vals((n, d), np.float32))
    acc = jnp.asarray(_vals((k, d), np.float32))
    got = ops.onehot_fold(jnp.asarray(keys), vals, acc)
    want = ref.onehot_fold(jnp.asarray(keys), vals, acc)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("op", ["add", "max", "min"])
@pytest.mark.parametrize("n,d,k", [(50, 4, 11), (200, 2, 37)])
def test_chunk_monoid_fold(op, n, d, k):
    """Unsorted-chunk monoid fold: carry rows for absent keys unchanged."""
    keys = RNG.integers(0, k + 1, size=n).astype(np.int32)
    vals = jnp.asarray(_vals((n, d), np.float32))
    acc = jnp.asarray(_vals((k, d), np.float32))
    got = ops.chunk_monoid_fold(jnp.asarray(keys), vals, acc, op)
    want = ref.chunk_monoid_fold(jnp.asarray(keys), vals, acc, op)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


def test_chunk_fold_chain_matches_single_shot():
    """Folding a stream chunk-by-chunk == one-shot combine (holder carry)."""
    n, d, k, chunk = 96, 4, 17, 32
    keys = RNG.integers(0, k, size=n).astype(np.int32)
    vals = _vals((n, d), np.float32)
    acc = jnp.zeros((k, d), jnp.float32)
    for t0 in range(0, n, chunk):
        acc = ops.onehot_fold(jnp.asarray(keys[t0:t0 + chunk]),
                              jnp.asarray(vals[t0:t0 + chunk]), acc)
    want = ref.onehot_combine(jnp.asarray(keys), jnp.asarray(vals), k)
    np.testing.assert_allclose(np.asarray(acc), np.asarray(want),
                               rtol=1e-4, atol=1e-4)


def test_fold_kernels_empty_chunk():
    """n=0 chunks return the accumulator unchanged instead of crashing."""
    acc = jnp.asarray(_vals((9, 3), np.float32))
    got = ops.onehot_fold(jnp.zeros((0,), jnp.int32),
                          jnp.zeros((0, 3), jnp.float32), acc)
    np.testing.assert_allclose(np.asarray(got), np.asarray(acc))
    got = ops.chunk_monoid_fold(jnp.zeros((0,), jnp.int32),
                                jnp.zeros((0, 3), jnp.float32), acc, "max")
    np.testing.assert_allclose(np.asarray(got), np.asarray(acc))


@pytest.mark.parametrize("n,k,bs,pa", [
    (64, 64, 16, 8), (200, 128, 32, 16), (300, 100, 32, 16),  # K % bs != 0
    (50, 256, 256, 16),  # single bucket
])
def test_radix_partition_matches_ref(n, k, bs, pa):
    """Two-pass histogram + bucket-scatter vs the argsort oracle: identical
    padded layout, bucket-grouped keys, stable within-bucket order."""
    keys = RNG.integers(0, k + 1, size=n).astype(np.int32)  # incl. sentinel
    vals = _vals((n, 4), np.float32)
    got_k, got_v, got_s = ops.radix_partition(
        jnp.asarray(keys), jnp.asarray(vals), k, bucket_size=bs, pad_align=pa,
        tile_n=pa)
    want_k, want_v, want_s = ref.radix_partition(
        jnp.asarray(keys), jnp.asarray(vals), k, bucket_size=bs, pad_align=pa)
    np.testing.assert_array_equal(np.asarray(got_s), np.asarray(want_s))
    np.testing.assert_array_equal(np.asarray(got_k), np.asarray(want_k))
    # value rows: only real-pair slots are contractual (pad slots carry
    # zeros in both; sentinel/trash slot contents are dropped downstream)
    real = np.asarray(want_k) < k
    np.testing.assert_allclose(np.asarray(got_v)[real],
                               np.asarray(want_v)[real], rtol=1e-6)


def test_radix_partition_bucket_invariants():
    """Every non-sentinel key lies inside its bucket's key range and every
    bucket region is pad_align-aligned."""
    n, k, bs, pa = 500, 512, 64, 32
    keys = RNG.integers(0, k, size=n).astype(np.int32)
    vals = _vals((n, 1), np.float32)
    pk, _, starts = ops.radix_partition(jnp.asarray(keys), jnp.asarray(vals),
                                        k, bucket_size=bs, pad_align=pa)
    pk, starts = np.asarray(pk), np.asarray(starts)
    assert (starts % pa == 0).all()
    for b in range(k // bs):
        lo = starts[b]
        hi = starts[b + 1] if b + 1 < len(starts) else len(pk)
        seg = pk[lo:hi]
        real = seg[seg < k]
        assert ((real >= b * bs) & (real < (b + 1) * bs)).all(), b
    got = np.sort(pk[pk < k])
    np.testing.assert_array_equal(got, np.sort(keys))


@pytest.mark.parametrize("op", ["add", "max", "min"])
@pytest.mark.parametrize("n,d,k,bs", [(100, 3, 64, 16), (333, 2, 1000, 256)])
def test_sort_segment_fold_matches_ref(op, n, d, k, bs):
    """Radix partition + segment_reduce pipeline == argsort/segment oracle,
    merged into a carried accumulator."""
    keys = RNG.integers(0, k + 1, size=n).astype(np.int32)
    vals = jnp.asarray(_vals((n, d), np.float32))
    acc = jnp.asarray(_vals((k, d), np.float32))
    got = ops.sort_segment_fold(jnp.asarray(keys), vals, acc, op,
                                bucket_size=bs)
    want = ref.sort_segment_fold(jnp.asarray(keys), vals, acc, op)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-4)


def test_radix_partition_vmem_guard():
    with pytest.raises(ValueError, match="VMEM"):
        ops.radix_partition(jnp.zeros(1 << 16, jnp.int32),
                            jnp.zeros((1 << 16, 128), jnp.float32),
                            key_space=1 << 20, bucket_size=256)


# ---------------------------------------------------------------------------
# Radix partition over many buckets, any pair tile
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("n,k,bs,tile_n,pa", [
    (200, 256, 16, 16, 16),
    (300, 100, 8, 48, 16),          # K % bs != 0, tile not a pa multiple
    (500, 1000, 16, 32, 32),        # 63 buckets, ragged last one
    (64, 64, 4, 8, 8),
    (333, 2000, 64, 1000, 16),      # one tile holds every pair
])
def test_radix_partition_multi_matches_single_level_oracle(n, k, bs,
                                                           tile_n, pa):
    """The one-pass partition over many buckets is bitwise the argsort
    oracle, whatever tile the kernels walk the pairs in: the scalar walk
    is in pair order, so the partition is stable."""
    keys = RNG.integers(0, k + 1, size=n).astype(np.int32)  # incl. sentinel
    vals = _vals((n, 3), np.float32)
    got_k, got_v, got_s = ops.radix_partition(
        jnp.asarray(keys), jnp.asarray(vals), k, bucket_size=bs,
        pad_align=pa, tile_n=tile_n)
    want_k, want_v, want_s = ref.radix_partition(
        jnp.asarray(keys), jnp.asarray(vals), k, bucket_size=bs, pad_align=pa)
    np.testing.assert_array_equal(np.asarray(got_s), np.asarray(want_s))
    np.testing.assert_array_equal(np.asarray(got_k), np.asarray(want_k))
    real = np.asarray(want_k) < k
    np.testing.assert_allclose(np.asarray(got_v)[real],
                               np.asarray(want_v)[real], rtol=1e-6)


def test_radix_partition_multi_bucket_invariants():
    """Bucket regions of a 32-bucket partition: every real key inside its
    bucket range, aligned region starts, nothing lost, trash slots
    sentinel-normalized."""
    n, k, bs, pa = 400, 512, 16, 16
    keys = RNG.integers(0, k + 1, size=n).astype(np.int32)
    vals = _vals((n, 1), np.float32)
    pk, _, starts = ops.radix_partition(
        jnp.asarray(keys), jnp.asarray(vals), k, bucket_size=bs,
        pad_align=pa, tile_n=pa)
    pk, starts = np.asarray(pk), np.asarray(starts)
    assert starts.shape[0] == k // bs
    assert (starts % pa == 0).all()
    assert (pk <= k).all()  # every dropped slot carries THE sentinel
    for b in range(k // bs):
        lo = starts[b]
        hi = starts[b + 1] if b + 1 < len(starts) else len(pk)
        real = pk[lo:hi][pk[lo:hi] < k]
        assert ((real >= b * bs) & (real < (b + 1) * bs)).all(), b
    np.testing.assert_array_equal(np.sort(pk[pk < k]),
                                  np.sort(keys[keys < k]))


@pytest.mark.parametrize("op", ["add", "max", "min"])
def test_sort_segment_fold_multi_level_matches_ref(op):
    """The full pipeline over many buckets (12 buckets of 256 keys, the
    last one ragged, feeding segment_reduce key blocks) == the
    argsort/segment oracle, merged into a carried accumulator."""
    n, d, k = 333, 2, 3000
    keys = RNG.integers(0, k + 1, size=n).astype(np.int32)
    vals = jnp.asarray(_vals((n, d), np.float32))
    acc = jnp.asarray(_vals((k, d), np.float32))
    got = ops.sort_segment_fold(jnp.asarray(keys), vals, acc, op,
                                bucket_size=256)
    want = ref.sort_segment_fold(jnp.asarray(keys), vals, acc, op)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-4)


def test_plan_radix_levels_small_keyspaces_stay_single_level():
    """Bucket sizing: a single bucket for tiny K (plain segment reduce),
    about K/32 keys per bucket above the 8·pad_align floor."""
    assert ops.auto_bucket_size(512) == 512
    assert ops.auto_bucket_size(32768, d=2) == 2048
    assert ops.auto_bucket_size(131072, d=2) == 4096


def test_plan_radix_levels_multi_level_and_budget():
    """Large key spaces keep buckets at MAX_BUCKET_SIZE and grow their
    count; a bucket count whose padded regions overflow the partition's
    VMEM budget raises — it never partitions anywhere else."""
    bs = ops.auto_bucket_size(1 << 20, d=2)
    assert bs == ops.MAX_BUCKET_SIZE
    assert (1 << 20) // bs == 64
    keys = jnp.zeros(16384, jnp.int32)
    vals = jnp.zeros((16384, 1), jnp.float32)
    with pytest.raises(ValueError, match="VMEM"):
        ops.radix_partition(keys, vals, 1 << 22)  # 256 buckets


def test_radix_partition_multi_requires_aligned_tiles():
    """The raw kernel walks its pairs in order, so no tile length is
    required to match pad_align: a tile shorter than pad_align, a ragged
    one and one longer than the chunk give the same layout."""
    from repro.kernels import radix_partition as rp

    keys = RNG.integers(0, 257, size=64).astype(np.int32)
    vals = _vals((64, 1), np.float32)
    want_k, _, want_s = ref.radix_partition(
        jnp.asarray(keys), jnp.asarray(vals), 256, bucket_size=16,
        pad_align=16)
    for tile_n in (8, 24, 128):
        got_k, _, got_s = rp.radix_partition(
            jnp.asarray(keys), jnp.asarray(vals), 256, bucket_size=16,
            pad_align=16, tile_n=tile_n, interpret=True)
        np.testing.assert_array_equal(np.asarray(got_k), np.asarray(want_k))
        np.testing.assert_array_equal(np.asarray(got_s), np.asarray(want_s))


def test_fold_kernel_autoblocks_past_vmem_budget():
    """A key space whose [Tn, K] one-hot would blow VMEM is auto-partitioned
    into key blocks instead of raising; an explicitly oversized block still
    trips the guard (which accounts for the one-hot, not just the table)."""
    K = 1 << 20
    assert ops.auto_key_block(K, d=1, tile_n=512) < K
    keys = jnp.asarray(RNG.integers(0, K, 512).astype(np.int32))
    got = ops.onehot_fold(keys, jnp.ones((512, 1), jnp.float32),
                          jnp.zeros((K, 1), jnp.float32))
    want = np.zeros(K); np.add.at(want, np.asarray(keys), 1.0)
    np.testing.assert_array_equal(np.asarray(got)[:, 0], want)
    with pytest.raises(ValueError, match="VMEM"):
        ops.onehot_fold(jnp.zeros(512, jnp.int32), jnp.zeros((512, 1)),
                        jnp.zeros((K, 1)), block_k=K)
