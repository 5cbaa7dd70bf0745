"""The paper's 7 Phoenix benchmarks as MR4X MapReduce apps (Table 2 / §4.1.3).

Each app writes ONLY map + reduce (the paper's programmability claim); every
combiner in the combine-flow measurements is generated by the optimizer, as
in the paper ("These combiners are all generated by the optimizer described
in this paper").

Key/value cardinality classes from Table 2 are preserved:
  HG 768 keys × huge values | KM 100 × large | LR 5 × large | MM med × med
  PC med × med | SM 4 keys × few values (the regression case) | WC large×large
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import MapReduceApp
from repro.data import datasets

I32 = jnp.int32
F32 = jnp.float32


class Histogram(MapReduceApp):
    """HG: pixel -> (channel*256 + intensity, 1); reduce = sum."""

    key_space = 768
    value_aval = jax.ShapeDtypeStruct((), I32)
    emit_capacity = 3
    max_values_per_key = 4096  # bounded by pixels/key in the bench dataset

    def map(self, pixel, emit):  # pixel: [3] int32 rgb
        keys = jnp.arange(3, dtype=I32) * 256 + pixel
        emit(keys, jnp.ones((3,), I32))

    def reduce(self, key, values, count):
        return jnp.sum(values)


class KMeans(MapReduceApp):
    """KM: (cluster, point) -> centroid = running coordinate sum / count.

    The paper's hard case: the combiner needs state (the running sum);
    the optimizer derives the product monoid (sum over axis 0) + finalize."""

    key_space = 100
    value_aval = jax.ShapeDtypeStruct((3,), F32)
    emit_capacity = 1
    max_values_per_key = 1024

    def map(self, item, emit):
        cid, pt = item
        emit(cid.astype(I32), pt)

    def reduce(self, key, values, count):
        return jnp.sum(values, axis=0) / jnp.maximum(count, 1).astype(F32)


class LinearRegression(MapReduceApp):
    """LR: sufficient statistics (Σx, Σy, Σxx, Σxy, n) as a 5-vector sum."""

    key_space = 1
    value_aval = jax.ShapeDtypeStruct((5,), F32)
    emit_capacity = 1
    max_values_per_key = 1 << 17

    def map(self, item, emit):  # item: [2] = (x, y)
        x, y = item[0], item[1]
        emit(jnp.zeros((), I32),
             jnp.stack([x, y, x * x, x * y, jnp.ones((), F32)]))

    def reduce(self, key, values, count):
        return jnp.sum(values, axis=0)


class MatrixMultiply(MapReduceApp):
    """MM: C[i, :] contributions keyed by row; reduce = sum of partials."""

    def __init__(self, n: int, tile: int = 16):
        self.n = n
        self.tile = tile
        MatrixMultiply.key_space = n
        MatrixMultiply.value_aval = jax.ShapeDtypeStruct((n,), F32)
        MatrixMultiply.emit_capacity = 1
        MatrixMultiply.max_values_per_key = n // tile

    def map(self, item, emit):
        # item: (row_id, a_strip [tile], b_strip [tile, n]) — one row's
        # partial product over a K-tile
        row, a_strip, b_strip = item
        emit(row.astype(I32), a_strip @ b_strip)

    def reduce(self, key, values, count):
        return jnp.sum(values, axis=0)


class PCA(MapReduceApp):
    """PC: per-row mean/var statistics of the matrix (paper's PC phase 1)."""

    def __init__(self, rows: int, cols: int):
        PCA.key_space = rows
        PCA.value_aval = jax.ShapeDtypeStruct((2,), F32)
        PCA.emit_capacity = 1
        PCA.max_values_per_key = 4

    def map(self, item, emit):
        rid, row = item
        emit(rid.astype(I32),
             jnp.stack([jnp.sum(row), jnp.sum(row * row)]))

    def reduce(self, key, values, count):
        return jnp.sum(values, axis=0)


class StringMatch(MapReduceApp):
    """SM: few keys, few values, no compute — the paper's regression case
    (combiner overhead is not amortized; Fig 7 shows ~1x or below)."""

    key_space = 4
    value_aval = jax.ShapeDtypeStruct((), I32)
    emit_capacity = 1
    max_values_per_key = 4096

    def map(self, item, emit):  # item: candidate id or -1
        emit(jnp.maximum(item, 0), jnp.ones((), I32), valid=item >= 0)

    def reduce(self, key, values, count):
        return jnp.sum(values)


class WordCount(MapReduceApp):
    """WC: the running example (Figs 1-3).

    ``dtype`` is the count type: int32 as in the paper, or float32 so the
    holder tables are the f32 ones the Pallas fold kernels carry (counts
    stay exact below 2^24 per word)."""

    def __init__(self, vocab: int, dtype=I32):
        self.key_space = vocab
        self.value_aval = jax.ShapeDtypeStruct((), dtype)
        self.emit_capacity = 16
        # zipf(1.2) head: the top word is ~19% of all tokens — the reduce
        # flow must buffer it in full (the paper's materialized value lists)
        self.max_values_per_key = 16384

    def map(self, window, emit):  # [16] token ids
        emit(window, jnp.ones_like(window, self.value_aval.dtype))

    def reduce(self, key, values, count):
        return jnp.sum(values)


# ---------------------------------------------------------------------------
# dataset -> (app, items) builders, CPU-bench scale
# ---------------------------------------------------------------------------


def build(name: str, rng: np.random.Generator, scale: float = 1.0):
    s = lambda n: max(16, int(n * scale))
    if name == "HG":
        px = datasets.histogram_data(rng, pixels=s(1 << 17))
        return Histogram(), jnp.asarray(px)
    if name == "KM":
        pts, assign, k = datasets.kmeans_data(rng, points=s(1 << 14))
        return KMeans(), (jnp.asarray(assign), jnp.asarray(pts))
    if name == "LR":
        pts = datasets.linear_regression_data(rng, points=s(1 << 16))
        return LinearRegression(), jnp.asarray(pts)
    if name == "MM":
        n, tile = 96, 16
        a, b = datasets.matmul_data(rng, n=n)
        rows = np.repeat(np.arange(n), n // tile).astype(np.int32)
        a_strips = a.reshape(n, n // tile, tile)[
            np.arange(n)[:, None], np.arange(n // tile)[None, :]].reshape(-1, tile)
        b_strips = np.broadcast_to(
            b.reshape(n // tile, tile, n)[None], (n, n // tile, tile, n)
        ).reshape(-1, tile, n)
        return (MatrixMultiply(n, tile),
                (jnp.asarray(rows), jnp.asarray(a_strips),
                 jnp.asarray(np.ascontiguousarray(b_strips))))
    if name == "PC":
        m = datasets.pca_data(rng, rows=128, cols=64)
        rids = np.arange(128, dtype=np.int32)
        return PCA(128, 64), (jnp.asarray(rids), jnp.asarray(m))
    if name == "SM":
        ids = datasets.string_match_data(rng, n=s(1 << 12))
        return StringMatch(), jnp.asarray(ids)
    if name == "WC":
        n_tok = max(256, s(1 << 16) // 16 * 16)  # window-aligned
        toks, vocab = datasets.wordcount_data(rng, tokens=n_tok, vocab=4096)
        return WordCount(vocab), jnp.asarray(toks[:n_tok].reshape(-1, 16))
    raise KeyError(name)


ALL = ("HG", "KM", "LR", "MM", "PC", "SM", "WC")
