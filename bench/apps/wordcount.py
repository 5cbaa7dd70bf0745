"""Phoenix word_count as a MapReduce job, and its input made on the device.

The job is the one every user of the engine writes: map a window of word
ids to ``(word, 1)`` pairs, reduce by summing (``benchmarks/apps.py``
``WordCount``, copied here so that the benchmark does not change when that
file does).  The input is ``windows x window_tokens`` int32 word ids drawn
from the seed by rank from a Zipf law truncated to the vocabulary.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def make_app(cfg):
    from repro.core import MapReduceApp

    dtype = jnp.dtype(cfg["count_dtype"])

    class WordCount(MapReduceApp):
        key_space = cfg["vocab"]
        value_aval = jax.ShapeDtypeStruct((), dtype)
        emit_capacity = cfg["window_tokens"]
        # the reduce flow buffers the Zipf head in full
        max_values_per_key = 16384

        def map(self, window, emit):
            emit(window, jnp.ones_like(window, dtype))

        def reduce(self, key, values, count):
            return jnp.sum(values)

    return WordCount()


def items_shape(cfg):
    return (cfg["windows"], cfg["window_tokens"]), jnp.int32


def pairs(cfg) -> int:
    return cfg["windows"] * cfg["window_tokens"]


def generate(cfg, key):
    """Word ids ``[windows, window_tokens]``: rank r of a Zipf(a) law
    truncated to ``vocab`` words, drawn by inverting its CDF."""
    vocab = cfg["vocab"]
    ranks = jnp.arange(1, vocab + 1, dtype=jnp.float32)
    cdf = jnp.cumsum(ranks ** -cfg["zipf_a"])
    cdf = cdf / cdf[-1]
    u = jax.random.uniform(key, (pairs(cfg),), jnp.float32)
    ids = jnp.minimum(jnp.searchsorted(cdf, u, side="right"), vocab - 1)
    return ids.astype(jnp.int32).reshape(items_shape(cfg)[0])


def key_space(cfg) -> int:
    return cfg["vocab"]
