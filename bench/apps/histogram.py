"""Phoenix histogram as a MapReduce job, and its bitmap made on the device.

Map a 24-bit pixel to ``(channel * 256 + intensity, 1)`` for each of its
three channels, reduce by summing (``benchmarks/apps.py`` ``Histogram``,
copied).  Pixels stay at the source's width: uint8 ``[pixels, 3]``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def make_app(cfg):
    from repro.core import MapReduceApp

    dtype = jnp.dtype(cfg["count_dtype"])
    channels, levels = cfg["channels"], cfg["levels"]

    class Histogram(MapReduceApp):
        key_space = channels * levels
        value_aval = jax.ShapeDtypeStruct((), dtype)
        emit_capacity = channels
        max_values_per_key = 4096

        def map(self, pixel, emit):
            keys = jnp.arange(channels, dtype=jnp.int32) * levels + pixel
            emit(keys, jnp.ones((channels,), dtype))

        def reduce(self, key, values, count):
            return jnp.sum(values)

    return Histogram()


def items_shape(cfg):
    return (cfg["pixels"], cfg["channels"]), jnp.dtype(cfg["pixel_dtype"])


def pairs(cfg) -> int:
    return cfg["pixels"] * cfg["channels"]


def generate(cfg, key):
    """Uniform pixels ``[pixels, 3]`` uint8: the low three bytes of one
    random 32-bit word per pixel."""
    bits = jax.random.bits(key, (cfg["pixels"],), jnp.uint32)
    chans = [(bits >> (8 * c)) & 0xFF for c in range(cfg["channels"])]
    return jnp.stack(chans, axis=1).astype(jnp.dtype(cfg["pixel_dtype"]))


def key_space(cfg) -> int:
    return cfg["channels"] * cfg["levels"]
