"""A table-valued toy job for the tests of the benchmark's check.

Items are two columns, ``key`` (int32 in ``[0, keys)``) and ``x`` (float32
in ``[0, 1)``).  Per key the job returns ``(sum of floor(100 x) as int32,
mean of x as float32)``: an exact integer sum, a float mean that is only
close, and the counts.  It is never a cell of ``BENCHMARK.json``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def make_app(cfg):
    from repro.core import MapReduceApp

    class ToyTable(MapReduceApp):
        key_space = cfg["keys"]
        value_aval = jax.ShapeDtypeStruct((), jnp.float32)
        emit_capacity = 1

        def map(self, item, emit):
            emit(item["key"][None], item["x"][None])

        def reduce(self, key, values, count):
            cents = jnp.floor(values * jnp.float32(100)).astype(jnp.int32)
            return jnp.sum(cents), jnp.sum(values) / count

    return ToyTable()


def items_shape(cfg):
    return {"key": ((cfg["rows"],), jnp.dtype(jnp.int32)),
            "x": ((cfg["rows"],), jnp.dtype(jnp.float32))}


def pairs(cfg) -> int:
    return cfg["rows"]


def generate(cfg, key):
    k_key, k_x = jax.random.split(key)
    return {"key": jax.random.randint(k_key, (cfg["rows"],), 0, cfg["keys"],
                                      jnp.int32),
            "x": jax.random.uniform(k_x, (cfg["rows"],), jnp.float32)}


def key_space(cfg) -> int:
    return cfg["keys"]
