"""Distributed word count on a 4-device mesh: the combine flow merges
holder tables with an all-reduce (O(K)); the baseline shuffles raw pairs
with all-to-all (O(N)).  Prints both results + the collectives each flow
lowered to.  On a host with four chips it runs as is; on a CPU, ask for
four fake devices:

  XLA_FLAGS=--xla_force_host_platform_device_count=4 JAX_PLATFORMS=cpu \
      PYTHONPATH=src python examples/wordcount_cluster.py
"""

import os
import sys
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import re
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core import MapReduceApp, plan_execution
from repro.core import engine as eng

VOCAB = 64


class WordCount(MapReduceApp):
    key_space = VOCAB
    value_aval = jax.ShapeDtypeStruct((), jnp.int32)
    emit_capacity = 8
    max_values_per_key = 512

    def map(self, item, emit):
        emit(item, jnp.ones_like(item))

    def reduce(self, key, values, count):
        return jnp.sum(values)


if len(jax.devices()) < 4:
    sys.exit(f"needs 4 devices, found {len(jax.devices())}: set XLA_FLAGS "
             f"as the docstring shows")
mesh = jax.make_mesh((4,), ("data",))
rng = np.random.default_rng(0)
toks = jax.device_put(
    jnp.asarray(rng.integers(0, VOCAB, (128, 8)).astype(np.int32)),
    NamedSharding(mesh, P("data")))
app = WordCount()
want = np.bincount(np.asarray(toks).reshape(-1), minlength=VOCAB)

with mesh:
    for flow in ("auto", "reduce"):
        plan = plan_execution(app, flow=flow)
        k, v, c = eng.run_distributed(app, plan, toks, mesh=mesh)
        txt = jax.jit(partial(eng.run_distributed, app, plan, mesh=mesh)
                      ).lower(toks).compile().as_text()
        colls = sorted(set(re.findall(
            r"(all-reduce|all-gather|all-to-all|collective-permute)", txt)))
        print(f"{plan.flow:8s} flow -> collectives: {colls}")
        if plan.optimized:  # stream/combine: replicated O(K) tables
            assert np.array_equal(np.asarray(v), want)
print("distributed word count OK")
