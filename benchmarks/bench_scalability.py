"""Paper Fig 5 (scalability) — distributed version.

The paper scales MR4J over 1..64 hardware threads.  This container has one
core, so wall-clock scaling is meaningless; what CAN be measured exactly is
the quantity that governs scaling at pod scale: **collective wire bytes per
shard** as the shard count grows.  The combine flow all-reduces O(K) holder
tables (shard-count-independent per-shard volume) while the reduce flow
all-to-alls O(N) raw pairs.  Derived from compiled HLO on fake meshes in a
subprocess per shard count.  The children compile for the CPU backend
(``JAX_PLATFORMS=cpu``), never for a chip the parent may hold: the rows
are byte counts from CPU-compiled HLO, not device measurements."""

from __future__ import annotations

import json
import os
import subprocess
import sys
import textwrap

from benchmarks.common import bench_scale, row

_CODE = """
import sys, json
sys.path.insert(0, {src!r})
import numpy as np, jax, jax.numpy as jnp
from functools import partial
from jax.sharding import PartitionSpec as P, NamedSharding
from repro.core import MapReduceApp, plan_execution
from repro.core import engine as eng
from repro.roofline import hlo_parser

VOCAB = 512
class WC(MapReduceApp):
    key_space = VOCAB
    value_aval = jax.ShapeDtypeStruct((), jnp.int32)
    max_values_per_key = 4096
    emit_capacity = 8
    def map(self, item, emit): emit(item, jnp.ones_like(item))
    def reduce(self, key, values, count): return jnp.sum(values)

S = {S}
mesh = jax.make_mesh((S,), ("data",))
toks = jax.ShapeDtypeStruct((S * 256, 8), jnp.int32)
app = WC()
out = {{}}
with mesh:
    for flow in ("auto", "reduce"):
        plan = plan_execution(app, flow=flow)
        c = jax.jit(partial(eng.run_distributed, app, plan, mesh=mesh)).lower(toks).compile()
        hc = hlo_parser.analyze_text(c.as_text(), default_group=S)
        out["optimized" if plan.optimized else "reduce"] = hc.collective_bytes
        out.setdefault("optimized_flow", plan.flow if plan.optimized else None)
print("RESULT " + json.dumps(out))
"""

SRC = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", "src"))


def main():
    print("# paper Fig 5 analogue: per-shard collective bytes vs shard "
          "count (stream/combine flow = O(K) tables, reduce flow = "
          "O(N) pairs), counted on CPU fake meshes")
    shard_counts = (2, 4) if bench_scale() < 1 else (2, 4, 8)
    failed = []
    for S in shard_counts:
        env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=SRC,
                   XLA_FLAGS=f"--xla_force_host_platform_device_count={S}")
        r = subprocess.run([sys.executable, "-c", _CODE.format(S=S, src=SRC)],
                           capture_output=True, text=True, timeout=420,
                           env=env)
        line = [l for l in r.stdout.splitlines() if l.startswith("RESULT")]
        if not line:
            print(row(f"scalability_S{S}", 0.0,
                      f"FAILED: {r.stderr[-200:]}"))
            failed.append(S)
            continue
        data = json.loads(line[0][len("RESULT "):])
        flow = data.get("optimized_flow") or "combine"
        print(row(f"scalability_S{S}_{flow}_wire_bytes", data["optimized"],
                  "count from CPU-compiled HLO"))
        print(row(f"scalability_S{S}_reduce_wire_bytes", data["reduce"],
                  f"count from CPU-compiled HLO; "
                  f"ratio={data['reduce']/max(data['optimized'],1):.1f}x"))
    if failed:  # surface subprocess failures to run.py's health gate
        raise RuntimeError(f"scalability subprocesses failed: S={failed}")


if __name__ == "__main__":
    main()
