"""Batch jobs over a mesh of chips: ``MapReduce(app, flow=...)
.run_distributed(items, mesh=..., options=ExecutionOptions(shuffle=...))``.

The input is made on the chips already sharded over the mesh's one data
axis.  The shuffle's per-destination capacity is ``"shard_pairs"`` (every
destination can take all of one source shard's pairs, so nothing can
overflow) or a number; ``strict`` makes an overflow raise.  Set-up runs one
job, which compiles the jitted ``shard_map`` the entry point keeps.
"""

from __future__ import annotations

from bench import harness
from bench.jobs import BatchJobs


class Driver(BatchJobs):
    def __init__(self, run: harness.Run):
        import jax
        from jax.sharding import NamedSharding
        from jax.sharding import PartitionSpec as P

        from repro.core import ExecutionOptions, MapReduce, ShuffleOptions

        tr = run.traffic
        self.run = run
        self.mesh = jax.make_mesh((len(run.devices),), (tr["axis"],),
                                  devices=run.devices)
        self.items = run.make_items(NamedSharding(self.mesh, P(tr["axis"])))
        app = run.app_mod.make_app(run.cfg)
        cap = tr["capacity"]
        if cap == "shard_pairs":
            cap = run.app_mod.pairs(run.cfg) // len(run.devices)
        self.options = ExecutionOptions(
            mesh=self.mesh, data_axis=tr["axis"],
            shuffle=ShuffleOptions(capacity=int(cap), strict=tr["strict"],
                                   wire=tr["wire"]))
        self.mr = MapReduce(app, flow=tr["flow"])
        self.warm_up()
        harness.log(f"plan: {self.plan()}")

    def call(self):
        return self.mr.run_distributed(self.items, options=self.options)

    def hlo_texts(self) -> list[str]:
        jitted = self.mr.lower(self.items, options=self.options
                               ).compile()._entry.executable
        return [jitted.lower(self.items).compile().as_text()]
