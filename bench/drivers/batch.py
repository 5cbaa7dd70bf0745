"""Batch jobs on one chip: ``MapReduce(app, flow=...).run(items)``.

Set-up makes the input on the chip, plans the job, compiles it (the
ahead-of-time executable that ``run`` then finds in the engine's in-memory
cache) and runs it once.  The window runs whole jobs back to back, some
dispatched ahead.
"""

from __future__ import annotations

from bench import harness
from bench.jobs import BatchJobs


class Driver(BatchJobs):
    def __init__(self, run: harness.Run):
        from repro.core import MapReduce

        self.run = run
        self.items = run.make_items()
        self.mr = MapReduce(run.app_mod.make_app(run.cfg),
                            flow=run.traffic["flow"])
        self.compiled = self.mr.lower(self.items).compile()
        self.warm_up()
        harness.log(f"plan: {self.plan()}")

    def call(self):
        return self.mr.run(self.items)

    def hlo_texts(self) -> list[str]:
        return [self.compiled.as_text()]
