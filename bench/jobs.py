"""Whole-input jobs back to back: what the batch drivers share.

A subclass sets ``run``, ``items`` and ``mr``, defines ``call()``, the
entry-point call that starts one job, and runs ``warm_up()`` in set-up.  A
job is that call plus copying keys, values and counts to the host: a job
ends when the user holds the answer.  The window keeps the traffic's
``ahead_s`` seconds of jobs dispatched beyond the one it waits for, so
that a stall of the host leaves the chip fed.
"""

from __future__ import annotations

import time

import numpy as np

from bench import harness


class BatchJobs:
    run: harness.Run
    outs: list

    def call(self):
        raise NotImplementedError

    def plan(self) -> str:
        return " | ".join(ln for ln in self.mr.explain().splitlines()
                          if ln.startswith(("flow:", "tiling:")))

    def start(self):
        with self.run.spans("bench.job.call"):
            return self.call()

    def finish(self, res):
        with self.run.spans("bench.job.fetch"):
            return harness.fetch(res)

    def job(self):
        return self.finish(self.start())

    def warm_up(self):
        """One job in set-up: the executable's first run, and the job time
        that ``ahead`` is counted in."""
        t = time.perf_counter()
        self.outs = [self.job()]
        self.warm_s = time.perf_counter() - t

    def ahead(self) -> int:
        """Jobs kept dispatched beyond the one waited for: the traffic's
        ``ahead_s`` seconds of them (none where it gives none)."""
        ahead_s = float(self.run.traffic.get("ahead_s", 0))
        if ahead_s <= 0:
            return 0
        return max(1, round(ahead_s / self.warm_s))

    def window(self, seconds: float) -> dict:
        ahead = self.ahead()
        window_s, outs = harness.run_jobs(self.start, self.finish, seconds,
                                          ahead)
        harness.log(f"window: {len(outs)} jobs in {window_s:.4f}s, "
                    f"{ahead} dispatched ahead")
        self.outs += outs
        return {"job_s": window_s / len(outs)}

    def traced(self) -> dict:
        n = int(self.run.traffic.get("trace_jobs", 1))
        self.outs += [self.job() for _ in range(n)]
        calls = self.run.spans.durations("bench.job.call")[-n:]
        fetches = self.run.spans.durations("bench.job.fetch")[-n:]
        return {"jobs": n,
                "job_s": [c + f for c, f in zip(calls, fetches)],
                "bytes": self.groupby_bytes()}

    def groupby_bytes(self) -> int:
        """The least a group-by moves: read the items (every column) once
        and write the result table (keys, every value column, counts)
        once."""
        import jax

        K = self.run.app_mod.key_space(self.run.cfg)
        row = sum(a.dtype.itemsize * (a.size // max(a.shape[0], 1))
                  for a in jax.tree_util.tree_leaves(self.outs[-1]))
        return (sum(int(a.nbytes)
                    for a in jax.tree_util.tree_leaves(self.items))
                + row * K)

    def answers(self):
        import jax

        items = jax.tree_util.tree_map(np.asarray, self.items)
        del self.items, self.mr
        expected = self.run.expected(items)
        return ([(f"job {i}", *o) for i, o in enumerate(self.outs)],
                [expected] * len(self.outs))
