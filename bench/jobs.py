"""Whole-input jobs back to back: what the batch drivers share.

A subclass sets ``run``, ``items`` and ``mr`` and defines ``call()``, the
entry-point call that starts one job.  A job is that call plus copying
keys, values and counts to the host: a job ends when the user holds the
answer.
"""

from __future__ import annotations

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

    def job(self):
        spans = self.run.spans
        with spans("bench.job.call"):
            res = self.call()
        with spans("bench.job.fetch"):
            out = harness.fetch(res)
        return out

    def window(self, seconds: float) -> dict:
        window_s, outs = harness.run_jobs(self.job, seconds)
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
        """The least a group-by moves: read the items once and write the
        result table (keys, values, counts) once."""
        K = self.run.app_mod.key_space(self.run.cfg)
        row = sum(a.dtype.itemsize * (a.size // max(a.shape[0], 1))
                  for a in self.outs[-1])
        return int(self.items.nbytes) + row * K

    def answers(self):
        items = np.asarray(self.items)
        del self.items, self.mr
        expected = self.run.expected(items)
        return ([(f"job {i}", *o) for i, o in enumerate(self.outs)],
                [expected] * len(self.outs))
