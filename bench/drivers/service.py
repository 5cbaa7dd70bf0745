"""Streaming ingest: ``MapReduce(app, streaming=True).serve(...)``.

One producer drains a backlog in a closed loop: it calls ``ingest()`` with
the next micro-batch as soon as the previous call returns, and after every
``snapshot_every``-th ingest takes a ``snapshot()`` and copies its keys,
values and counts to the host.  Micro-batches are cut in turn from the
device-resident input during set-up (one jitted call), so the loop moves
no data from the host.

- ``ingest_rate``: pairs ingested in the window / the window's length; the
  window closes when the first snapshot ready after ``seconds`` is on the
  host, so every ingest counted has been folded.
- ``fresh_p95_ms``: for every micro-batch of the window, the time from its
  ``ingest()`` call until the first snapshot taken after it is on the
  host; the 95th percentile over all of them.
"""

from __future__ import annotations

import time

import numpy as np

from bench import harness
from bench.reference import windows as ref_windows


class Driver:
    def __init__(self, run: harness.Run):
        import jax

        from repro.core import MapReduce
        from repro.streaming import sliding

        tr = run.traffic
        self.run = run
        self.batch = int(tr["batch_items"])
        self.every = int(tr["snapshot_every"])
        items = run.make_items()
        n_batches = items.shape[0] // self.batch
        split = jax.jit(lambda a: tuple(
            a[: n_batches * self.batch].reshape(
                (n_batches, self.batch) + a.shape[1:])[i]
            for i in range(n_batches)))
        self.pool = jax.block_until_ready(split(items))
        self.items = items
        self.pairs_per_batch = (self.batch
                                * run.app_mod.pairs(run.cfg)
                                // items.shape[0])
        self.mr = MapReduce(run.app_mod.make_app(run.cfg), streaming=True)
        self.svc = self.mr.serve(
            batch_capacity=self.batch,
            window=sliding(int(tr["window_size"]), int(tr["window_slide"])))
        self.n = 0  # micro-batches ingested
        self.snaps: list = []  # (batches covered, keys, values, counts)
        self.dispatch: list = []
        warm = int(tr["warmup_batches"])
        if warm % self.every:
            raise ValueError("warmup_batches must be a multiple of "
                             "snapshot_every")
        self._loop(lambda: self.n >= warm)

    def _ingest(self):
        spans = self.run.spans
        t = time.perf_counter()
        with spans("bench.ingest"):
            self.svc.ingest(self.pool[self.n % len(self.pool)])
        self.dispatch.append(time.perf_counter() - t)
        self.n += 1
        return t

    def _snapshot(self):
        with self.run.spans("bench.snapshot"):
            out = harness.fetch(self.svc.snapshot())
        self.snaps.append((self.n, *out))
        return time.perf_counter()

    def _loop(self, done, fresh=None):
        calls = []
        while True:
            calls.append(self._ingest())
            if self.n % self.every == 0:
                ready = self._snapshot()
                if fresh is not None:
                    fresh += [ready - t for t in calls]
                calls = []
                if done():
                    return

    def window(self, seconds: float) -> dict:
        fresh: list = []
        n0 = self.n
        t0 = time.perf_counter()
        self._loop(lambda: time.perf_counter() - t0 >= seconds, fresh)
        window_s = time.perf_counter() - t0
        pairs = (self.n - n0) * self.pairs_per_batch
        slow = sorted((e - s, n, s - t0) for n, s, e in self.run.spans.records
                      if s >= t0)[-3:]
        harness.log("slowest calls in the window: " + ", ".join(
            f"{n} {1e3 * d:.1f}ms at +{at:.2f}s" for d, n, at in slow))
        return {"ingest_rate": pairs / window_s,
                "fresh_p95_ms": 1e3 * float(np.percentile(fresh, 95))}

    def traced(self) -> dict:
        n = int(self.run.traffic["trace_batches"])
        start = self.n
        self.dispatch = []
        self._loop(lambda: self.n - start >= n)
        return {"dispatch_s": list(self.dispatch)}

    def hlo_texts(self) -> list[str]:
        return [self.svc._compiled.as_text()]

    def answers(self):
        tr = self.run.traffic
        items = np.asarray(self.items)
        del self.items, self.pool, self.svc, self.mr
        per_batch: dict = {}
        n_pool = items.shape[0] // self.batch

        def batch_counts(b):
            i = b % n_pool
            if i not in per_batch:
                per_batch[i] = self.run.ref_mod.counts(
                    items[i * self.batch:(i + 1) * self.batch], self.run.cfg)
            return per_batch[i]

        answers, expected = [], []
        for n, *out in self.snaps:
            cover = ref_windows.covered(n, int(tr["window_size"]),
                                        int(tr["window_slide"]))
            expected.append(harness.count_table(
                sum(batch_counts(b) for b in cover)))
            answers.append((f"snapshot after {n} batches", *out))
        return answers, expected
