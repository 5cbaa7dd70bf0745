"""MapReduceService: a long-lived, continuously-ingesting MapReduce.

The batch engine answers "fold these N items"; the production posture for
millions of users is a service that absorbs micro-batches *forever* and
answers live queries.  The paper's semantic argument carries over intact:
the derived combiner is a monoid, so partial tables can be folded into and
merged at any time — merge-on-arrival is exact, not approximate.

Staging: the service compiles ONCE through the PR 6 staged path
(``lower().optimize().compile()`` at mode="streaming").  The compiled
artifact is a pure AOT ingest executable
``(state, padded_items, n_valid) -> state`` sized to ``batch_capacity``;
every ``ingest()`` thereafter is a plain dispatch — zero re-traces,
re-tunes and re-compiles, assertable via ``plan_cache.stats_snapshot()``.
Micro-batches smaller than the capacity are padded and masked (pad
emissions go to the sentinel key), so ONE executable serves every batch
size — the pow2-bucket serving idea taken to its streaming limit.

Consistency: the whole mutable service state lives in one immutable
:class:`_ServiceState` record behind a single reference.  ``ingest()``
builds a *new* record (JAX arrays are immutable — the old tables are
never written through) and swaps the reference; ``snapshot()`` reads the
reference once and works off that frozen view.  That is the
double-buffered table swap: snapshots are consistent without pausing
ingestion and without copying tables.

Width: a wide job (64-bit values, ``api.wide_job``) ingests, merges,
snapshots and restores in its 64-bit scope, as its batch runs do, so its
tables are never truncated to 32 bits.

Durability: every ``ckpt_every`` batches the slot states are snapshotted
atomically via ``checkpoint/ckpt.py`` (tmp + ``os.replace``), keyed by
the monotonically increasing batch id.  ``restore()`` reloads the newest
complete snapshot bitwise, so a restarted service continues exactly where
the checkpoint was cut — the same partial-aggregate argument that made
``run_resilient`` recovery exact.
"""

from __future__ import annotations

import dataclasses
import threading
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint import ckpt
from repro.core import engine as eng
from repro.core import plan_cache as pc
from repro.core import trace
from repro.core.api import ExecutionOptions, MapReduce, MapReduceResult
from repro.streaming.windows import Window


class ServiceFailedError(RuntimeError):
    """The service was marked failed (fatal ingestion-worker death or an
    explicit ``fail()``); ingestion is refused but ``snapshot()`` keeps
    serving the last consistent state — readers outlive a broken writer
    path, and a warm ``restore()`` clears the mark."""


@dataclasses.dataclass(frozen=True)
class _ServiceState:
    """One immutable generation of the service: swap-on-ingest."""

    slots: tuple  # per-window-slot carried combiner states
    batch_id: int  # micro-batches ingested so far (monotonic)
    n_items: int  # items ingested so far


class MapReduceService:
    """Continuous-ingestion MapReduce over a staged, compiled-once plan.

    Build via :meth:`MapReduce.serve`::

        mr = MapReduce(app, streaming=True)
        svc = mr.serve(batch_capacity=512, window=sliding(8, 2),
                       ckpt_dir="/ckpts", ckpt_every=16)
        svc.ingest(items)                # folds one micro-batch
        res = svc.snapshot()             # live MapReduceResult, no pause

    ``window=None`` aggregates globally (nothing ever expires); a
    :class:`~repro.streaming.Window` bounds results to the trailing
    micro-batches via ring-buffered per-slot tables (see windows.py).
    Windowed serving requires the derived combiner's partials to be
    mergeable (``derivation.mergeable_partials``) — the per-slot partials
    are merged at query time.
    """

    def __init__(self, mr: MapReduce, *, batch_capacity: int,
                 window: Window | None = None,
                 options: ExecutionOptions | None = None,
                 item_spec: Any = None,
                 ckpt_dir: str | None = None, ckpt_every: int = 0,
                 keep_ckpts: int = 3, retry_policy: Any = None):
        if batch_capacity <= 0:
            raise ValueError("batch_capacity must be positive")
        if mr.plan.flow != "stream":
            raise ValueError(
                f"MapReduceService needs the stream flow (micro-batches "
                f"fold into its carried holder tables); this plan chose "
                f"{mr.plan.flow!r} — construct MapReduce(app, "
                f"streaming=True)")
        d = mr.plan.derivation
        if (window is not None and d is not None
                and not d.mergeable_partials):
            raise ValueError(
                "windowed serving merges per-slot partial tables at query "
                "time, but this combiner's partials are not mergeable "
                f"({mr.plan.spec.describe}); use window=None (global "
                "aggregation) or a merge-capable reducer")
        self.mr = mr
        self.app = mr.app
        self.spec = mr.plan.spec
        self.batch_capacity = int(batch_capacity)
        self.window = window
        cap = max(self.app.emit_capacity, 1)
        opts = options if options is not None else ExecutionOptions()
        if opts.chunk_pairs is None:
            # one fold per ingest: the chunk is the micro-batch itself, so
            # N ingests replay exactly the chunk sequence of a batch run
            # with this chunk_pairs — the bitwise-parity alignment
            opts = dataclasses.replace(
                opts, chunk_pairs=self.batch_capacity * cap)
        self.options = opts
        self._ckpt_dir = (ckpt.service_state_dir(ckpt_dir)
                          if ckpt_dir is not None else None)
        self.ckpt_every = int(ckpt_every)
        self.keep_ckpts = int(keep_ckpts)
        self.retry_policy = retry_policy
        self._lock = threading.Lock()  # serializes writers, never readers
        self._compiled = None
        self._state: _ServiceState | None = None
        self._failed: BaseException | None = None
        #: control-plane event lines (retries/backoffs on checkpoint and
        #: restore, failure marks) — shown by explain(), mirrored onto
        #: the compiled plan's ``recovery`` diagnostics
        self.events: list[str] = []
        if item_spec is not None:
            self._compile(item_spec)

    # -- failure state ------------------------------------------------------

    def fail(self, exc: BaseException) -> None:
        """Mark the service failed (called by the ingestion front end on
        fatal worker death).  Ingestion is refused from here on;
        snapshots keep serving the last published state."""
        self._failed = exc
        self._record(f"service marked FAILED: {type(exc).__name__}: {exc}; "
                     f"snapshots still serve the last consistent state")

    @property
    def failed(self) -> BaseException | None:
        """The failure the service was marked with, or None."""
        return self._failed

    def _record(self, line: str) -> None:
        self.events.append(line)
        if self._compiled is not None:
            self._compiled.plan.recovery += (line,)

    def _retried(self, op: str, fn):
        if self.retry_policy is None:
            return fn()
        return self.retry_policy.call(fn, op=op, on_event=self._record)

    # -- staging ------------------------------------------------------------

    @property
    def n_slots(self) -> int:
        return self.window.n_slots if self.window is not None else 1

    def _compile(self, item_spec) -> None:
        """Stage and AOT-compile the ingest executable (once)."""
        batch_spec = jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(
                (self.batch_capacity,) + tuple(a.shape), a.dtype),
            pc.items_spec_of(item_spec))
        self._compiled = self.mr.lower(
            batch_spec, options=self.options, mode="streaming"
        ).optimize().compile()
        self._state = _ServiceState(
            slots=tuple(self._compiled.init_state()
                        for _ in range(self.n_slots)),
            batch_id=0, n_items=0)

    def _ensure_compiled(self, items) -> None:
        if self._compiled is None:
            self._compile(jax.tree.map(
                lambda a: jax.ShapeDtypeStruct(tuple(a.shape[1:]), a.dtype),
                items))

    # -- ingestion ----------------------------------------------------------

    def ingest(self, items) -> int:
        """Fold one micro-batch (≤ ``batch_capacity`` items) into the live
        tables; returns the batch id (1-based count of batches ingested).

        Thread-safe single-writer: concurrent callers serialize on the
        service lock; snapshots never wait on it."""
        with trace.span(trace.INGEST) as span, self.mr.width():
            return self._ingest(items, span)

    def _ingest(self, items, span: trace.span) -> int:
        if self._failed is not None:
            raise ServiceFailedError(
                f"service is marked failed "
                f"({type(self._failed).__name__}: {self._failed}); "
                f"snapshot() still serves, restore() a checkpoint to "
                f"resume ingestion") from self._failed
        items = jax.tree.map(jnp.asarray, items)
        n = int(jax.tree.leaves(items)[0].shape[0])
        if n > self.batch_capacity:
            raise ValueError(
                f"micro-batch of {n} items exceeds batch_capacity="
                f"{self.batch_capacity}; split it or raise the capacity")
        self._ensure_compiled(items)
        if n < self.batch_capacity:
            pad = self.batch_capacity - n
            items = jax.tree.map(
                lambda a: jnp.concatenate(
                    [a, jnp.zeros((pad,) + a.shape[1:], a.dtype)]), items)
        with self._lock:
            st = self._state
            b = st.batch_id  # 0-based id of the incoming batch
            slots = list(st.slots)
            span.rid = b + 1  # the id this call publishes
            if self.window is not None:
                i = self.window.slot_of(b)
                seed = slots[i]
                if b % self.window.slide == 0:
                    # first batch of a new slide period: re-initialize the
                    # slot, overwriting (expiring) the oldest period's
                    # tables
                    with trace.span(trace.SEED):
                        seed = self._compiled.init_state()
            else:
                i, seed = 0, slots[0]
            with trace.span(trace.DISPATCH):
                slots[i] = self._compiled.ingest_state(seed, items, n)
            new = _ServiceState(tuple(slots), b + 1, st.n_items + n)
            self._state = new  # atomic publish: snapshots see old or new
            if (self._ckpt_dir is not None and self.ckpt_every > 0
                    and new.batch_id % self.ckpt_every == 0):
                self._checkpoint(new)
        return new.batch_id

    # -- queries ------------------------------------------------------------

    def _live_slots(self, st: _ServiceState) -> list:
        """Live slot states, oldest period first (deterministic merge
        order — what makes restore-then-snapshot bitwise reproducible)."""
        if self.window is None or st.batch_id == 0:
            return [st.slots[0]] if self.window is None else []
        p = self.window.period_of(st.batch_id - 1)  # current period
        live = min(p + 1, self.window.n_slots)
        return [st.slots[(p - k) % self.window.n_slots]
                for k in range(live - 1, -1, -1)]

    def snapshot(self) -> MapReduceResult:
        """Consistent view of the live tables — ingestion is NOT paused.

        Reads the current state reference once (one immutable generation)
        and finalizes/merges off that view; a concurrent ingest publishes
        a new generation without disturbing this one."""
        if self._state is None:
            raise RuntimeError(
                "service not staged yet: ingest a first micro-batch or "
                "construct with item_spec=... to compile eagerly")
        st = self._state
        with trace.span(trace.SNAPSHOT, rid=st.batch_id), self.mr.width():
            states = self._live_slots(st)
            if len(states) > 1:
                with trace.span(trace.MERGE):
                    pairs = [self._compiled.state_tables(s) for s in states]
                    keys, values, counts = eng.merge_partial_tables(
                        self.app, self.spec,
                        [t for t, _ in pairs], [c for _, c in pairs])
            else:
                # one live slot, or a windowed service before any ingest
                state = states[0] if states else self._compiled.init_state()
                with trace.span(trace.FINALIZE):
                    g = self._compiled.finalize_state(state)
                keys, values, counts = g.keys, g.values, g.counts
            return MapReduceResult(keys, values, counts,
                                   plan=self._compiled.plan,
                                   batch_id=st.batch_id)

    @property
    def batch_id(self) -> int:
        """Micro-batches ingested so far."""
        return self._state.batch_id if self._state is not None else 0

    @property
    def n_items(self) -> int:
        """Items ingested so far."""
        return self._state.n_items if self._state is not None else 0

    # -- durability ---------------------------------------------------------

    def _state_tree(self, st: _ServiceState) -> dict:
        return {"slots": list(st.slots),
                "meta": np.asarray([st.batch_id, st.n_items], np.int64)}

    def _checkpoint(self, st: _ServiceState) -> None:
        self._retried(
            f"checkpoint batch {st.batch_id}",
            lambda: ckpt.save(self._ckpt_dir, st.batch_id,
                              self._state_tree(st), keep=self.keep_ckpts))

    def checkpoint(self) -> str:
        """Snapshot the current state to the checkpoint dir now (atomic);
        returns the written path."""
        if self._ckpt_dir is None:
            raise RuntimeError("service was built without ckpt_dir")
        if self._state is None:
            raise RuntimeError("nothing to checkpoint: service not staged")
        with self._lock:
            st = self._state
            return self._retried(
                f"checkpoint batch {st.batch_id}",
                lambda: ckpt.save(self._ckpt_dir, st.batch_id,
                                  self._state_tree(st),
                                  keep=self.keep_ckpts))

    def restore(self, ckpt_dir: str | None = None,
                *, step: int | None = None) -> int:
        """Warm restart: load the newest VALID checkpoint (or ``step``)
        and resume bitwise-identical to the service that wrote it.

        Integrity: every snapshot is checksummed (checkpoint/ckpt.py).
        With an explicit ``step``, a torn or corrupt snapshot raises
        :class:`~repro.checkpoint.ckpt.CheckpointCorruptError` naming the
        step and path (the artifact is quarantined to ``*.corrupt``).
        With ``step=None``, corrupt candidates are quarantined and
        skipped and the newest VALID snapshot is restored — a torn
        newest write degrades to the previous snapshot instead of
        crashing the restart.  ``retry_policy`` (if set) retries flaky
        store reads on its bounded deterministic backoff.

        The service must be staged first (construct with ``item_spec=``,
        or over the same app after one ingest) so the state structure is
        known.  A successful restore clears a ``failed`` mark.  Returns
        the restored batch id."""
        d = (ckpt.service_state_dir(ckpt_dir) if ckpt_dir is not None
             else self._ckpt_dir)
        if d is None:
            raise RuntimeError("no checkpoint dir: pass ckpt_dir=...")
        if self._compiled is None:
            raise RuntimeError(
                "service not staged: construct with item_spec=... so the "
                "carried-state structure is known before restore")
        example = self._state_tree(_ServiceState(
            slots=tuple(self._compiled.init_state()
                        for _ in range(self.n_slots)),
            batch_id=0, n_items=0))
        with self.mr.width():
            tree, step = self._retried(
                f"service restore from {d}",
                lambda: ckpt.restore(d, example, step=step))
        with self._lock:
            self._state = _ServiceState(
                slots=tuple(tree["slots"]),
                batch_id=int(tree["meta"][0]),
                n_items=int(tree["meta"][1]))
            if self._failed is not None:
                self._record(f"service failure mark cleared by restore of "
                             f"batch {step}")
                self._failed = None
        return step

    # -- introspection -------------------------------------------------------

    def explain(self) -> str:
        """The service's decision record, one format with the batch entry
        points: the compiled plan (flow, combiner, tiling, plan-cache and
        compiled-cache provenance), then the serving configuration —
        window, table residency (roofline model), checkpoint cadence."""
        from repro.roofline import analysis

        lines = []
        if self._compiled is not None:
            lines.append(self._compiled.explain())
        else:
            lines.append(self.mr.explain())
            lines.append("mode: streaming (not staged yet — no item spec)")
        cap = max(self.app.emit_capacity, 1)
        lines.append(
            f"service: batch_capacity={self.batch_capacity} items "
            f"({self.batch_capacity * cap} pairs/ingest), ingested "
            f"{self.batch_id} batches / {self.n_items} items")
        lines.append("window: "
                     + (self.window.describe() if self.window is not None
                        else "global (no expiry)"))
        K = self.app.key_space
        _, holder_bytes = self.spec.holder_width(self.app.value_aval)
        table_bytes = K * (holder_bytes + 4)  # + int32 counts
        value_bytes = int(jnp.dtype(self.app.value_aval.dtype).itemsize
                          * max(1, int(np.prod(self.app.value_aval.shape))))
        peak = analysis.mapreduce_flow_peak_bytes(
            "stream", n_pairs=self.batch_capacity * cap, key_space=K,
            value_bytes=value_bytes, holder_bytes=holder_bytes,
            chunk_pairs=self.options.chunk_pairs)
        lines.append(
            f"residency: holder tables {table_bytes:,} B/slot x "
            f"{self.n_slots} slot(s) = {table_bytes * self.n_slots:,} B "
            f"resident; ~{peak:,.0f} B peak per ingest (roofline stream "
            f"model, K={K})")
        if self._ckpt_dir is not None and self.ckpt_every > 0:
            last = ckpt.latest_step(self._ckpt_dir)
            lines.append(
                f"checkpoint: {self._ckpt_dir} every {self.ckpt_every} "
                f"batches (keep={self.keep_ckpts}, last="
                f"{'none' if last is None else f'batch {last}'})")
        else:
            lines.append("checkpoint: off")
        if self._failed is not None:
            lines.append(f"state: FAILED ({type(self._failed).__name__}: "
                         f"{self._failed}) — snapshots only")
        for ev in self.events:
            lines.append(f"event: {ev}")
        return "\n".join(lines)
