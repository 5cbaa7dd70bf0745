"""Program spans (``repro.core.trace``): silent without a profiler; under
one, the span tree of the entry points and the service with parents, rids
and per-span trace counts, on the profiler's own clock; named device
scopes in the compiled programs' ``op_name`` metadata; a bounded buffer."""

import os
import re
import sys
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import MapReduce, make_app, trace
from repro.streaming import sliding

V = 64
B = 64


def wc_app():
    return make_app(
        map_fn=lambda item, emit: emit(item % V, jnp.ones((), jnp.int32)),
        reduce_fn=lambda k, vs, n: vs.sum(),
        key_space=V,
        value_aval=jax.ShapeDtypeStruct((), jnp.int32),
        emit_capacity=1,
    )


@pytest.fixture
def clean():
    trace.clear()
    yield
    trace.clear()


def batches(n):
    rng = np.random.default_rng(0)
    return [jnp.asarray(rng.integers(0, 4 * V, B).astype(np.int32))
            for _ in range(n)]


class Listener:
    """The test's own count of jaxpr traces, while ``on``."""

    def __init__(self):
        self.on = False
        self.traces = 0
        jax.monitoring.register_event_duration_secs_listener(self)

    def __call__(self, event, duration, **_):
        if self.on and event == trace.TRACE_EVENT:
            self.traces += 1

    def close(self):
        jax.monitoring.unregister_event_duration_listener(self)


def drive(mr, svc, bs, listener=None):
    """A stream job, then ingests with a snapshot after every 4th; returns
    the job's result, the batch ids and, per snapshot, the traces the
    test's listener saw during it."""
    res = mr.run(jnp.concatenate(bs))
    ids, snap_traces = [], []
    for b in bs:
        ids.append(svc.ingest(b))
        if ids[-1] % 4 == 0:
            if listener is not None:
                before = listener.traces
                listener.on = True
            svc.snapshot()
            if listener is not None:
                listener.on = False
                snap_traces.append(listener.traces - before)
    return res, ids, snap_traces


def test_no_profiler_records_nothing(clean):
    mr = MapReduce(wc_app(), flow="stream")
    svc = MapReduce(wc_app(), streaming=True).serve(
        batch_capacity=B, window=sliding(4, 2))
    drive(mr, svc, batches(8))
    assert trace.records() == []


def test_profiled_calls_record_the_span_tree(clean, tmp_path):
    mr = MapReduce(wc_app(), flow="stream")
    svc = MapReduce(wc_app(), streaming=True).serve(
        batch_capacity=B, window=sliding(4, 2))
    bs = batches(8)
    drive(mr, svc, bs)  # compile everything outside the profiled stretch
    listener = Listener()
    try:
        with jax.profiler.trace(str(tmp_path)):
            _, ids, snap_traces = drive(mr, svc, bs, listener)
    finally:
        listener.close()
    recs = trace.records()
    by_index = {r.index: r for r in recs}
    assert len(by_index) == len(recs)
    for r in recs:
        assert r.start_ns <= r.end_ns
        if r.parent != -1:
            outer = by_index[r.parent]
            assert outer.start_ns <= r.start_ns <= r.end_ns <= outer.end_ns

    (run,) = [r for r in recs if r.name == trace.RUN]
    assert run.parent == -1 and run.rid is not None
    (disp,) = [r for r in recs if r.parent == run.index]
    assert disp.name == trace.DISPATCH and disp.rid == run.rid

    ingests = [r for r in recs if r.name == trace.INGEST]
    assert [r.rid for r in ingests] == ids
    for r in ingests:
        kids = [k for k in recs if k.parent == r.index]
        names = [k.name for k in kids]
        assert names.count(trace.DISPATCH) == 1
        # a slot is re-seeded at the first batch of every slide period
        assert (trace.SEED in names) == ((r.rid - 1) % 2 == 0)
        assert all(k.rid == r.rid for k in kids)
        assert r.traces >= sum(k.traces for k in kids)

    snaps = [r for r in recs if r.name == trace.SNAPSHOT]
    assert [r.rid for r in snaps] == [i for i in ids if i % 4 == 0]
    assert [r.traces for r in snaps] == snap_traces
    for r in snaps:
        kids = [k.name for k in recs if k.parent == r.index]
        assert kids == [trace.MERGE]  # sliding(4, 2) keeps 2 live slots


def test_span_counts_traces_and_compiles_on_its_thread(clean, tmp_path):
    listener = Listener()
    try:
        with jax.profiler.trace(str(tmp_path)):
            with trace.span("test.outer", rid=7):
                listener.on = True
                with trace.span("test.inner"):
                    jax.jit(lambda x: x * 3 + 1)(jnp.ones(5))
                listener.on = False
    finally:
        listener.close()
    inner, outer = trace.records()
    assert inner.traces == listener.traces >= 1
    assert inner.compiles >= 1
    assert (outer.traces, outer.compiles) == (inner.traces, inner.compiles)
    assert inner.parent == outer.index and inner.rid == outer.rid == 7


def test_spans_nest_per_thread(clean, tmp_path):
    go = threading.Barrier(4)

    def worker(k):
        with trace.span("test.outer", rid=k):
            go.wait(timeout=30)
            for _ in range(50):
                with trace.span("test.inner"):
                    pass

    with jax.profiler.trace(str(tmp_path)):
        threads = [threading.Thread(target=worker, args=(k,))
                   for k in range(4)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
    assert not any(t.is_alive() for t in threads)
    recs = trace.records()
    outers = {r.index: r for r in recs if r.name == "test.outer"}
    assert sorted(r.rid for r in outers.values()) == [0, 1, 2, 3]
    inners = [r for r in recs if r.name == "test.inner"]
    assert len(inners) == 200
    for r in inners:
        assert outers[r.parent].rid == r.rid


def test_spans_share_the_profilers_clock(clean, tmp_path):
    svc = MapReduce(wc_app(), streaming=True).serve(
        batch_capacity=B, window=sliding(4, 2))
    bs = batches(4)
    for b in bs:
        svc.ingest(b)
    with jax.profiler.trace(str(tmp_path)):
        with jax.profiler.TraceAnnotation("test.enclosing"):
            for b in bs:
                svc.ingest(b)
            svc.snapshot()
    names = {r.name for r in trace.records()}
    assert names == {trace.INGEST, trace.SEED, trace.DISPATCH,
                     trace.SNAPSHOT, trace.MERGE}

    (path,) = list(tmp_path.rglob("*.xplane.pb"))
    host = []
    for plane in jax.profiler.ProfileData.from_file(str(path)).planes:
        if plane.name.startswith("/host"):
            for line in plane.lines:
                host += [(line.name, ev.name, ev.start_ns,
                          ev.start_ns + ev.duration_ns)
                         for ev in line.events]
    (enc,) = [h for h in host if h[1] == "test.enclosing"]
    spans = [h for h in host if h[1].startswith("mr.")]
    assert {h[1] for h in spans} == names
    assert len(spans) == len(trace.records())
    for line, _, start, end in spans:
        assert line == enc[0] and enc[2] <= start <= end <= enc[3]


def _scopes(hlo_text: str) -> set:
    return set(re.findall(r"mr\.[a-z_]+", hlo_text))


@pytest.mark.parametrize("flow,want", [
    ("stream", {trace.MAP, trace.FOLD, trace.FINALIZE}),
    ("sort", {trace.MAP, trace.PARTITION, trace.SEGMENT_REDUCE,
              trace.FINALIZE}),
])
def test_named_scopes_reach_the_compiled_program(flow, want):
    items = jnp.arange(4 * B, dtype=jnp.int32)
    compiled = MapReduce(wc_app(), flow=flow).lower(items).compile()
    assert want <= _scopes(compiled.as_text())


def test_named_scopes_reach_the_ingest_program():
    svc = MapReduce(wc_app(), streaming=True).serve(batch_capacity=B)
    svc.ingest(batches(1)[0])
    assert {trace.MAP, trace.FOLD} <= _scopes(svc._compiled.as_text())


def test_distributed_spans_and_scopes_on_four_devices():
    sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..",
                                    "integration"))
    from _subproc import run_with_devices

    out = run_with_devices("""
        import json, re, tempfile
        import jax, jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.core import (ExecutionOptions, MapReduce, ShuffleOptions,
                                make_app, trace)

        app = make_app(
            map_fn=lambda item, emit: emit(item % 64, jnp.ones((), jnp.int32)),
            reduce_fn=lambda k, vs, n: vs.sum(), key_space=64,
            value_aval=jax.ShapeDtypeStruct((), jnp.int32), emit_capacity=1)
        mesh = jax.make_mesh((4,), ("data",))
        items = jax.device_put(jnp.arange(1024, dtype=jnp.int32),
                               NamedSharding(mesh, P("data")))
        opts = ExecutionOptions(mesh=mesh, shuffle=ShuffleOptions(
            capacity=256, strict=True))
        mr = MapReduce(app, flow="sort")
        mr.run_distributed(items, options=opts)
        with jax.profiler.trace(tempfile.mkdtemp()):
            res = mr.run_distributed(items, options=opts)
        assert int(res.counts.sum()) == 1024
        jitted = mr.lower(items, options=opts).compile()._entry.executable
        text = jitted.lower(items).compile().as_text()
        print(json.dumps({"records": [r._asdict() for r in trace.records()],
                          "scopes": sorted(set(re.findall(r"mr\\.[a-z_]+",
                                                          text)))}))
    """)
    got = __import__("json").loads(out.strip().splitlines()[-1])
    recs = got["records"]
    (run,) = [r for r in recs if r["name"] == trace.RUN_DISTRIBUTED]
    kids = [r["name"] for r in recs if r["parent"] == run["index"]]
    assert kids == [trace.DISPATCH, trace.SYNC, trace.POST]
    assert {r["rid"] for r in recs} == {run["rid"]}
    assert {trace.SHUFFLE, trace.SEGMENT_REDUCE, trace.MAP} <= set(
        got["scopes"])


def test_buffer_stays_bounded(clean, tmp_path):
    extra = 100
    with jax.profiler.trace(str(tmp_path)):
        for _ in range(trace.BUFFER_RECORDS + extra):
            with trace.span("test.tick"):
                pass
    recs = trace.records()
    assert len(recs) == trace.BUFFER_RECORDS
    first = recs[0].index
    assert [r.index for r in recs] == list(
        range(first, first + trace.BUFFER_RECORDS))
