"""MapReduceService: incremental-fold parity (N ingests ≡ one batch run,
bitwise), zero re-trace/re-tune/re-compile steady state, window expiry,
snapshot-under-ingestion consistency, and checkpointed warm restarts."""

import tempfile
import time
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (
    ExecutionOptions,
    MapReduce,
    MapReduceResult,
    make_app,
)
from repro.core import plan_cache as pc
from repro.core.plan import plan_execution
from repro.streaming import IngestionQueue, sliding, tumbling

I32 = jnp.int32
F32 = jnp.float32
VOCAB = 64
B = 64  # micro-batch capacity used throughout


def kv_app(reduce_fn, value_aval):
    """(keys, values) item pairs -> reduce over values per key."""
    return make_app(
        map_fn=lambda item, emit: emit(item[0], item[1]),
        reduce_fn=reduce_fn,
        key_space=VOCAB,
        value_aval=value_aval,
        emit_capacity=1,
    )


def wc_app():
    """Scalar token items -> (token, 1) word count."""
    return make_app(
        map_fn=lambda item, emit: emit(item % VOCAB, jnp.ones((), I32)),
        reduce_fn=lambda k, vs, n: vs.sum(),
        key_space=VOCAB,
        value_aval=jax.ShapeDtypeStruct((), I32),
        emit_capacity=1,
    )


def kv_batches(rng, n_batches, *, dtype=np.float32, width=()):
    out = []
    for _ in range(n_batches):
        keys = rng.integers(0, VOCAB, size=B).astype(np.int32)
        if np.issubdtype(dtype, np.integer):
            vals = rng.integers(-50, 50, size=(B,) + width).astype(dtype)
        else:
            vals = rng.standard_normal((B,) + width).astype(dtype)
        out.append((jnp.asarray(keys), jnp.asarray(vals)))
    return out


def concat(batches):
    return jax.tree.map(lambda *xs: jnp.concatenate(xs), *batches)


def batch_reference(app, batches):
    """One batch run over the concatenated items with the chunk boundary
    aligned to the micro-batch size — the bitwise reference.  flow is
    forced so the reference stays on the stream fold under every
    REPRO_TEST_FLOW matrix leg (the service side is pinned by design)."""
    cap = max(app.emit_capacity, 1)
    mr = MapReduce(app, flow="stream")
    return mr.run(concat(batches),
                  options=ExecutionOptions(chunk_pairs=B * cap))


def count_of(res, key):
    """Per-key count lookup that doesn't assume identity key order."""
    keys = np.asarray(res.keys)
    counts = np.asarray(res.counts)
    (idx,) = np.nonzero(keys == key)
    return int(counts[idx[0]]) if idx.size else 0


# ---------------------------------------------------------------------------
# Incremental-fold parity across the derivable spec matrix
# ---------------------------------------------------------------------------

SPECS = {
    "sum_i32": (lambda k, v, c: jnp.sum(v),
                jax.ShapeDtypeStruct((), I32), np.int32, ()),
    "sum_f32": (lambda k, v, c: jnp.sum(v),
                jax.ShapeDtypeStruct((), F32), np.float32, ()),
    "max_f32": (lambda k, v, c: jnp.max(v),
                jax.ShapeDtypeStruct((), F32), np.float32, ()),
    "mean_f32": (lambda k, v, c: jnp.sum(v) / jnp.maximum(c, 1).astype(F32),
                 jax.ShapeDtypeStruct((), F32), np.float32, ()),
    "count": (lambda k, v, c: c,
              jax.ShapeDtypeStruct((), I32), np.int32, ()),
    "vecsum_f32": (lambda k, v, c: jnp.sum(v, axis=0),
                   jax.ShapeDtypeStruct((4,), F32), np.float32, (4,)),
    # wide jobs: int64 values (the map widens the int32 items)
    "count_i64": (lambda k, v, c: c,
                  jax.ShapeDtypeStruct((), jnp.int64), np.int32, ()),
    "sum_i64": (lambda k, v, c: jnp.sum(v * (1 << 40)),
                jax.ShapeDtypeStruct((), jnp.int64), np.int32, ()),
}


@pytest.mark.parametrize("name", sorted(SPECS))
def test_incremental_parity_bitwise(name):
    """N sequential ingests == one batch run over the concatenation,
    bitwise, for every derivable combiner strategy."""
    reduce_fn, aval, dtype, width = SPECS[name]
    rng = np.random.default_rng(1 + sorted(SPECS).index(name))
    batches = kv_batches(rng, 6, dtype=dtype, width=width)

    svc = MapReduce(kv_app(reduce_fn, aval),
                    streaming=True).serve(batch_capacity=B)
    for b in batches:
        svc.ingest(b)
    got = svc.snapshot()

    want = batch_reference(kv_app(reduce_fn, aval), batches)
    np.testing.assert_array_equal(np.asarray(want.keys),
                                  np.asarray(got.keys))
    np.testing.assert_array_equal(np.asarray(want.values),
                                  np.asarray(got.values))
    np.testing.assert_array_equal(np.asarray(want.counts),
                                  np.asarray(got.counts))


def test_partial_batches_exact():
    """Micro-batches below capacity are padded + masked: the pad rows
    contribute exactly nothing (parity against the unpadded run)."""
    rng = np.random.default_rng(5)
    svc = MapReduce(wc_app(), streaming=True).serve(batch_capacity=B)
    sizes = [B, 7, 1, 33, B, 12]
    chunks = [jnp.asarray(rng.integers(0, VOCAB, size=s), dtype=np.int32)
              for s in sizes]
    for c in chunks:
        svc.ingest(c)
    got = svc.snapshot()
    assert got.batch_id == len(sizes)

    want = MapReduce(wc_app(), flow="stream").run(jnp.concatenate(chunks))
    np.testing.assert_array_equal(np.asarray(want.values),
                                  np.asarray(got.values))
    np.testing.assert_array_equal(np.asarray(want.counts),
                                  np.asarray(got.counts))


def test_oversized_batch_rejected():
    svc = MapReduce(wc_app(), streaming=True).serve(batch_capacity=8)
    with pytest.raises(ValueError, match="batch_capacity"):
        svc.ingest(jnp.zeros((9,), I32))


# ---------------------------------------------------------------------------
# Zero re-trace / re-tune / re-compile steady state
# ---------------------------------------------------------------------------


def test_zero_retrace_across_100_ingests():
    """After the first ingest stages the executable, 100 more ingests (of
    varying sizes — one executable serves them all) run zero optimizer
    derives, zero autotunes, zero probes and zero staged compiles."""
    rng = np.random.default_rng(7)
    svc = MapReduce(wc_app(), streaming=True).serve(batch_capacity=32)
    svc.ingest(jnp.asarray(rng.integers(0, VOCAB, size=32), dtype=np.int32))

    s0 = pc.stats_snapshot()
    for i in range(100):
        n = 32 if i % 3 else 11
        svc.ingest(jnp.asarray(rng.integers(0, VOCAB, size=n),
                               dtype=np.int32))
        if i % 25 == 0:
            svc.snapshot()  # queries must not re-stage anything either
    s1 = pc.stats_snapshot()
    for counter in ("derives", "autotunes", "probes", "compiles"):
        assert s1[counter] == s0[counter], (counter, s0, s1)
    assert svc.batch_id == 101


def test_second_service_hits_compiled_cache():
    """A second service over a content-identical app re-uses the staged
    executable: zero compiles end to end (the plan cache's serving win)."""
    rng = np.random.default_rng(8)
    items = jnp.asarray(rng.integers(0, VOCAB, size=B), dtype=np.int32)
    MapReduce(wc_app(), streaming=True).serve(batch_capacity=B).ingest(items)
    s0 = pc.stats_snapshot()
    svc2 = MapReduce(wc_app(), streaming=True).serve(batch_capacity=B)
    svc2.ingest(items)
    s1 = pc.stats_snapshot()
    assert s1["compiles"] == s0["compiles"], (s0, s1)
    assert "compiled-cache: hit" in svc2.explain()


# ---------------------------------------------------------------------------
# Windowed aggregation: coverage + expiry, exact by construction
# ---------------------------------------------------------------------------


def sum_app():
    return kv_app(lambda k, v, c: jnp.sum(v), jax.ShapeDtypeStruct((), I32))


def test_tumbling_window_covers_current_period_only():
    rng = np.random.default_rng(11)
    batches = kv_batches(rng, 10, dtype=np.int32)
    svc = MapReduce(sum_app(), streaming=True).serve(batch_capacity=B,
                                                     window=tumbling(2))
    for b in batches:
        svc.ingest(b)
    got = svc.snapshot()
    # 10 batches, size-2 tumbling: the live window is batches 8..9
    want = batch_reference(sum_app(), batches[8:10])
    np.testing.assert_array_equal(np.asarray(want.values),
                                  np.asarray(got.values))
    np.testing.assert_array_equal(np.asarray(want.counts),
                                  np.asarray(got.counts))


def test_sliding_window_merges_live_slots():
    rng = np.random.default_rng(12)
    batches = kv_batches(rng, 9, dtype=np.int32)
    svc = MapReduce(sum_app(), streaming=True).serve(batch_capacity=B,
                                                     window=sliding(4, 2))
    for b in batches:
        svc.ingest(b)
    got = svc.snapshot()
    # 9 batches, size-4/slide-2 ring: the live slots hold the last full
    # slide period {6,7} plus the in-progress one {8}
    want = batch_reference(sum_app(), batches[6:9])
    np.testing.assert_array_equal(np.asarray(want.values),
                                  np.asarray(got.values))
    np.testing.assert_array_equal(np.asarray(want.counts),
                                  np.asarray(got.counts))


def test_window_expiry_drops_old_keys():
    """Keys seen only in expired batches disappear from snapshots — the
    ring-slot overwrite IS the TTL."""
    svc = MapReduce(wc_app(), streaming=True).serve(batch_capacity=B,
                                                    window=tumbling(2))
    hot = jnp.full((B,), 3, dtype=I32)
    cold = jnp.full((B,), 40, dtype=I32)
    svc.ingest(hot)
    svc.ingest(hot)
    assert count_of(svc.snapshot(), 3) == 2 * B
    svc.ingest(cold)  # new period: the hot batches expire
    snap = svc.snapshot()
    assert count_of(snap, 3) == 0
    assert count_of(snap, 40) == B


def test_window_invalid_config():
    with pytest.raises(ValueError, match="multiple of slide"):
        sliding(5, 2)
    with pytest.raises(ValueError, match="positive"):
        tumbling(0)


# ---------------------------------------------------------------------------
# Snapshot-under-ingestion consistency
# ---------------------------------------------------------------------------


def test_snapshot_consistent_under_concurrent_ingestion():
    """Snapshots taken while a background IngestionQueue folds batches
    always see a whole number of batches: every batch contributes exactly
    B pairs, so a torn/partially-applied view would break
    counts.sum() == batch_id * B."""
    rng = np.random.default_rng(13)
    svc = MapReduce(wc_app(), streaming=True).serve(batch_capacity=B)
    q = IngestionQueue(svc, maxsize=4)
    n_batches = 30
    deadline = time.monotonic() + 120.0
    for _ in range(n_batches):
        q.put(jnp.asarray(rng.integers(0, VOCAB, size=B), dtype=np.int32),
              timeout=120.0)

    seen = []
    while svc.batch_id < n_batches and time.monotonic() < deadline:
        if svc.batch_id == 0:
            time.sleep(0.001)  # not staged yet: first ingest in flight
            continue
        snap = svc.snapshot()
        total = int(np.asarray(snap.counts).sum())
        assert total == snap.batch_id * B, (total, snap.batch_id)
        seen.append(snap.batch_id)
    q.close()
    assert seen == sorted(seen)  # monotone generations
    final = svc.snapshot()
    assert final.batch_id == n_batches
    assert int(np.asarray(final.counts).sum()) == n_batches * B


def test_ingestion_queue_surfaces_worker_errors():
    svc = MapReduce(wc_app(), streaming=True).serve(batch_capacity=4)
    q = IngestionQueue(svc, maxsize=2)
    q.put(jnp.zeros((16,), I32))  # oversized: worker raises
    with pytest.raises(ValueError, match="batch_capacity"):
        q.join()
    q.close()


def test_ingestion_queue_quarantines_poison_batch():
    """A poison batch is quarantined with its sequence number; the worker
    keeps consuming and the service keeps folding + serving snapshots."""
    svc = MapReduce(wc_app(), streaming=True).serve(batch_capacity=8)
    q = IngestionQueue(svc, maxsize=4)
    q.put(jnp.zeros((8,), I32))        # seq 1: fine
    q.put(jnp.zeros((16,), I32))       # seq 2: poison (oversized)
    q.put(jnp.full((8,), 5, I32))      # seq 3: still folded after poison
    with pytest.raises(ValueError, match="batch_capacity"):
        q.join()
    q.close()
    assert [p.seq for p in q.quarantined] == [2]
    assert "batch_capacity" in str(q.quarantined[0].error)
    snap = svc.snapshot()
    assert snap.batch_id == 2        # batches 1 and 3 both landed
    assert count_of(snap, 5) == 8
    assert not svc.failed            # poison != service failure


def test_ingestion_queue_worker_death_unstrands_producers():
    """Regression: a fatal (non-batch) worker death used to kill the
    thread silently — producers then blocked forever on a full queue and
    close() hung.  Now the death surfaces as WorkerDiedError on the next
    put()/close(), and the service is marked failed."""
    from repro.streaming import ServiceFailedError, WorkerDiedError

    class Dying:
        batch_id = 0

        def __init__(self):
            self.failure = None

        def ingest(self, items):
            raise KeyboardInterrupt("simulated fatal worker death")

        def fail(self, exc):
            self.failure = exc

    svc = Dying()
    q = IngestionQueue(svc, maxsize=1)
    q.put(jnp.zeros((4,), I32))  # worker dies processing this
    with pytest.raises(WorkerDiedError):
        deadline = time.monotonic() + 30.0
        while time.monotonic() < deadline:  # death is asynchronous
            q.put(jnp.zeros((4,), I32), timeout=5.0)
    with pytest.raises(WorkerDiedError):
        q.close()
    assert isinstance(svc.failure, KeyboardInterrupt)

    # a real service marked failed: ingest raises, snapshots keep serving
    real = MapReduce(wc_app(), streaming=True).serve(batch_capacity=8)
    real.ingest(jnp.full((8,), 7, I32))
    real.fail(RuntimeError("ingestion worker died"))
    assert real.failed
    with pytest.raises(ServiceFailedError, match="worker died"):
        real.ingest(jnp.zeros((8,), I32))
    snap = real.snapshot()  # reads stay up for the last good state
    assert snap.batch_id == 1 and count_of(snap, 7) == 8
    assert "FAILED" in real.explain()


# ---------------------------------------------------------------------------
# Checkpointed warm restart
# ---------------------------------------------------------------------------


def test_restore_resumes_bitwise():
    rng = np.random.default_rng(17)
    batches = kv_batches(rng, 12, dtype=np.int32)

    def build(d):
        return MapReduce(sum_app(), streaming=True).serve(
            batch_capacity=B, window=sliding(4, 2), ckpt_dir=d,
            ckpt_every=4,
            item_spec=(jax.ShapeDtypeStruct((), I32),
                       jax.ShapeDtypeStruct((), I32)))

    with tempfile.TemporaryDirectory() as d:
        svc = build(d)
        for b in batches:
            svc.ingest(b)
        want = svc.snapshot()

        # "crash" after batch 8's checkpoint: a fresh service restores it
        # and replays 8..12 — bitwise the unfailed run
        svc2 = build(d)
        assert svc2.restore(step=8) == 8
        assert svc2.batch_id == 8
        for b in batches[8:]:
            svc2.ingest(b)
        got = svc2.snapshot()
        np.testing.assert_array_equal(np.asarray(want.values),
                                      np.asarray(got.values))
        np.testing.assert_array_equal(np.asarray(want.counts),
                                      np.asarray(got.counts))

        # restoring the newest checkpoint reproduces the final tables
        # directly (no replay)
        svc3 = build(d)
        assert svc3.restore() == 12
        got3 = svc3.snapshot()
        np.testing.assert_array_equal(np.asarray(want.values),
                                      np.asarray(got3.values))


def test_restore_requires_staging():
    with tempfile.TemporaryDirectory() as d:
        svc = MapReduce(wc_app(), streaming=True).serve(
            batch_capacity=B, ckpt_dir=d, ckpt_every=1)
        with pytest.raises(RuntimeError, match="item_spec"):
            svc.restore()


# ---------------------------------------------------------------------------
# Staging guards + the unified result/explain surface
# ---------------------------------------------------------------------------


def test_streaming_pins_stream_flow():
    with pytest.raises(ValueError, match="stream"):
        MapReduce(wc_app(), streaming=True, flow="sort")
    with pytest.raises(ValueError, match="stream"):
        plan_execution(wc_app(), streaming=True, flow="reduce")
    # a non-derivable reducer (order-dependent) cannot stream at all
    bad = make_app(
        map_fn=lambda item, emit: emit(item % 8, item.astype(F32)),
        reduce_fn=lambda k, vs, n: vs[0] - vs[-1],
        key_space=8,
        value_aval=jax.ShapeDtypeStruct((), F32),
        emit_capacity=1,
    )
    with pytest.raises(ValueError, match="derivation failed"):
        MapReduce(bad, streaming=True)


def test_service_rejects_non_stream_plan():
    from repro.streaming import MapReduceService

    mr = MapReduce(wc_app(), flow="combine")
    with pytest.raises(ValueError, match="stream"):
        MapReduceService(mr, batch_capacity=B)


def test_snapshot_returns_mapreduce_result():
    svc = MapReduce(wc_app(), streaming=True).serve(batch_capacity=B)
    svc.ingest(jnp.zeros((B,), I32))
    res = svc.snapshot()
    assert isinstance(res, MapReduceResult)
    assert res.plan is not None and res.plan.flow == "stream"
    assert isinstance(res.diagnostics, tuple)
    assert res.batch_id == 1
    with pytest.warns(DeprecationWarning, match="named fields"):
        keys, values, counts = res
    np.testing.assert_array_equal(np.asarray(keys), np.asarray(res.keys))


def test_explain_reports_service_surface():
    with tempfile.TemporaryDirectory() as d:
        svc = MapReduce(wc_app(), streaming=True).serve(
            batch_capacity=B, window=sliding(6, 3), ckpt_dir=d,
            ckpt_every=5)
        svc.ingest(jnp.zeros((B,), I32))
        text = svc.explain()
        assert "mode: streaming" in text
        assert "plan-cache:" in text
        assert "compiled-cache:" in text  # provenance: hit/miss + key
        assert "window: sliding size=6 slide=3" in text
        assert "residency: holder tables" in text
        assert "every 5 batches" in text
        assert f"batch_capacity={B}" in text


def test_streaming_compiled_rejects_batch_call():
    svc = MapReduce(wc_app(), streaming=True).serve(batch_capacity=B)
    svc.ingest(jnp.zeros((B,), I32))
    with pytest.raises(TypeError, match="MapReduceService"):
        svc._compiled(jnp.zeros((B,), I32))


def test_unwindowed_snapshot_before_ingest_is_empty():
    svc = MapReduce(wc_app(), streaming=True).serve(
        batch_capacity=B, item_spec=jax.ShapeDtypeStruct((), I32))
    res = svc.snapshot()
    assert res.batch_id == 0
    assert int(np.asarray(res.counts).sum()) == 0


def test_field_access_emits_no_deprecation():
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        svc = MapReduce(wc_app(), streaming=True).serve(batch_capacity=B)
        svc.ingest(jnp.zeros((B,), I32))
        res = svc.snapshot()
        res.keys, res.values, res.counts  # noqa: B018 — named-field access
    assert not [w for w in caught
                if issubclass(w.category, DeprecationWarning)
                and "named fields" in str(w.message)]
