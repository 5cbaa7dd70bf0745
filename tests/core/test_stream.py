"""Streaming map+combine fusion: parity with the legacy flows + the
bytes-pressure ordering the paper's Figs 8/9 claim (stream ≤ combine <
reduce on the WordCount system workload).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import MapReduce, MapReduceApp, make_app
from repro.core import combiner as C
from repro.roofline import hlo_parser

VOCAB = 512


class WordCount(MapReduceApp):
    key_space = VOCAB
    value_aval = jax.ShapeDtypeStruct((), jnp.int32)
    emit_capacity = 8
    max_values_per_key = 1024

    def map(self, window, emit):
        emit(window, jnp.ones_like(window))

    def reduce(self, key, values, count):
        return jnp.sum(values)


@pytest.fixture(scope="module")
def tokens():
    rng = np.random.default_rng(0)
    return rng.integers(0, VOCAB, size=(128, 8)).astype(np.int32)


# ---------------------------------------------------------------------------
# Parity: stream == combine == reduce on the canonical apps
# ---------------------------------------------------------------------------


def test_wordcount_three_flow_parity(tokens):
    want = np.bincount(tokens.reshape(-1), minlength=VOCAB)
    results = {
        flow: MapReduce(WordCount(), flow=flow).run(jnp.asarray(tokens))
        for flow in ("stream", "combine", "reduce")
    }
    for flow in ("stream", "combine"):
        np.testing.assert_array_equal(np.asarray(results[flow].values), want)
        np.testing.assert_array_equal(np.asarray(results[flow].counts), want)
    mask = want > 0
    np.testing.assert_array_equal(
        np.asarray(results["reduce"].values)[mask], want[mask])


def test_histogram_parity_multichunk():
    """Chunking engages (pairs >> chunk size); all flows agree."""
    rng = np.random.default_rng(1)
    px = rng.integers(0, 256, size=(4096, 3)).astype(np.int32)

    class Histogram(MapReduceApp):
        key_space = 768
        value_aval = jax.ShapeDtypeStruct((), jnp.int32)
        emit_capacity = 3
        max_values_per_key = 8192

        def map(self, pixel, emit):
            emit(jnp.arange(3, dtype=jnp.int32) * 256 + pixel,
                 jnp.ones((3,), jnp.int32))

        def reduce(self, key, values, count):
            return jnp.sum(values)

    want = np.bincount(
        (np.arange(3) * 256 + px).reshape(-1), minlength=768)
    mr = MapReduce(Histogram(), flow="stream", stream_chunk_pairs=1024)
    res = mr.run(jnp.asarray(px))
    np.testing.assert_array_equal(np.asarray(res.values), want)
    res_c = MapReduce(Histogram(), flow="combine").run(jnp.asarray(px))
    np.testing.assert_array_equal(np.asarray(res_c.values), want)


def test_mean_reducer_parity_stream():
    """Finalizing combiner (sum/count product) through the stream flow."""
    rng = np.random.default_rng(2)
    cids = rng.integers(0, 5, size=333).astype(np.int32)  # non-divisible
    pts = rng.standard_normal((333, 3)).astype(np.float32)
    app = make_app(
        lambda item, emit: emit(item[0].astype(jnp.int32), item[1]),
        lambda k, v, c: jnp.sum(v, axis=0) / jnp.maximum(c, 1).astype(
            jnp.float32),
        key_space=5,
        value_aval=jax.ShapeDtypeStruct((3,), jnp.float32),
        max_values_per_key=512,
        emit_capacity=1,
    )
    res = MapReduce(app, flow="stream", stream_chunk_pairs=64).run(
        (jnp.asarray(cids), jnp.asarray(pts)))
    got = np.asarray(res.values)
    for k in range(5):
        np.testing.assert_allclose(got[k], pts[cids == k].mean(0), atol=1e-5)


def test_masked_emission_stream():
    app = make_app(
        lambda item, emit: emit(item, jnp.ones_like(item), valid=item != 3),
        lambda k, v, c: jnp.sum(v),
        key_space=8,
        value_aval=jax.ShapeDtypeStruct((), jnp.int32),
        emit_capacity=8, max_values_per_key=64,
    )
    toks = jnp.asarray([[0, 3, 3, 1, 2, 3, 0, 1]] * 40, jnp.int32)
    res = MapReduce(app, flow="stream", stream_chunk_pairs=64).run(toks)
    assert int(res.counts[3]) == 0
    assert int(res.values[0]) == 80


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.int64],
                         ids=["f32", "wide_i64"])
def test_first_idiom_stream(dtype):
    """First-element idiom: holder keeps the first-arriving value across
    chunk boundaries (also for a wide job, whose carried counts must stay
    int32 through the chunk scan)."""
    app = make_app(
        lambda item, emit: emit(item[0], item[1]),
        lambda k, v, c: v[0],
        key_space=4,
        value_aval=jax.ShapeDtypeStruct((), dtype),
        emit_capacity=1, max_values_per_key=256,
    )
    keys = np.array([2, 0, 2, 1, 0, 1, 3, 2] * 16, np.int32)
    vals = np.arange(len(keys), dtype=np.float32)
    if dtype == jnp.int64:
        vals = vals.astype(np.int32)
    mr = MapReduce(app, flow="stream", stream_chunk_pairs=16)
    assert mr.plan.derivation.strategy == C.STRATEGY_FIRST
    res = mr.run((jnp.asarray(keys), jnp.asarray(vals)))
    got = np.asarray(res.values)
    for k in range(4):
        assert got[k] == vals[np.argmax(keys == k)]


def test_generic_holder_stream_matches_segment():
    """Coupled-holder combiner (logsumexp) exercises the sequential
    holder-carry fallback across chunks."""
    rng = np.random.default_rng(3)
    keys = rng.integers(0, 8, 200).astype(np.int32)
    vals = rng.standard_normal(200).astype(np.float32)
    app = make_app(
        lambda item, emit: emit(item[0], item[1]),
        lambda k, v, c: jax.scipy.special.logsumexp(v),
        key_space=8,
        value_aval=jax.ShapeDtypeStruct((), jnp.float32),
        emit_capacity=1, max_values_per_key=256,
        manual_combiner=C.logsumexp_spec(),
    )
    res_s = MapReduce(app, flow="stream", stream_chunk_pairs=32).run(
        (jnp.asarray(keys), jnp.asarray(vals)))
    res_c = MapReduce(app, flow="combine").run(
        (jnp.asarray(keys), jnp.asarray(vals)))
    np.testing.assert_allclose(np.asarray(res_s.values),
                               np.asarray(res_c.values), atol=1e-5)


def test_stream_use_kernels_parity(tokens):
    want = np.bincount(tokens.reshape(-1), minlength=VOCAB)
    res = MapReduce(WordCount(), flow="stream", use_kernels=True,
                    stream_chunk_pairs=256).run(jnp.asarray(tokens))
    np.testing.assert_array_equal(np.asarray(res.values), want)
    np.testing.assert_array_equal(np.asarray(res.counts), want)


# ---------------------------------------------------------------------------
# Bytes pressure: the paper's Figs 8/9 ordering, un-inverted
# ---------------------------------------------------------------------------


def _flow_bytes(mr, items):
    c = mr.lower(items).compile()
    return hlo_parser.analyze_text(c.as_text()).bytes_accessed


def test_bytes_monotonicity_stream_combine_reduce(tokens):
    """stream ≤ combine < reduce on the WordCount system workload: the
    derived-combiner flows move fewer bytes than the baseline, and the
    fused streaming flow is never worse than the legacy combine flow."""
    toks = jnp.asarray(tokens)
    b = {flow: _flow_bytes(MapReduce(WordCount(), flow=flow), toks)
         for flow in ("stream", "combine", "reduce")}
    assert b["stream"] <= b["combine"], b
    assert b["combine"] < b["reduce"], b


@pytest.mark.purejax_lowering  # skipped under the CI kernels override
def test_stream_peak_residency_bounded():
    """Peak live bytes of the stream flow stay O(K + chunk) while the
    legacy combine flow's grow with the full pair stream (Figs 8/9: the
    heap-pressure collapse)."""
    rng = np.random.default_rng(4)
    toks = jnp.asarray(rng.integers(0, VOCAB, (4096, 8)).astype(np.int32))

    def peak(mr):
        m = mr.lower(toks).compile().memory_analysis()
        return (m.argument_size_in_bytes + m.output_size_in_bytes +
                m.temp_size_in_bytes - m.alias_size_in_bytes)

    peak_stream = peak(MapReduce(WordCount(), flow="stream"))
    peak_combine = peak(MapReduce(WordCount(), flow="combine"))
    assert peak_stream < peak_combine / 2, (peak_stream, peak_combine)


def test_large_key_space_keeps_onehot_path():
    """key_space beyond the old dense-fold budget now stays on the one-hot
    additive fold (key-blocked where the lowering needs it) instead of
    silently degrading to the scatter fallback."""
    from repro.core import collector as col
    from repro.core import engine as eng

    BIG_K = (col.DENSE_FOLD_ELEMS_BUDGET // 256) + 1  # old scatter threshold
    app = make_app(
        lambda item, emit: emit(item, jnp.ones_like(item)),
        lambda k, v, c: jnp.sum(v),
        key_space=BIG_K,
        value_aval=jax.ShapeDtypeStruct((), jnp.int32),
        emit_capacity=4, max_values_per_key=64,
    )
    rng = np.random.default_rng(5)
    keys = rng.integers(0, BIG_K, (128, 4)).astype(np.int32)
    mr = MapReduce(app, flow="stream", stream_chunk_pairs=256)
    assert mr.tiling is not None and mr.tiling.mode == "additive"
    sc = eng._stream_combiner(app, mr.plan.spec, chunk_pairs=256)
    assert sc.mode == "additive"
    res = mr.run(jnp.asarray(keys))
    want = np.bincount(keys.reshape(-1), minlength=BIG_K)
    present = np.flatnonzero(want)
    np.testing.assert_array_equal(np.asarray(res.values)[present],
                                  want[present])


def test_scatter_fallback_beyond_fused_regime_warns():
    """Only past the fused-contraction pair regime does the pure-JAX
    streaming fold degrade to exact scatter — and it says so instead of
    choosing silently.  The Pallas kernel path is exempt (VMEM-resident
    one-hot tile)."""
    import pytest as _pytest

    from repro.core import collector as col
    from repro.core import combiner as C
    from repro.kernels import ops

    # past the fused pair regime AND the blocked dense budget at this
    # (chunk, key_block) — nothing scatter-free is left
    K = 1 << 16
    chunk = col.ADDITIVE_FOLD_PAIRS_FUSED * 2
    with _pytest.warns(col.LoweringFallbackWarning):
        sc = col.StreamCombiner(C.sum_spec(), K,
                                jax.ShapeDtypeStruct((), jnp.int32),
                                chunk_pairs=chunk)
    assert sc.mode == "scatter"
    # kernel path (float holders -> fused kernel runs): VMEM-resident
    # one-hot tile, no pair-regime limit
    sck = col.StreamCombiner(C.sum_spec(), K,
                             jax.ShapeDtypeStruct((), jnp.float32),
                             chunk_pairs=chunk, fold_fn=ops.onehot_fold)
    assert sck.mode == "additive"
    # ...but int holders bypass the fused kernel (exact-accumulation path),
    # so the pure-JAX budgets still apply under use_kernels
    sci = col.StreamCombiner(C.sum_spec(), K,
                             jax.ShapeDtypeStruct((), jnp.int32),
                             chunk_pairs=chunk, fold_fn=ops.onehot_fold)
    assert sci.mode == "scatter"


@pytest.mark.parametrize("flow", ["stream", "sort"])
def test_kernels_that_cannot_carry_the_holder_warn(tokens, flow):
    """use_kernels=True with int32 holder tables: the Pallas kernels carry
    f32 tables only, so the fold runs in pure JAX — and says so."""
    from repro.core import LoweringFallbackWarning

    with pytest.warns(LoweringFallbackWarning, match="only f32 holder"):
        res = MapReduce(WordCount(), flow=flow,
                        use_kernels=True).run(jnp.asarray(tokens))
    want = np.bincount(tokens.reshape(-1), minlength=VOCAB)
    np.testing.assert_array_equal(np.asarray(res.values), want)


def test_int_tables_accumulate_exactly_per_chunk():
    """Integer holder tables accumulate in their own dtype across chunks
    (per-chunk f32 deltas are exact; the running sum is int32)."""
    app = make_app(
        lambda item, emit: emit(jnp.zeros_like(item), item),
        lambda k, v, c: jnp.sum(v),
        key_space=2,
        value_aval=jax.ShapeDtypeStruct((), jnp.int32),
        emit_capacity=1, max_values_per_key=1 << 12,
    )
    # each value near 2^20; 1024 of them sum to ~2^30 — far beyond f32's
    # 2^24 exact-integer range (an f32 running accumulator would drift by
    # the rounded-off low bits) but within int32, so exactness requires
    # the int32 table carry
    vals = np.full(1024, (1 << 20) + 7, np.int32)
    res = MapReduce(app, flow="stream", stream_chunk_pairs=64).run(
        jnp.asarray(vals))
    assert int(res.values[0]) == int(vals.astype(np.int64).sum())
