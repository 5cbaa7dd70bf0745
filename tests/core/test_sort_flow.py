"""The sort-based shuffle flow (radix-bucketed segment reduce) and the
cost-model flow selection.

Parity: the sort flow computes exactly what the reduce/stream flows compute
for every combiner strategy (monoid, product, first, size, sequential),
across chunk boundaries, with exact integer accumulation.

Selection: with a workload hint the planner ranks stream vs sort with the
roofline+compute cost model, the report lands on the plan, and explain()
shows flow + bucket count + cost terms.

Satellites: the per-plan LoweringFallbackWarning dedupe and the persistent
autotune probe cache.
"""

import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

#: tests below that assert how flow="auto" RESOLVES cannot run under the
#: CI flow-matrix override (conftest redirects the auto default there and
#: owns the skip, via pytest_collection_modifyitems).
auto_flow_semantics = pytest.mark.auto_flow

from repro.core import MapReduce, MapReduceApp, make_app
from repro.core import autotune as at
from repro.core import collector as col
from repro.core import combiner as C
from repro.core import cost_model as cm

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
# Parity with the other flows
# ---------------------------------------------------------------------------


def test_wordcount_sort_flow_bitwise_parity(tokens):
    want = np.bincount(tokens.reshape(-1), minlength=VOCAB)
    res = MapReduce(WordCount(), flow="sort").run(jnp.asarray(tokens))
    np.testing.assert_array_equal(np.asarray(res.values), want)
    np.testing.assert_array_equal(np.asarray(res.counts), want)
    res_r = MapReduce(WordCount(), flow="reduce").run(jnp.asarray(tokens))
    mask = want > 0
    np.testing.assert_array_equal(np.asarray(res_r.values)[mask],
                                  np.asarray(res.values)[mask])


def test_sort_flow_multichunk_matches_single_chunk(tokens):
    want = np.bincount(tokens.reshape(-1), minlength=VOCAB)
    res = MapReduce(WordCount(), flow="sort",
                    stream_chunk_pairs=128).run(jnp.asarray(tokens))
    np.testing.assert_array_equal(np.asarray(res.values), want)
    np.testing.assert_array_equal(np.asarray(res.counts), want)


def test_sort_flow_max_monoid_segmented_scan():
    rng = np.random.default_rng(1)
    keys = rng.integers(0, 50, 500).astype(np.int32)
    vals = rng.standard_normal(500).astype(np.float32)
    app = make_app(
        lambda item, emit: emit(item[0], item[1]),
        lambda k, v, c: jnp.max(v),
        key_space=50, value_aval=jax.ShapeDtypeStruct((), jnp.float32),
        emit_capacity=1, max_values_per_key=512, pad_value=-np.inf,
    )
    res = MapReduce(app, flow="sort", stream_chunk_pairs=128).run(
        (jnp.asarray(keys), jnp.asarray(vals)))
    got = np.asarray(res.values)
    for k in range(50):
        if (keys == k).any():
            np.testing.assert_allclose(got[k], vals[keys == k].max(),
                                       rtol=1e-6)


def test_sort_flow_mean_product_spec():
    rng = np.random.default_rng(2)
    cids = rng.integers(0, 5, 333).astype(np.int32)
    pts = rng.standard_normal((333, 3)).astype(np.float32)
    app = make_app(
        lambda item, emit: emit(item[0].astype(jnp.int32), item[1]),
        lambda k, v, c: jnp.sum(v, axis=0) / jnp.maximum(c, 1).astype(
            jnp.float32),
        key_space=5, value_aval=jax.ShapeDtypeStruct((3,), jnp.float32),
        emit_capacity=1, max_values_per_key=512,
    )
    res = MapReduce(app, flow="sort", stream_chunk_pairs=64).run(
        (jnp.asarray(cids), jnp.asarray(pts)))
    got = np.asarray(res.values)
    for k in range(5):
        np.testing.assert_allclose(got[k], pts[cids == k].mean(0), atol=1e-5)


def test_sort_flow_first_idiom_stable_across_chunks():
    """The packed sort is stable, so the run start IS the first-arrived
    value — including across chunk boundaries via the count gate."""
    app = make_app(
        lambda item, emit: emit(item[0], item[1]),
        lambda k, v, c: v[0],
        key_space=4, value_aval=jax.ShapeDtypeStruct((), jnp.float32),
        emit_capacity=1, max_values_per_key=256,
    )
    keys = np.array([2, 0, 2, 1, 0, 1, 3, 2] * 16, np.int32)
    vals = np.arange(len(keys), dtype=np.float32)
    mr = MapReduce(app, flow="sort", stream_chunk_pairs=16)
    assert mr.plan.derivation.strategy == C.STRATEGY_FIRST
    res = mr.run((jnp.asarray(keys), jnp.asarray(vals)))
    got = np.asarray(res.values)
    for k in range(4):
        assert got[k] == vals[np.argmax(keys == k)]


def test_sort_flow_sequential_fallback_logsumexp():
    rng = np.random.default_rng(3)
    keys = rng.integers(0, 8, 200).astype(np.int32)
    vals = rng.standard_normal(200).astype(np.float32)
    app = make_app(
        lambda item, emit: emit(item[0], item[1]),
        lambda k, v, c: jax.scipy.special.logsumexp(v),
        key_space=8, value_aval=jax.ShapeDtypeStruct((), jnp.float32),
        emit_capacity=1, max_values_per_key=256,
        manual_combiner=C.logsumexp_spec(),
    )
    res_s = MapReduce(app, flow="sort", stream_chunk_pairs=32).run(
        (jnp.asarray(keys), jnp.asarray(vals)))
    res_c = MapReduce(app, flow="combine").run(
        (jnp.asarray(keys), jnp.asarray(vals)))
    np.testing.assert_allclose(np.asarray(res_s.values),
                               np.asarray(res_c.values), atol=1e-5)


def test_sort_flow_masked_emission():
    app = make_app(
        lambda item, emit: emit(item, jnp.ones_like(item), valid=item != 3),
        lambda k, v, c: jnp.sum(v),
        key_space=8, value_aval=jax.ShapeDtypeStruct((), jnp.int32),
        emit_capacity=8, max_values_per_key=64,
    )
    toks = jnp.asarray([[0, 3, 3, 1, 2, 3, 0, 1]] * 40, jnp.int32)
    res = MapReduce(app, flow="sort", stream_chunk_pairs=64).run(toks)
    assert int(res.counts[3]) == 0
    assert int(res.values[0]) == 80


def test_sort_flow_int_tables_exact_beyond_f32():
    """Integer holder specs bypass the fused f32 accumulator: per-chunk
    int32 cumsums merge into int32 tables, exact past 2^24."""
    app = make_app(
        lambda item, emit: emit(jnp.zeros_like(item), item),
        lambda k, v, c: jnp.sum(v),
        key_space=2, value_aval=jax.ShapeDtypeStruct((), jnp.int32),
        emit_capacity=1, max_values_per_key=1 << 12,
    )
    vals = np.full(1024, (1 << 20) + 7, np.int32)
    res = MapReduce(app, flow="sort", stream_chunk_pairs=64).run(
        jnp.asarray(vals))
    assert int(res.values[0]) == int(vals.astype(np.int64).sum())


def test_sort_flow_use_kernels_parity(tokens):
    """The radix-partition + segment_reduce pipeline (interpret mode)."""
    want = np.bincount(tokens.reshape(-1), minlength=VOCAB)
    app = make_app(
        lambda item, emit: emit(item, jnp.ones_like(item, jnp.float32)),
        lambda k, v, c: jnp.sum(v),
        key_space=VOCAB, value_aval=jax.ShapeDtypeStruct((), jnp.float32),
        emit_capacity=8, max_values_per_key=1024,
    )
    res = MapReduce(app, flow="sort", use_kernels=True,
                    stream_chunk_pairs=512).run(jnp.asarray(tokens))
    np.testing.assert_array_equal(np.asarray(res.values), want)
    np.testing.assert_array_equal(np.asarray(res.counts), want)


def test_sort_combiner_fused_accumulator_engaged():
    spec = C.sum_spec()
    aval_f = jax.ShapeDtypeStruct((), jnp.float32)
    aval_i = jax.ShapeDtypeStruct((), jnp.int32)
    assert col.SortCombiner(spec, 64, aval_f)._fused_acc
    assert not col.SortCombiner(spec, 64, aval_i)._fused_acc  # exactness
    rng = np.random.default_rng(4)
    keys = jnp.asarray(rng.integers(0, 65, 200).astype(np.int32))  # + sentinel
    vals = jnp.asarray(rng.standard_normal(200).astype(np.float32))
    stream = col.PairStream(keys, vals, 64)
    grouped = col.sort_flow(spec, stream)
    want = np.zeros(64, np.float64)
    np.add.at(want, np.asarray(keys)[np.asarray(keys) < 64],
              np.asarray(vals, np.float64)[np.asarray(keys) < 64])
    np.testing.assert_allclose(np.asarray(grouped.values), want, atol=1e-4)


def test_forced_sort_on_noncombinable_raises():
    app = make_app(
        lambda item, emit: emit(item, item.astype(jnp.float32)),
        lambda k, v, c: jnp.sort(v)[0],
        key_space=8, value_aval=jax.ShapeDtypeStruct((), jnp.float32),
        emit_capacity=8, max_values_per_key=64,
    )
    with pytest.raises(ValueError, match="derivation failed"):
        MapReduce(app, flow="sort")


# ---------------------------------------------------------------------------
# Multi-pass radix sort and the kernel partition past one bucket
# ---------------------------------------------------------------------------


BIG_SORT_K = 1 << 17  # past the 31-bit packed-sort regime at 16k chunks


def test_stable_sort_multi_pass_equals_two_key():
    """The lax.scan-over-levels radix sort is stable and bitwise equal to
    the two-key comparator sort it replaces (keys + permutation)."""
    rng = np.random.default_rng(7)
    keys = jnp.asarray(rng.integers(0, BIG_SORT_K + 1, 1 << 14)
                       .astype(np.int32))  # incl. sentinel
    sk_r, ord_r = jax.jit(lambda x: col.stable_sort_by_key(
        x, BIG_SORT_K, impl="radix"))(keys)
    sk_t, ord_t = jax.jit(lambda x: col.stable_sort_by_key(
        x, BIG_SORT_K, impl="two_key"))(keys)
    np.testing.assert_array_equal(np.asarray(sk_r), np.asarray(sk_t))
    np.testing.assert_array_equal(np.asarray(ord_r), np.asarray(ord_t))
    # auto resolves to the multi-pass radix here (the old silent degrade)
    sk_a, ord_a = jax.jit(lambda x: col.stable_sort_by_key(
        x, BIG_SORT_K))(keys)
    np.testing.assert_array_equal(np.asarray(ord_a), np.asarray(ord_t))


def test_sort_radix_passes_regimes():
    assert col.sort_radix_passes(1 << 14, 1 << 15) == 1  # packed fits
    assert col.sort_radix_passes(1 << 14, BIG_SORT_K) == 2
    assert col.sort_radix_passes(4096, 1 << 20) == 2
    with pytest.raises(ValueError, match="packed"):
        col.stable_sort_by_key(jnp.zeros(1 << 14, jnp.int32), 1 << 20,
                               impl="packed")


def test_sort_flow_multi_pass_regime_parity():
    """flow="sort" past the packed regime: 16k-pair chunks at K=2^17 push
    (key, index) past 31 bits, so the fold runs the multi-pass radix —
    exact parity with the bincount ground truth, across chunk boundaries."""
    rng = np.random.default_rng(8)
    toks = rng.integers(0, BIG_SORT_K, size=(4096, 8)).astype(np.int32)
    app = _sum_app(BIG_SORT_K)
    want = np.bincount(toks.reshape(-1), minlength=BIG_SORT_K)
    mr = MapReduce(app, flow="sort", stream_chunk_pairs=1 << 14)
    assert mr.tiling.sort_passes > 1  # the multi-pass regime is engaged
    res = mr.run(jnp.asarray(toks))
    np.testing.assert_array_equal(np.asarray(res.values), want)
    np.testing.assert_array_equal(np.asarray(res.counts), want)


def test_sort_flow_kernel_hierarchy_parity(monkeypatch):
    """use_kernels with a key space of many buckets: the one-pass kernel
    partition stays bitwise exact across chunk boundaries.  The bucket
    cap is shrunk so K=4096 takes 16 buckets."""
    from repro.kernels import ops

    monkeypatch.setattr(ops, "MAX_BUCKET_SIZE", 256)
    K = 4096
    app = make_app(
        lambda item, emit: emit(item, jnp.ones_like(item, jnp.float32)),
        lambda k, v, c: jnp.sum(v),
        key_space=K, value_aval=jax.ShapeDtypeStruct((), jnp.float32),
        emit_capacity=8, max_values_per_key=1024,
    )
    rng = np.random.default_rng(9)
    toks = rng.integers(0, K, size=(128, 8)).astype(np.int32)
    mr = MapReduce(app, flow="sort", use_kernels=True,
                   stream_chunk_pairs=512)
    assert mr.tiling.key_block == 256 and mr.tiling.n_key_blocks == 16
    res = mr.run(jnp.asarray(toks))
    want = np.bincount(toks.reshape(-1), minlength=K)
    np.testing.assert_array_equal(np.asarray(res.values), want)
    np.testing.assert_array_equal(np.asarray(res.counts), want)


def test_sort_flow_level_budget_fallback_warns_once(monkeypatch):
    """The kernel sort flow hides nothing: holders the kernels cannot
    carry (int32 counts) fire ONE LoweringFallbackWarning per plan with
    diagnostics and fold exactly in pure JAX, and a bucket count whose
    padded partition overflows VMEM raises instead of falling back."""
    from repro.kernels import ops

    K = 4096
    rng = np.random.default_rng(10)
    toks = rng.integers(0, K, size=(64, 8)).astype(np.int32)
    want = np.bincount(toks.reshape(-1), minlength=K)
    mr = MapReduce(_sum_app(K), flow="sort", use_kernels=True)
    with pytest.warns(col.LoweringFallbackWarning, match="only f32 holder"):
        res = mr.run(jnp.asarray(toks))
    np.testing.assert_array_equal(np.asarray(res.values), want)
    assert any("only f32 holder" in d for d in mr.plan.diagnostics)
    with warnings.catch_warnings():  # re-trace: deduped per plan
        warnings.simplefilter("error", col.LoweringFallbackWarning)
        mr.run(jnp.asarray(rng.integers(0, K, size=(80, 8))
                           .astype(np.int32)))

    monkeypatch.setattr(ops, "MAX_BUCKET_SIZE", 8)  # 512 buckets
    app = make_app(
        lambda item, emit: emit(item, jnp.ones_like(item, jnp.float32)),
        lambda k, v, c: jnp.sum(v),
        key_space=K, value_aval=jax.ShapeDtypeStruct((), jnp.float32),
        emit_capacity=8, max_values_per_key=1024,
    )
    with pytest.raises(ValueError, match="VMEM"):
        MapReduce(app, flow="sort", use_kernels=True).run(jnp.asarray(toks))


def test_sort_cost_model_prices_multi_pass():
    """The extended cost model charges the pure-JAX lowering one packed
    sort per digit pass — the sort estimate must grow past the packed
    regime — while still picking sort over the one-hot fold at K=1M."""
    small = cm.estimate_flow_cost("sort", n_pairs=4096, key_space=1 << 15)
    big = cm.estimate_flow_cost("sort", n_pairs=4096, key_space=1 << 20)
    assert dict(big.terms)["sort"] > dict(small.terms)["sort"]
    report = cm.choose_flow(n_pairs=4096, key_space=1 << 20, backend="cpu")
    assert report.chosen == "sort"


def test_explain_shows_levels_at_large_k():
    """At K=2^20 explain() shows the kernel buckets and the pure-JAX
    lowering's two packed-sort passes."""
    mr = MapReduce(_sum_app(1 << 20, jnp.float32), flow="sort",
                   n_pairs_hint=4096)
    text = mr.explain()
    assert "buckets=64×16384keys" in text and "sort_passes=2" in text
    assert mr.tiling.sort_passes == 2


# -- hypothesis: tiled partition ≡ one-tile partition ≡ reduce -------------

try:  # optional dependency (mirrors tests/core/test_properties.py)
    from hypothesis import given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:  # pragma: no cover
    HAVE_HYPOTHESIS = False


if HAVE_HYPOTHESIS:

    @settings(max_examples=12, deadline=None)
    @given(
        bucket_pow=st.integers(2, 4),       # bucket 4..16 keys
        n_buckets=st.integers(2, 16),
        k_off=st.integers(0, 3),            # K not a bucket multiple
        n=st.integers(1, 120),
        tile_n=st.integers(1, 64),
        seed=st.integers(0, 2 ** 16),
    )
    def test_multi_pass_equals_single_pass_equals_reduce(
            bucket_pow, n_buckets, k_off, n, tile_n, seed):
        """Random bucket layouts and pair tiles: the partition walked in
        ``tile_n``-pair tiles, the partition walked in one tile, and the
        reduce-flow ground truth agree, including a ragged last bucket,
        chunks shorter than ``pad_align`` and sentinel/trash
        invariants."""
        from repro.kernels import ops, ref

        bs = 1 << bucket_pow
        k = max(bs * n_buckets - k_off, bs + 1)  # >1 bucket, ragged last
        rng = np.random.default_rng(seed)
        keys = rng.integers(0, k + 1, size=n).astype(np.int32)  # + sentinel
        vals = rng.standard_normal((n, 1)).astype(np.float32)
        pa = 8
        tiled_k, tiled_v, _ = ops.radix_partition(
            jnp.asarray(keys), jnp.asarray(vals), k, bucket_size=bs,
            pad_align=pa, tile_n=tile_n)
        whole_k, whole_v, _ = ops.radix_partition(
            jnp.asarray(keys), jnp.asarray(vals), k, bucket_size=bs,
            pad_align=pa, tile_n=max(n, 8))
        np.testing.assert_array_equal(np.asarray(tiled_k),
                                      np.asarray(whole_k))
        real = np.asarray(whole_k) < k
        np.testing.assert_allclose(np.asarray(tiled_v)[real],
                                   np.asarray(whole_v)[real], rtol=1e-6)
        # sentinel/trash invariants: dropped slots normalized, none lost
        tk = np.asarray(tiled_k)
        np.testing.assert_array_equal(np.sort(tk[tk < k]),
                                      np.sort(keys[keys < k]))
        assert (tk <= k).all()
        # the folded table == the reduce-flow per-key sums (ground truth)
        acc = jnp.zeros((k, 1), jnp.float32)
        got = ops.sort_segment_fold(jnp.asarray(keys), jnp.asarray(vals),
                                    acc, "add", bucket_size=bs, pad_align=pa)
        want = np.zeros((k, 1), np.float64)
        np.add.at(want, keys[keys < k],
                  vals[keys < k].astype(np.float64))
        np.testing.assert_allclose(np.asarray(got), want, atol=1e-4)
        oracle = ref.sort_segment_fold(jnp.asarray(keys), jnp.asarray(vals),
                                       acc, "add")
        np.testing.assert_allclose(np.asarray(got), np.asarray(oracle),
                                   atol=1e-4)


# ---------------------------------------------------------------------------
# Cost-model flow selection + explain()
# ---------------------------------------------------------------------------


def _sum_app(key_space, dtype=jnp.int32):
    return make_app(
        lambda item, emit: emit(item, jnp.ones_like(item)),
        lambda k, v, c: jnp.sum(v),
        key_space=key_space, value_aval=jax.ShapeDtypeStruct((), dtype),
        emit_capacity=8, max_values_per_key=64,
    )


@auto_flow_semantics
def test_cost_model_picks_sort_at_large_sparse_k():
    mr = MapReduce(_sum_app(32768), n_pairs_hint=1024)
    assert mr.plan.flow == "sort"
    assert mr.plan.cost is not None and mr.plan.cost.chosen == "sort"
    sort_c = mr.plan.cost.cost_of("sort")
    stream_c = mr.plan.cost.cost_of("stream")
    assert sort_c.est_s < stream_c.est_s
    # the separating term is compute: the one-hot fold's O(N·K)
    assert dict(stream_c.terms)["onehot"] > dict(sort_c.terms)["sort"]


@auto_flow_semantics
def test_cost_model_keeps_stream_at_small_k():
    mr = MapReduce(_sum_app(4), n_pairs_hint=1024)
    assert mr.plan.flow == "stream"


@auto_flow_semantics
def test_auto_without_hint_keeps_stream_default():
    """No workload hint -> the paper's one-flag behaviour is unchanged."""
    mr = MapReduce(_sum_app(32768))
    assert mr.plan.flow == "stream"


@auto_flow_semantics
def test_cost_model_not_offered_for_coupled_holders():
    """Scan-fold specs can't take the vectorized sort path; the model only
    ranks flows the combiner can actually run."""
    app = make_app(
        lambda item, emit: emit(item[0], item[1]),
        lambda k, v, c: jax.scipy.special.logsumexp(v),
        key_space=32768, value_aval=jax.ShapeDtypeStruct((), jnp.float32),
        emit_capacity=1, max_values_per_key=64,
        manual_combiner=C.logsumexp_spec(),
    )
    mr = MapReduce(app, n_pairs_hint=1024)
    assert mr.plan.flow == "stream"
    assert tuple(c.flow for c in mr.plan.cost.costs) == ("stream",)


@auto_flow_semantics
def test_explain_reports_flow_buckets_and_cost_terms():
    mr = MapReduce(_sum_app(32768), n_pairs_hint=1024)
    text = mr.explain()
    assert "flow: sort" in text
    assert "cost model" in text and "est=" in text
    assert "buckets=" in text  # radix bucket count via the tiling record
    assert mr.tiling.mode == "sort" and mr.tiling.n_key_blocks >= 1


def test_flow_cost_model_bytes_ordering():
    """The analytic bytes chain the crossover benchmark asserts:
    sort ≤ combine < reduce (single chunk — sort == combine there)."""
    kw = dict(n_pairs=1024, key_space=32768, max_values_per_key=8,
              backend="cpu")
    b = {f: cm.estimate_flow_cost(f, **kw).model_bytes
         for f in ("sort", "combine", "reduce")}
    assert b["sort"] <= b["combine"] < b["reduce"]


def test_tpu_profile_moves_crossover_right():
    """On the MXU profile the one-hot fold stays cheap far past the CPU
    crossover (the co-design point: same semantics, different winner per
    architecture) — the radix partition's per-pair scalar stores only pay
    off when K reaches the few-hundred-k range."""
    cpu = cm.choose_flow(n_pairs=1024, key_space=32768, backend="cpu")
    v5e = "TPU v5 lite"  # pricing a TPU from this host names the chip
    tpu = cm.choose_flow(n_pairs=1024, key_space=32768, backend="tpu",
                         device_kind=v5e)
    assert cpu.chosen == "sort"
    assert tpu.chosen == "stream"
    tpu_big = cm.choose_flow(n_pairs=1024, key_space=1 << 21, backend="tpu",
                             device_kind=v5e)
    assert tpu_big.chosen == "sort"


def test_tpu_profile_refuses_unknown_device_kind():
    """A chip without a row in the peaks table is an error, never priced
    as a v5e."""
    with pytest.raises(ValueError, match="no published peaks"):
        cm.choose_flow(n_pairs=1024, key_space=32768, backend="tpu",
                       device_kind="TPU v4")


# ---------------------------------------------------------------------------
# Satellite: per-plan LoweringFallbackWarning dedupe
# ---------------------------------------------------------------------------


def test_fallback_warning_deduped_per_plan():
    """The dense-budget degrade warns ONCE per plan (not once per trace)
    while the plan diagnostic list stays complete."""
    app = _sum_app(1 << 16)
    # chunk past the fused regime AND blocking disabled -> nothing
    # scatter-free is left, the collector degrades (and used to warn on
    # every trace)
    mr = MapReduce(app, flow="stream", stream_chunk_pairs=4096,
                   stream_key_block=None)
    rng = np.random.default_rng(5)
    with pytest.warns(col.LoweringFallbackWarning):
        mr.run(jnp.asarray(
            rng.integers(0, 1 << 16, (1024, 8)).astype(np.int32)))
    with warnings.catch_warnings():
        warnings.simplefilter("error", col.LoweringFallbackWarning)
        # a NEW shape forces a re-trace of the same plan — no second warning
        mr.run(jnp.asarray(
            rng.integers(0, 1 << 16, (1536, 8)).astype(np.int32)))
    assert any("scatter" in d for d in mr.plan.diagnostics)


def test_direct_collector_construction_still_warns():
    """Without a plan sink the legacy warn-at-construction behaviour holds
    (tests and direct users keep their signal)."""
    with pytest.warns(col.LoweringFallbackWarning):
        col.StreamCombiner(C.sum_spec(), 1 << 16,
                           jax.ShapeDtypeStruct((), jnp.int32),
                           chunk_pairs=col.ADDITIVE_FOLD_PAIRS_FUSED * 2)


# ---------------------------------------------------------------------------
# Satellite: persistent autotune probe cache
# ---------------------------------------------------------------------------


def test_tune_cache_persists_probe_results(tmp_path, monkeypatch):
    path = str(tmp_path / "tune.json")
    monkeypatch.setenv(at.TUNE_CACHE_ENV, path)
    app = _sum_app(64)
    spec = C.sum_spec()
    t1 = at.autotune_stream(app, spec, probe=True, probe_pairs=256)
    assert t1.source == "probe"
    cache = at.load_tune_cache(path)
    assert len(cache) == 1
    (entry,) = cache.values()
    assert entry["chunk_pairs"] == t1.chunk_pairs
    # second run: measured result reused, no re-probing
    t2 = at.autotune_stream(app, spec, probe=True, probe_pairs=256)
    assert t2.source == "cache"
    assert t2.chunk_pairs == t1.chunk_pairs
    assert any("cache hit" in n for n in t2.notes)


def test_tune_cache_off_by_default(monkeypatch):
    monkeypatch.delenv(at.TUNE_CACHE_ENV, raising=False)
    assert at.tune_cache_path() is None
    t = at.autotune_stream(_sum_app(64), C.sum_spec(), probe=True,
                           probe_pairs=256)
    assert t.source == "probe"  # measured, nothing persisted


def test_tune_cache_ignores_corrupt_file(tmp_path, monkeypatch):
    path = str(tmp_path / "tune.json")
    with open(path, "w") as f:
        f.write("{not json")
    monkeypatch.setenv(at.TUNE_CACHE_ENV, path)
    t = at.autotune_stream(_sum_app(64), C.sum_spec(), probe=True,
                           probe_pairs=256)
    assert t.source == "probe"  # advisory: bad cache never breaks a run
