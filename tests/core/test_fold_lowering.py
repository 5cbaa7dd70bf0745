"""The additive fold's lowering rule: one-hot contraction or scatter-add.

On a TPU the one-hot contraction costs per pair and key, the scatter-add
per pair, so above ``collector.TPU_SCATTER_MIN_KEYS`` keys every additive
leaf and the counts fold by an exact scatter-add, with the chunk sized for
the scatter.  XLA:CPU keeps the one-hot contraction.  The platform is
steered here through ``collector.fold_platform``.
"""

import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import MapReduce, make_app
from repro.core import autotune as at
from repro.core import collector as col
from repro.core import combiner as C
from repro.core import plan_cache as pc
from repro.kernels import ops

I32 = jnp.int32
CROSS = col.TPU_SCATTER_MIN_KEYS
KEY_SPACES = [768, CROSS, CROSS + 1, 131072]


def _wc_app(key_space, emit_capacity=16):
    return make_app(
        lambda window, emit: emit(window, jnp.ones_like(window)),
        lambda k, v, c: jnp.sum(v),
        key_space=key_space,
        value_aval=jax.ShapeDtypeStruct((), I32),
        emit_capacity=emit_capacity, max_values_per_key=64,
    )


@pytest.fixture
def on_platform(monkeypatch):
    def set_platform(name):
        monkeypatch.setattr(col, "fold_platform", lambda: name)
    return set_platform


def _expected(platform, key_space):
    return ("scatter" if platform == "tpu" and key_space > CROSS
            else "additive")


def _tiling(key_space):
    with warnings.catch_warnings():
        warnings.simplefilter("error", col.LoweringFallbackWarning)
        return at.autotune_stream(_wc_app(key_space), C.sum_spec())


@pytest.mark.parametrize("key_space", KEY_SPACES)
@pytest.mark.parametrize("platform", ["tpu", "cpu"])
def test_rule_picks_scatter_on_tpu_above_crossover(on_platform, platform,
                                                   key_space):
    """The autotuner and the collector agree on the lowering, name it in
    the tiling notes, and a chosen scatter raises no fallback warning."""
    on_platform(platform)
    want = _expected(platform, key_space)
    t = _tiling(key_space)
    assert t.mode == want
    assert any(n.startswith(f"fold lowering: {want} on {platform}")
               for n in t.notes), t.notes
    assert not any(n.startswith("FALLBACK") for n in t.notes)
    with warnings.catch_warnings():
        warnings.simplefilter("error", col.LoweringFallbackWarning)
        sc = col.StreamCombiner(
            C.sum_spec(), key_space, jax.ShapeDtypeStruct((), I32),
            chunk_pairs=t.chunk_pairs,
            key_block=t.key_block if t.blocked else None)
    assert sc.mode == want


@pytest.mark.parametrize("key_space", KEY_SPACES)
@pytest.mark.parametrize("platform", ["tpu", "cpu"])
def test_chunk_is_sized_for_the_chosen_lowering(on_platform, platform,
                                                key_space):
    """The fused cap of 2,048 pairs holds for the one-hot contraction only;
    the scatter takes the model-balanced chunk, unblocked."""
    on_platform(platform)
    t = _tiling(key_space)
    if t.mode == "additive":
        assert t.chunk_pairs <= col.ADDITIVE_FOLD_PAIRS_FUSED
        return
    assert t.chunk_pairs == at.choose_chunk_pairs(
        key_space, holder_bytes=4, pair_bytes=8, emit_capacity=16)
    assert t.chunk_pairs > col.ADDITIVE_FOLD_PAIRS_FUSED
    assert not t.blocked
    if key_space == 131072:
        assert t.chunk_pairs == at.MAX_CHUNK_PAIRS == 65536


@pytest.mark.parametrize("key_space", KEY_SPACES)
@pytest.mark.parametrize("platform", ["tpu", "cpu"])
def test_fold_counter_advances_once_per_fold_built(on_platform, platform,
                                                   key_space):
    on_platform(platform)
    want = _expected(platform, key_space)
    s0 = pc.stats_snapshot()
    col.StreamCombiner(C.sum_spec(), key_space,
                       jax.ShapeDtypeStruct((), I32), chunk_pairs=2048)
    s1 = pc.stats_snapshot()
    moved = {m: s1[f"folds.{m}"] - s0[f"folds.{m}"]
             for m in pc.FOLD_LOWERINGS}
    assert moved == {m: int(m == want) for m in pc.FOLD_LOWERINGS}


def test_kernel_fold_keeps_the_onehot_on_tpu(on_platform):
    """The Pallas fold kernel is outside the rule: f32 holders with the
    kernel supplied stay on the kernel's one-hot at any key space."""
    on_platform("tpu")
    sc = col.StreamCombiner(C.sum_spec(), 131072,
                            jax.ShapeDtypeStruct((), jnp.float32),
                            chunk_pairs=65536, fold_fn=ops.onehot_fold)
    assert sc.mode == "additive"


def test_plan_cache_keys_the_platform(on_platform):
    """A plan resolved for one platform is not served to another."""
    app = _wc_app(131072)
    on_platform("cpu")
    assert MapReduce(app).tiling.mode == "additive"
    on_platform("tpu")
    assert MapReduce(app).tiling.mode == "scatter"


@pytest.mark.parametrize("dtype,scatters", [(I32, 1), (jnp.float32, 2)])
def test_scatter_fold_is_exact_and_contracts_nothing(on_platform, dtype,
                                                     scatters):
    """Four 65,536-pair chunks of Zipf keys with sentinel pairs, folded by
    scatter at K = 131,072 with 256-key blocks given: values and counts
    equal ``np.bincount``, and the fold holds no contraction (the counts
    scatter too, though the blocks would fit the dense budget).  Int32
    values share one scatter with the int32 counts; f32 values take their
    own."""
    on_platform("tpu")
    K, chunk, n_chunks = 131072, 65536, 4
    sc = col.StreamCombiner(C.sum_spec(), K, jax.ShapeDtypeStruct((), dtype),
                            chunk_pairs=chunk, key_block=256)
    assert sc.mode == "scatter" and sc._dense_ok

    rng = np.random.default_rng(14)
    keys = np.minimum(rng.zipf(1.2, (n_chunks, chunk)) - 1, K - 1)
    keys[rng.random(keys.shape) < 0.05] = K  # sentinel: invalid pairs
    keys = keys.astype(np.int32)
    vals = rng.integers(-5, 6, (n_chunks, chunk)).astype(dtype)

    def fold(state, k, v):
        return sc.fold_chunk(state, col.PairStream(k, v, K))

    jaxpr = str(jax.make_jaxpr(fold)(sc.init_state(), keys[0], vals[0]))
    assert "dot_general" not in jaxpr
    assert jaxpr.count("scatter-add") == scatters

    step = jax.jit(fold)
    state = sc.init_state()
    for i in range(n_chunks):
        state = step(state, jnp.asarray(keys[i]), jnp.asarray(vals[i]))
    tables, counts = sc.tables_counts(state)
    flat_k, flat_v = keys.reshape(-1), vals.reshape(-1)
    ok = flat_k < K
    np.testing.assert_array_equal(
        np.asarray(jax.tree.leaves(tables)[0]),
        np.bincount(flat_k[ok], weights=flat_v[ok], minlength=K))
    np.testing.assert_array_equal(
        np.asarray(counts), np.bincount(flat_k[ok], minlength=K))
