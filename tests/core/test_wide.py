"""Wide jobs: a job whose declared values are 64-bit integers runs in a
64-bit scope of its own, with exact sums past 2^32 per key, while every
array the engine makes for itself stays 32-bit and no global flag is set;
a job of 32-bit values lowers with no 64-bit type at all."""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import MapReduce, make_app, trace
from repro.core import collector as col
from repro.core import plan_cache as pc
from repro.core.api import wide_job
from repro.streaming import sliding

K = 8
#: values near 2^31 - 1: a key's sum passes 2^32 after three of them
BIG = 2_000_000_000


def wide_app(reduce_fn=None):
    """``(key, value)`` int32 items; the map widens the value to int64,
    the reduce returns its sum, the sum of three times it, and the count."""
    def reduce(k, v, c):
        return jnp.sum(v), jnp.sum(v * 3), c

    return make_app(
        map_fn=lambda item, emit: emit(item[0], item[1].astype(jnp.int64)),
        reduce_fn=reduce_fn or reduce,
        key_space=K,
        value_aval=jax.ShapeDtypeStruct((), jnp.int64),
        emit_capacity=1,
        max_values_per_key=4096,
    )


def wide_items(n=3000, seed=0):
    rng = np.random.default_rng(seed)
    keys = rng.integers(0, K, n).astype(np.int32)
    vals = rng.integers(BIG - 1000, BIG, n).astype(np.int32)
    vals[::7] *= -1
    return keys, vals


def exact_sums(keys, vals):
    out = np.zeros(K, np.int64)
    np.add.at(out, keys, vals.astype(np.int64))
    return out


@pytest.mark.parametrize("flow", ["stream", "sort", "combine", "reduce"])
def test_wide_sums_past_2_32_are_exact(flow):
    keys, vals = wide_items()
    want = exact_sums(keys, vals)
    assert np.abs(want).max() > 2 ** 32  # int32 sums would wrap
    mr = MapReduce(wide_app(), flow=flow)
    res = mr.run((jnp.asarray(keys), jnp.asarray(vals)))
    total, triple, count = (np.asarray(a) for a in res.values)
    assert total.dtype == np.int64 and triple.dtype == np.int64
    np.testing.assert_array_equal(total, want)
    np.testing.assert_array_equal(triple, 3 * want)
    np.testing.assert_array_equal(np.asarray(res.counts),
                                  np.bincount(keys, minlength=K))
    assert res.keys.dtype == jnp.int32 and res.counts.dtype == jnp.int32
    assert not jax.config.jax_enable_x64  # no global flag was set


def test_wide_job_is_counted_and_keyed_by_width():
    before = pc.stats_snapshot()["wide_jobs"]
    wide = MapReduce(wide_app(), flow="stream")
    narrow_app = wide_app()
    narrow_app.value_aval = jax.ShapeDtypeStruct((), jnp.int32)
    narrow = MapReduce(narrow_app, flow="stream")
    assert wide_job(wide.app) and not wide_job(narrow.app)
    assert pc.stats_snapshot()["wide_jobs"] == before + 1
    assert wide._plan_key != narrow._plan_key
    # two int64 sums and the int32 count per pair; the narrow job's
    # sums are int32
    assert pc.STATS.holder_bytes[wide._plan_key] == 8 + 8 + 4
    assert pc.STATS.holder_bytes[narrow._plan_key] == 4 + 4 + 4


def test_wide_derivation_probes_keep_64_bits():
    """The validation probes run on int64 values: a reduce whose result
    depends on bits above 31 still derives its combiner."""
    def reduce(k, v, c):
        return jnp.sum(v * (1 << 33)) >> 33

    mr = MapReduce(wide_app(reduce), flow="auto")
    assert mr.plan.flow == "stream", mr.explain()
    keys, vals = wide_items(500, seed=3)
    res = mr.run((jnp.asarray(keys), jnp.asarray(vals)))
    want = (exact_sums(keys, vals) * (1 << 33)) >> 33  # wraps mod 2^64
    np.testing.assert_array_equal(np.asarray(res.values), want)


@pytest.mark.parametrize("n", [1, 2048, 5000])
def test_limb_sums_wrap_as_int64_sums(n):
    """The one-hot fold's int32 limbs give the int64 per-key sums of any
    values, wrapped modulo 2^64 as a native 64-bit sum wraps."""
    rng = np.random.default_rng(n)
    info = np.iinfo(np.int64)
    flat = rng.integers(info.min, info.max, (n, 3), dtype=np.int64)
    flat[0] = info.max
    keys = rng.integers(0, K + 1, n)  # K: the sentinel, dropped
    want = np.zeros((K, 3), np.int64)
    with np.errstate(over="ignore"):
        np.add.at(want, keys[keys < K], flat[keys < K])
    with jax.enable_x64(True):
        def contract(m):
            return jnp.einsum("nk,nd->kd",
                              jax.nn.one_hot(keys, K, dtype=m.dtype), m)
        got = col._wide_sums(contract, jnp.asarray(flat))
    np.testing.assert_array_equal(np.asarray(got), want)


def _hlo_lines(text: str, op: str) -> list[str]:
    return [ln for ln in text.splitlines() if f" {op}(" in ln]


@pytest.mark.parametrize("flow", ["stream", "sort"])
def test_wide_lowering_keeps_the_engines_arrays_32_bit(flow):
    """The wide job's module: no f64 at all; every iota (key ids,
    positions, masks) is s32 and no tensor of 64-bit integers is compared,
    so keys, counts and masks stay 32-bit; the result's keys and counts
    are s32 and only the value columns are s64."""
    items = (jax.ShapeDtypeStruct((5000,), jnp.int32),
             jax.ShapeDtypeStruct((5000,), jnp.int32))
    compiled = MapReduce(wide_app(), flow=flow).lower(items).compile()
    text = compiled.as_text()
    assert not re.search(r"\bf64\[", text)
    assert re.search(r"\bs64\[", text)  # the declared columns are wide
    for ln in _hlo_lines(text, "iota"):
        assert re.search(r"= s32\[", ln), ln
    for ln in _hlo_lines(text, "compare"):
        assert not re.search(r"compare\([su]64\[\d", ln), ln
    out = re.search(r"ENTRY .*-> \((.*)\) \{", text).group(1)
    assert out.startswith("s32[8]") and out.endswith("s32[8]"), out
    assert trace.PREMAP in text  # the derived premap's device scope


def test_narrow_lowering_has_no_64_bit_type():
    app = make_app(
        map_fn=lambda item, emit: emit(item, jnp.ones_like(item)),
        reduce_fn=lambda k, v, c: jnp.sum(v),
        key_space=K, value_aval=jax.ShapeDtypeStruct((), jnp.int32),
        emit_capacity=4)
    items = jax.ShapeDtypeStruct((4096, 4), jnp.int32)
    for flow in ("stream", "sort"):
        text = MapReduce(app, flow=flow).lower(items).compile().as_text()
        assert not re.search(r"\b(s64|u64|f64)\[", text), flow


@pytest.mark.parametrize("window", [None, sliding(4, 2)],
                         ids=["global", "sliding"])
def test_wide_service_is_exact(window):
    """``serve()`` of a wide job: micro-batches fold, merge and snapshot
    in 64 bits, never wrapped at 32."""
    keys, vals = wide_items(64 * 6, seed=5)
    mr = MapReduce(wide_app(), streaming=True)
    svc = mr.serve(batch_capacity=64, window=window)
    for b in range(6):
        sl = slice(64 * b, 64 * (b + 1))
        svc.ingest((jnp.asarray(keys[sl]), jnp.asarray(vals[sl])))
    snap = svc.snapshot()
    live = slice(64 * 2, None) if window is not None else slice(None)
    want = exact_sums(keys[live], vals[live])
    total = np.asarray(snap.values[0])
    assert total.dtype == np.int64
    np.testing.assert_array_equal(total, want)
    np.testing.assert_array_equal(
        np.asarray(snap.counts), np.bincount(keys[live], minlength=K))
