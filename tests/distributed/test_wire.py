"""The shuffle wire layer (distributed/wire.py).

Contracts under test:

* encode∘decode == identity for every codec on int-exact value specs —
  including skewed, empty, and capacity-boundary buckets, and hot-key
  symbols under a skew plan (hypothesis properties);
* the ``delta`` codec reproduces the RAW buckets bitwise (keys and value
  slots untouched), which is what makes every downstream flow
  bit-identical under it;
* the byte accounting (``encoded_nbytes``) matches the real encoded tree
  leaf for leaf, and the cost model's wire term equals those bytes over
  the link bandwidth;
* the resilient driver's checkpointed shard partials ARE the wire
  layer's encoding (satellite bugfix: one source of truth for the send
  buckets) — asserted bitwise against the npz trees on disk;
* a kill/restore drill under ``wire="delta"`` stays bitwise with the raw
  run, restoring compressed partials from disk;
* a checkpoint written under a DIFFERENT codec (or a foreign layout) is
  rejected at restore and the shard recomputes — never silently merged.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint import ckpt
from repro.core import (ExecutionOptions, MapReduce, MapReduceApp,
                        ShuffleOptions)
from repro.core import engine as eng
from repro.core import skew
from repro.core.plan import plan_execution
from repro.core import collector as col
from repro.distributed import fault as flt
from repro.distributed import wire

I32 = jnp.int32


def make_app(key_space, *, emit=4, dtype=I32):
    class App(MapReduceApp):
        pass

    app = App()
    app.key_space = key_space
    app.value_aval = jax.ShapeDtypeStruct((), dtype)
    app.max_values_per_key = 4096
    app.emit_capacity = emit
    app.map = lambda item, emit_: emit_(item, jnp.ones_like(item))
    app.reduce = lambda k, v, c: jnp.sum(v)
    return app


def make_stream(keys, values, key_space):
    return col.PairStream(jnp.asarray(keys, I32), jnp.asarray(values),
                          key_space)


def roundtrip(fmt, sk, sv):
    """Encode then decode each destination's own row — the receive side
    of a loopback all-to-all.  Returns [S, B]-shaped buckets (decode
    keeps the leading source axis)."""
    enc = wire.encode(fmt, sk, sv)
    ks, vs = [], []
    for d in range(fmt.num_shards):
        renc = jax.tree.map(lambda v, d=d: v[d:d + 1], enc)
        k, v = wire.decode(fmt, renc, d)
        ks.append(k)
        vs.append(v)
    keys = jnp.concatenate(ks)
    vals = jax.tree.map(lambda *ls: jnp.concatenate(ls), *vs)
    return keys, vals


def assert_trees_equal(a, b):
    la, lb = jax.tree.leaves(a), jax.tree.leaves(b)
    assert len(la) == len(lb)
    for x, y in zip(la, lb):
        assert np.array_equal(np.asarray(x), np.asarray(y)), (x, y)


# ---------------------------------------------------------------------------
# format resolution
# ---------------------------------------------------------------------------


def test_capacity_resolution_chain():
    assert wire.resolve_capacity(100, 4) == eng.shuffle_bucket_capacity(
        100, 4) == 50
    assert wire.resolve_capacity(100, 4, capacity=7) == 7
    plan = skew.ShufflePlan(key_space=16, num_shards=4,
                            boundaries=(0, 4, 8, 12, 16), max_dest_frac=0.9)
    assert wire.resolve_capacity(100, 4, plan=plan) == plan.capacity_for(100)
    # explicit beats the plan
    assert wire.resolve_capacity(100, 4, capacity=7, plan=plan) == 7


def test_wire_format_rejects_unknown_codec():
    with pytest.raises(ValueError, match="unknown wire codec"):
        wire.WireFormat(codec="zstd", num_shards=2, capacity=4,
                        key_space=8, lo=(0, 4), span=4)


def test_epoch_fingerprints_full_layout():
    base = dict(codec="raw", num_shards=2, capacity=4, key_space=8,
                lo=(0, 4), span=4)
    f = wire.WireFormat(**base)
    assert f.epoch != 0
    for change in (dict(codec="delta"), dict(capacity=8),
                   dict(hot_keys=(3,)), dict(plan_epoch=1),
                   dict(value_leaves=(("int16", 1),))):
        g = dataclasses.replace(f, **change)
        assert g.epoch != f.epoch, change


def test_delta_bits_static_width():
    f = wire.WireFormat(codec="delta", num_shards=2, capacity=4,
                        key_space=8, lo=(0, 4), span=4)
    # span 4 + 0 hot + sentinel = 5 symbols -> 3 bits
    assert f.delta_bits == 3
    assert f.packed_row_bytes == -(-4 * 3 // 8)


# ---------------------------------------------------------------------------
# codec round-trips
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("codec", wire.CODECS)
def test_roundtrip_simple(codec):
    K, S = 32, 4
    keys = np.array([0, 5, 9, 17, 25, 31, 8, 8], np.int32)
    vals = np.arange(8, dtype=np.int32) - 3
    stream = make_stream(keys, vals, K)
    fmt = wire.wire_format(key_space=K, num_shards=S, n_pairs=8,
                           value_avals=stream.values, codec=codec)
    sk_, sv, _ = wire.bucketize(fmt, stream)
    k, v = roundtrip(fmt, sk_, sv)
    assert np.array_equal(np.asarray(k), np.asarray(sk_))
    assert np.array_equal(np.asarray(v), np.asarray(sv))


@pytest.mark.parametrize("codec", ("delta", "packed"))
def test_roundtrip_empty_and_capacity_boundary(codec):
    K, S = 16, 4
    # empty: every key invalid (sentinel) -> all-pad buckets round-trip
    stream = make_stream(np.full(8, K, np.int32),
                         np.zeros(8, np.int32), K)
    fmt = wire.wire_format(key_space=K, num_shards=S, n_pairs=8,
                           value_avals=stream.values, codec=codec)
    sk_, sv, overflow = wire.bucketize(fmt, stream)
    assert int(overflow) == 0
    k, v = roundtrip(fmt, sk_, sv)
    assert np.array_equal(np.asarray(k), np.asarray(sk_))

    # capacity boundary: B pairs on one dest fit exactly; B+1 overflows
    B = fmt.capacity
    keys = np.zeros(B, np.int32)
    stream = make_stream(keys, np.arange(B, dtype=np.int32), K)
    fmt2 = wire.wire_format(key_space=K, num_shards=S, n_pairs=B,
                            value_avals=stream.values, codec=codec,
                            capacity=B)
    sk_, sv, overflow = wire.bucketize(fmt2, stream)
    assert int(overflow) == 0
    k, v = roundtrip(fmt2, sk_, sv)
    assert np.array_equal(np.asarray(k), np.asarray(sk_))
    stream = make_stream(np.zeros(B + 1, np.int32),
                         np.arange(B + 1, dtype=np.int32), K)
    fmt3 = wire.wire_format(key_space=K, num_shards=S, n_pairs=B + 1,
                            value_avals=stream.values, codec=codec,
                            capacity=B)
    _, _, overflow = wire.bucketize(fmt3, stream)
    assert int(overflow) == 1


def test_roundtrip_hot_key_symbols():
    """Hot split keys route OUTSIDE their owner's range; the delta codec
    gives them reserved symbols past the span and must still reproduce
    the raw buckets bitwise."""
    K, S = 64, 4
    plan = skew.ShufflePlan(key_space=K, num_shards=S,
                            boundaries=(0, 16, 32, 48, 64),
                            hot_keys=(3,), hot_ways=(4,))
    rng = np.random.default_rng(0)
    keys = rng.integers(0, K, 64).astype(np.int32)
    keys[::2] = 3  # heavy hot key, round-robined over all 4 dests
    stream = make_stream(keys, np.ones(64, np.int32), K)
    raw_fmt = wire.wire_format(key_space=K, num_shards=S, n_pairs=64,
                               value_avals=stream.values, codec="raw",
                               plan=plan)
    fmt = dataclasses.replace(raw_fmt, codec="delta")
    sk_raw, sv_raw, _ = wire.bucketize(raw_fmt, stream, plan)
    sk_, sv, _ = wire.bucketize(fmt, stream, plan)
    assert np.array_equal(np.asarray(sk_), np.asarray(sk_raw))
    k, v = roundtrip(fmt, sk_, sv)
    assert np.array_equal(np.asarray(k), np.asarray(sk_raw))
    assert np.array_equal(np.asarray(v), np.asarray(sv_raw))


def test_bucketize_rejects_foreign_plan():
    K, S = 64, 4
    p1 = skew.ShufflePlan(key_space=K, num_shards=S,
                          boundaries=(0, 16, 32, 48, 64))
    p2 = skew.ShufflePlan(key_space=K, num_shards=S,
                          boundaries=(0, 8, 32, 48, 64))
    stream = make_stream(np.zeros(8, np.int32), np.ones(8, np.int32), K)
    fmt = wire.wire_format(key_space=K, num_shards=S, n_pairs=8,
                           value_avals=stream.values, plan=p1)
    with pytest.raises(ValueError, match="not the one this WireFormat"):
        wire.bucketize(fmt, stream, p2)


def test_packed_float_values_quantize_within_bound():
    """packed float values are an explicit lossy opt-in: per-destination
    int8 quantization with error <= scale/2 (the compression.py bound)."""
    K, S = 16, 2
    rng = np.random.default_rng(3)
    keys = rng.integers(0, K, 32).astype(np.int32)
    vals = rng.standard_normal(32).astype(np.float32)
    stream = make_stream(keys, vals, K)
    fmt = wire.wire_format(key_space=K, num_shards=S, n_pairs=32,
                           value_avals=stream.values, codec="packed")
    sk_, sv, _ = wire.bucketize(fmt, stream)
    k, v = roundtrip(fmt, sk_, sv)
    assert np.array_equal(np.asarray(k), np.asarray(sk_))
    got = np.asarray(v).reshape(fmt.num_shards, fmt.capacity)
    want = np.asarray(sv)
    for d in range(S):
        scale = max(np.abs(want[d]).max(), 1e-12) / 127.0
        assert np.abs(got[d] - want[d]).max() <= scale / 2 + 1e-7


try:
    from hypothesis import given, settings, strategies as st
    HAVE_HYP = True
except ImportError:  # pragma: no cover
    HAVE_HYP = False


if HAVE_HYP:
    @settings(max_examples=40, deadline=None)
    @given(
        codec=st.sampled_from(("delta", "packed")),
        key_space=st.integers(2, 200),
        num_shards=st.integers(1, 9),
        n=st.integers(1, 64),
        skewed=st.booleans(),
        seed=st.integers(0, 2 ** 16),
    )
    def test_roundtrip_property(codec, key_space, num_shards, n, skewed,
                                seed):
        """encode∘decode == identity on int-exact specs, for uniform and
        skewed buckets, any (K, S, N) shape."""
        rng = np.random.default_rng(seed)
        keys = rng.integers(0, key_space, n).astype(np.int32)
        if skewed:
            keys[: n // 2 + 1] = int(keys[0])  # half the mass on one key
        keys[rng.random(n) < 0.1] = key_space  # some invalid pairs
        vals = rng.integers(-100, 101, n).astype(np.int32)  # int8-exact
        stream = make_stream(keys, vals, key_space)
        fmt = wire.wire_format(key_space=key_space, num_shards=num_shards,
                               n_pairs=n, value_avals=stream.values,
                               codec=codec, capacity=n)
        sk_, sv, overflow = wire.bucketize(fmt, stream)
        assert int(overflow) == 0  # capacity=n always fits
        k, v = roundtrip(fmt, sk_, sv)
        assert np.array_equal(np.asarray(k), np.asarray(sk_))
        assert np.array_equal(np.asarray(v), np.asarray(sv))

    @settings(max_examples=25, deadline=None)
    @given(
        codec=st.sampled_from(wire.CODECS),
        key_space=st.integers(2, 200),
        num_shards=st.integers(1, 9),
        n=st.integers(1, 64),
        seed=st.integers(0, 2 ** 16),
    )
    def test_encoded_nbytes_matches_real_tree(codec, key_space, num_shards,
                                              n, seed):
        rng = np.random.default_rng(seed)
        stream = make_stream(rng.integers(0, key_space, n).astype(np.int32),
                             rng.integers(-100, 101, n).astype(np.int32),
                             key_space)
        fmt = wire.wire_format(key_space=key_space, num_shards=num_shards,
                               n_pairs=n, value_avals=stream.values,
                               codec=codec)
        sk_, sv, _ = wire.bucketize(fmt, stream)
        enc = wire.encode(fmt, sk_, sv)
        assert wire.encoded_nbytes(fmt) == wire.tree_nbytes(enc)


# ---------------------------------------------------------------------------
# byte accounting + cost-model wire term
# ---------------------------------------------------------------------------


def test_delta_shrinks_wire_bytes():
    fmt = wire.wire_format(key_space=8192, num_shards=16, n_pairs=4096,
                           value_avals=jax.ShapeDtypeStruct((4096,),
                                                            jnp.int16),
                           codec="delta")
    assert wire.encoded_nbytes(fmt) < wire.raw_nbytes(fmt)
    # int16 values: 10-bit residuals vs 32-bit keys -> well under 0.6x
    ratio = wire.encoded_nbytes(fmt) / wire.raw_nbytes(fmt)
    assert ratio <= 0.6, ratio


def test_cost_model_wire_term_matches_wire_layer():
    from repro.core import cost_model as cm
    from repro.roofline import analysis as roofline

    n, K, S = 8192, 1024, 16
    for codec in wire.CODECS:
        fc = cm.estimate_flow_cost("sort", n_pairs=n, key_space=K,
                                   num_shards=S, wire=codec)
        per = -(-n // S)
        fmt = wire.wire_format(
            key_space=K, num_shards=S, n_pairs=per,
            value_avals=jax.ShapeDtypeStruct((per, 1), jnp.int32),
            codec=codec)
        want = wire.wire_bytes_per_shard(fmt) * cm.CPU_COEFF["wire"]
        assert dict(fc.terms)["wire"] == pytest.approx(want)
        assert roofline.shuffle_wire_bytes(
            codec, n_pairs=n, key_space=K,
            num_shards=S) == pytest.approx(wire.wire_bytes_per_shard(fmt))
    # the stream flow has no shuffle: no wire term
    fc = cm.estimate_flow_cost("stream", n_pairs=n, key_space=K,
                               num_shards=S, wire="delta")
    assert "wire" not in dict(fc.terms)


# ---------------------------------------------------------------------------
# resilient partials == the wire encoding (the satellite bugfix)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("codec", ("raw", "delta"))
def test_checkpointed_partials_are_wire_encoding(tmp_path, codec):
    """The resilient driver's durable shard partials must be EXACTLY the
    wire layer's encoding of that shard's send buckets — one source of
    truth (previously engine._shuffle_pairs and run_resilient each built
    buckets with separately-maintained capacity plumbing)."""
    K, S = 64, 4
    app = make_app(K, emit=4)
    rng = np.random.default_rng(7)
    items = jnp.asarray(rng.integers(0, K, (16, 4)).astype(np.int32))
    plan = plan_execution(app, flow="sort")
    d = str(tmp_path / codec)
    eng.run_resilient(app, plan, items, num_hosts=S, ckpt_dir=d,
                      wire=codec)

    # rebuild shard 0's buckets through the wire layer directly
    per = items.shape[0] // S
    stream = eng.map_phase(app, items[:per])
    fmt = wire.wire_format(key_space=K, num_shards=S,
                           n_pairs=stream.keys.shape[0],
                           value_avals=stream.values, codec=codec)
    sk_, sv, overflow = wire.bucketize(fmt, stream)
    want = {"wire": wire.encode(fmt, sk_, sv), "overflow": overflow,
            "wire_epoch": jnp.full((1,), fmt.epoch, jnp.uint32)}
    got, step = ckpt.restore(ckpt.shard_partial_dir(d, 0), want, step=0)
    assert_trees_equal(got, want)


def test_resilient_delta_kill_restore_bitwise(tmp_path):
    """Kill/restore drill under wire='delta': recovery restores the
    COMPRESSED partials from disk and the answer stays bitwise the raw
    fault-free run."""
    K = 128
    app = make_app(K, emit=8)
    keys = np.array(np.random.default_rng(5).zipf(1.1, (64, 8)) % K)
    items = jnp.asarray(keys, I32)
    # zipf keys overflow the 2x-uniform envelope: provision the full
    # per-shard pair count so the drill compares complete answers
    opts = ExecutionOptions(num_hosts=8, num_shards=8,
                            shuffle=ShuffleOptions(wire="raw", capacity=64))
    mr = MapReduce(app, flow="sort", cache=False)
    base = mr.run_resilient(items, options=opts)

    dopts = ExecutionOptions(
        num_hosts=8, num_shards=8, ckpt_dir=str(tmp_path),
        shuffle=ShuffleOptions(wire="delta", capacity=64))
    mr2 = MapReduce(app, flow="sort", cache=False)
    mr2.run_resilient(items, options=dopts)  # seed compressed checkpoints
    drill = mr2.run_resilient(items, options=dataclasses.replace(
        dopts, inject=flt.FaultInjection(dead_hosts=(3,),
                                         die_after_shards=0)))
    assert np.array_equal(np.asarray(drill.values), np.asarray(base.values))
    assert np.array_equal(np.asarray(drill.counts), np.asarray(base.counts))
    assert drill.recovery.restored, drill.recovery.summary()


def test_codec_change_rejected_at_restore(tmp_path):
    """A partial checkpointed under a DIFFERENT wire codec must never be
    merged (its bytes mean different things): the wire epoch rejects it
    and the shard recomputes — the answer stays exact."""
    K = 64
    app = make_app(K, emit=4)
    rng = np.random.default_rng(9)
    items = jnp.asarray(rng.integers(0, K, (32, 4)).astype(np.int32))

    def run(codec, inject=None):
        mr = MapReduce(app, flow="sort", cache=False)
        return mr.run_resilient(items, options=ExecutionOptions(
            num_hosts=4, num_shards=8, ckpt_dir=str(tmp_path),
            inject=inject, shuffle=ShuffleOptions(wire=codec, capacity=32)))

    base = run("raw")  # seeds raw-codec checkpoints for every shard
    drill = run("delta", inject=flt.FaultInjection(dead_hosts=(1,),
                                                   die_after_shards=1))
    assert np.array_equal(np.asarray(drill.values), np.asarray(base.values))
    assert np.array_equal(np.asarray(drill.counts), np.asarray(base.counts))
    # the shard host 1 completed BEFORE dying was checkpointed under
    # delta by the drill itself and restores fine; the one it never
    # reached only has the seeded raw partial, which must be rejected
    assert drill.recovery.epoch_rejects, drill.recovery.summary()


def test_stale_layout_structure_rejected_at_restore(tmp_path):
    """A partial whose npz leaf STRUCTURE no longer matches (e.g. written
    under the packed codec, restored under raw) is caught by the restore
    guard — rejected with a recompute, not a crash or a silent misread."""
    K = 64
    app = make_app(K, emit=4, dtype=jnp.float32)
    app.map = lambda item, emit_: emit_(
        item, jnp.ones_like(item, jnp.float32))
    rng = np.random.default_rng(11)
    items = jnp.asarray(rng.integers(0, K, (32, 4)).astype(np.int32))

    def run(codec, inject=None):
        mr = MapReduce(app, flow="sort", cache=False)
        return mr.run_resilient(items, options=ExecutionOptions(
            num_hosts=4, num_shards=8, ckpt_dir=str(tmp_path),
            inject=inject, shuffle=ShuffleOptions(wire=codec, capacity=32)))

    run("packed")  # float values -> extra per-dest scales leaf on disk
    base_mr = MapReduce(app, flow="sort", cache=False)
    base = base_mr.run_resilient(items, options=ExecutionOptions(
        num_hosts=4, num_shards=8,
        shuffle=ShuffleOptions(wire="raw", capacity=32)))
    drill = run("raw", inject=flt.FaultInjection(dead_hosts=(1,),
                                                 die_after_shards=1))
    assert np.array_equal(np.asarray(drill.values), np.asarray(base.values))
    assert drill.recovery.epoch_rejects, drill.recovery.summary()


# ---------------------------------------------------------------------------
# plan provenance
# ---------------------------------------------------------------------------


def test_explain_shows_wire_codec_and_bytes():
    K = 1024
    app = make_app(K, emit=8)
    rng = np.random.default_rng(0)
    items = jnp.asarray(rng.integers(0, K, (64, 8)).astype(np.int32))
    mr = MapReduce(app, cache=False)
    low = mr.lower(items, options=ExecutionOptions(
        num_hosts=16, shuffle=ShuffleOptions(wire="delta")),
        mode="resilient")
    text = low.mr.plan.explain()
    assert "wire: codec delta" in text
    assert "x raw" in text  # modeled encoded-vs-raw bytes line
