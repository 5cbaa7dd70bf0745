"""Intermediate (key, value) collectors — the two execution flows.

Paper §2.4/§3.1: MR4J's collector is a thread-safe hash table; a new key
instantiates a new value *list* (reduce flow) or a new *holder* (combine
flow).  The TPU-native equivalents:

* :func:`reduce_flow`  — **materializing collector**: the full pair stream is
  written out, sorted by key, grouped, and the user reduce is applied per key
  over gathered padded windows.  Costs O(N) pair buffer + a sort + an
  O(K·Lmax) window gather — the HBM analogue of the JVM heap pressure the
  paper measures in Figs 8/9.

* :func:`combine_flow` — **combining collector**: each emitted value is folded
  into a per-key holder table at emit time.  O(K) state, single pass, no sort,
  no reduce phase.  Lowers to (in preference order)
    - MXU one-hot matmul      (additive monoids, small key space),
    - ``table.at[keys].op()`` scatter-combine (any scatter monoid),
    - vectorized first-occurrence gather (the first-element idiom),
    - sorted segment fold     (generic streaming combiners, e.g. scan folds).

Keys are dense int32 ids in ``[0, key_space)``; invalid emissions use the
sentinel ``key_space`` and are dropped by out-of-bounds scatter semantics.
"""

from __future__ import annotations

import dataclasses
import warnings
from functools import partial
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from repro.core import combiner as C
from repro.core import plan_cache, trace

SENTINEL = "sentinel"  # invalid-pair key == key_space

#: legacy one-hot key-space cutoff for the single-shot combine flow (and
#: the onehot_combine kernel's VMEM-resident table envelope); the default
#: for ``combine_flow(onehot_max_keys=...)``.
ONEHOT_MAX_KEYS = 2048


class LoweringFallbackWarning(UserWarning):
    """A collector lowering silently available in principle was not taken.

    Emitted (at trace time, once per compilation) when an MXU-lowerable
    combiner degrades to the exact-scatter fallback — the optimizer's plan
    records the same decision so ``MapReduce.explain()`` shows it."""


def _emit_fallback(msg: str, on_fallback: Callable | None,
                   stacklevel: int = 3) -> None:
    """Route a fallback diagnostic to ``on_fallback`` when given, else warn.

    The engine passes a per-plan callback that warns ONCE per plan and
    appends every message to the plan's diagnostic list, so re-traces of
    the same plan (every chunked scan body, each new input shape) no
    longer spam one :class:`LoweringFallbackWarning` per trace while the
    plan record stays complete."""
    if on_fallback is not None:
        on_fallback(msg)
    else:
        warnings.warn(msg, LoweringFallbackWarning, stacklevel=stacklevel)


@dataclasses.dataclass(frozen=True)
class PairStream:
    """Flat emitted pairs. keys[i] == key_space marks an invalid slot."""

    keys: jax.Array  # [N] int32 in [0, key_space]
    values: jax.Array  # [N, *value_shape]
    key_space: int

    @property
    def valid(self) -> jax.Array:
        return self.keys < self.key_space


@dataclasses.dataclass(frozen=True)
class Grouped:
    """Result table over the dense key space."""

    keys: jax.Array  # [K] == arange(K)
    values: Any  # [K, *out_shape] (pytree)
    counts: jax.Array  # [K] int32; count == 0 -> key never emitted


# ---------------------------------------------------------------------------
# Reduce flow (baseline; the paper's un-optimized execution flow)
# ---------------------------------------------------------------------------


def reduce_flow(
    reduce_fn: Callable,
    stream: PairStream,
    *,
    max_values_per_key: int,
    pad_value,
) -> Grouped:
    """Materialize → sort → group → per-key reduce.

    ``max_values_per_key`` is the static bound Lmax on values per key (the
    paper's Phoenix buffers have the same role); counts are clipped to it.
    """
    K = stream.key_space
    Lmax = max_values_per_key
    keys = stream.keys
    values = stream.values
    n = keys.shape[0]

    order = jnp.argsort(keys)  # sentinel keys sort last
    skeys = keys[order]
    svals = jax.tree.map(lambda v: v[order], values)

    counts = jnp.bincount(keys, length=K + 1)[:K].astype(jnp.int32)
    offsets = jnp.concatenate([jnp.zeros((1,), jnp.int32),
                               jnp.cumsum(counts)[:-1].astype(jnp.int32)])

    def pad_tail(v):
        pad_shape = (Lmax,) + v.shape[1:]
        pad = jnp.full(pad_shape, pad_value, v.dtype)
        return jnp.concatenate([v, pad], axis=0)

    svals_p = jax.tree.map(pad_tail, svals)

    def one_key(k, off, cnt):
        def win_of(v):
            w = lax.dynamic_slice_in_dim(v, off, Lmax, axis=0)
            mask = (jnp.arange(Lmax) < cnt)
            bshape = (Lmax,) + (1,) * (w.ndim - 1)
            return jnp.where(mask.reshape(bshape), w,
                             jnp.asarray(pad_value, w.dtype))
        win = jax.tree.map(win_of, svals_p)
        cc = jnp.minimum(cnt, Lmax)
        return reduce_fn(k, win, cc)

    out = jax.vmap(one_key)(jnp.arange(K, dtype=jnp.int32), offsets, counts)
    return Grouped(jnp.arange(K, dtype=jnp.int32), out, counts)


# ---------------------------------------------------------------------------
# Combine flow (the optimizer's execution flow)
# ---------------------------------------------------------------------------


def _premap_stream(spec: C.CombinerSpec, values) -> tuple:
    """vmap the per-value premap over the pair stream."""
    with jax.named_scope(trace.PREMAP):
        return jax.vmap(spec.premap)(values)


def combine_scatter(spec: C.CombinerSpec, stream: PairStream) -> tuple[Any, jax.Array]:
    """Holder tables via ``table.at[keys].<monoid-op>`` scatter-combine."""
    assert spec.monoids is not None
    K = stream.key_space
    mapped = _premap_stream(spec, stream.values)
    leaf_avals = [jax.ShapeDtypeStruct(m.shape[1:], m.dtype) for m in mapped]
    tables = []
    for mono, chan, aval in zip(spec.monoids, mapped, leaf_avals):
        init = jnp.broadcast_to(mono.identity_like(aval), (K,) + tuple(aval.shape))
        upd = getattr(init.at[stream.keys], mono.scatter_method)
        tables.append(upd(chan, mode="drop"))
    counts = jnp.zeros((K,), jnp.int32).at[stream.keys].add(
        stream.valid.astype(jnp.int32), mode="drop")
    return tuple(tables), counts


def combine_onehot(
    spec: C.CombinerSpec,
    stream: PairStream,
    *,
    onehot_fn: Callable | None = None,
    block_pairs: int = 1024,
) -> tuple[Any, jax.Array]:
    """Additive monoids on the MXU: ``one_hot(keys)ᵀ @ premap(values)``.

    ``onehot_fn(keys, mat, K)`` may be the Pallas kernel (kernels/ops.py);
    defaults to a jnp einsum with the same semantics.
    """
    assert spec.mxu_lowerable
    K = stream.key_space
    mapped = _premap_stream(spec, stream.values)

    def default_onehot(keys, mat, k):
        oh = jax.nn.one_hot(keys, k, dtype=mat.dtype)  # sentinel -> all-zero
        return jnp.einsum("nk,nd->kd", oh, mat)

    tables = []
    for chan in mapped:
        if onehot_fn is not None:  # Pallas kernel contract is f32
            acc_dt = jnp.float32
        else:  # integer channels contract exactly in their own dtype
            acc_dt = (chan.dtype if jnp.issubdtype(chan.dtype, jnp.integer)
                      else jnp.float32)
        flat = chan.reshape(chan.shape[0], -1).astype(acc_dt)
        tab = (onehot_fn or default_onehot)(stream.keys, flat, K)
        tables.append(tab.reshape((K,) + chan.shape[1:]).astype(chan.dtype))
    if onehot_fn is not None:
        counts_chan = stream.valid.astype(jnp.float32)
        counts = onehot_fn(stream.keys, counts_chan[:, None],
                           K)[:, 0].astype(jnp.int32)
    else:
        counts = default_onehot(stream.keys,
                                stream.valid.astype(jnp.int32)[:, None],
                                K)[:, 0]
    return tuple(tables), counts


def combine_first(spec: C.CombinerSpec, stream: PairStream) -> tuple[Any, jax.Array]:
    """First-element idiom, vectorized: scatter-min of arrival order."""
    K = stream.key_space
    n = stream.keys.shape[0]
    mapped = _premap_stream(spec, stream.values)
    order = jnp.arange(n, dtype=jnp.int32)
    first_pos = jnp.full((K,), n, jnp.int32).at[stream.keys].min(
        order, mode="drop")
    safe = jnp.minimum(first_pos, n - 1)
    counts = jnp.zeros((K,), jnp.int32).at[stream.keys].add(
        stream.valid.astype(jnp.int32), mode="drop")
    tables = tuple(chan[safe] for chan in mapped)
    return tables, counts


def _sequential_fold(spec: C.CombinerSpec, tables, counts, keys, values
                     ) -> tuple[Any, jax.Array]:
    """Fold a pair stream into carried holder tables, one pair at a time.

    One ``lax.scan`` over the pairs; each step gathers the key's holder row,
    applies ``spec.combine`` and writes the row back (a dynamic-update-slice,
    in-place on TPU).  Correctness fallback for combiners with coupled
    holders (scan folds, logsumexp) that have no dense/monoid lowering.
    """
    K = counts.shape[0]

    def step(carry, xs):
        tables, counts = carry
        k, v = xs
        valid = k < K
        ks = jnp.minimum(k, K - 1)
        # holders live in the table: gather the key's holder, fold, write
        # back (sequential over the stream, so no conflicts).
        h = jax.tree.map(lambda t: t[ks], tables)
        nk = counts[ks]
        h2 = spec.combine(h, spec.premap(v), nk)
        tables = jax.tree.map(
            lambda t, new, old: t.at[ks].set(jnp.where(valid, new, old)),
            tables, h2, h)
        counts = counts.at[ks].add(valid.astype(jnp.int32))
        return (tables, counts), None

    (tables, counts), _ = lax.scan(step, (tables, counts), (keys, values))
    return tables, counts


def combine_segment(spec: C.CombinerSpec, stream: PairStream) -> tuple[Any, jax.Array]:
    """Generic streaming combiner: sort by key, sequential fold per segment.

    Correctness fallback for non-scatter combiners (scan folds, coupled
    holders).  One ``lax.scan`` over the sorted stream; holder written back
    on segment close.
    """
    order = jnp.argsort(stream.keys)
    skeys = stream.keys[order]
    svals = jax.tree.map(lambda v: v[order], stream.values)

    vaval = jax.tree.map(
        lambda v: jax.ShapeDtypeStruct(v.shape[1:], v.dtype), svals)
    tables0, counts0 = spec.init_tables(stream.key_space, vaval)
    return _sequential_fold(spec, tables0, counts0, skeys, svals)


@jax.named_scope(trace.FINALIZE)
def finalize_tables(spec: C.CombinerSpec, tables, counts, key_space: int) -> Grouped:
    keys = jnp.arange(key_space, dtype=jnp.int32)
    vals = jax.vmap(spec.finalize)(keys, tables, counts)
    return Grouped(keys, vals, counts)


def combine_flow(
    spec: C.CombinerSpec,
    stream: PairStream,
    *,
    impl: str = "auto",
    onehot_fn: Callable | None = None,
    onehot_max_keys: int = ONEHOT_MAX_KEYS,
    on_fallback: Callable | None = None,
) -> Grouped:
    """Run the combining collector with the best available lowering.

    One-hot eligibility: the legacy key-space cutoff (``K <=
    onehot_max_keys``, where materializing the ``[N, K]`` expansion is the
    combine flow's documented cost) — OR, new in PR 2, ANY key space while
    the pair count stays inside the fused-contraction regime
    (``N <= ADDITIVE_FOLD_PAIRS_FUSED``, where XLA keeps the one-hot
    on-chip), so large-K low-N workloads no longer silently hit the
    scatter fallback.  When neither holds, the single-shot combine flow
    cannot keep the expansion affordable (exactly what the chunked
    streaming flow fixes) and it degrades to scatter with a
    :class:`LoweringFallbackWarning`.
    """
    if impl == "auto":
        n = stream.keys.shape[0]
        # the fused-regime widening applies to the pure-JAX einsum only:
        # the legacy combine flow keeps the onehot_combine kernel under the
        # legacy cutoff (the chunked stream flow is the large-K kernel path)
        onehot_ok = (stream.key_space <= onehot_max_keys
                     or (onehot_fn is None
                         and n <= ADDITIVE_FOLD_PAIRS_FUSED))
        if spec.strategy == C.STRATEGY_SIZE:
            impl = "scatter"  # counts only; scatter path handles it
        elif spec.strategy == C.STRATEGY_FIRST:
            impl = "first"
        elif spec.mxu_lowerable and onehot_ok:
            # MXU-native for additive monoids; without a Pallas kernel the
            # jnp einsum default applies — still preferable to the scatter
            # path, which XLA:CPU serializes into a per-pair while loop.
            impl = "onehot"
        elif spec.scatter_lowerable:
            if spec.mxu_lowerable:
                if onehot_fn is not None:
                    reason = (f"key_space={stream.key_space} > "
                              f"{onehot_max_keys} exceeds the "
                              f"onehot_combine kernel's VMEM-resident "
                              f"table cutoff")
                else:
                    reason = (f"key_space={stream.key_space} > "
                              f"{onehot_max_keys} and {n} pairs exceed "
                              f"the fused one-hot contraction regime "
                              f"(N <= {ADDITIVE_FOLD_PAIRS_FUSED})")
                _emit_fallback(
                    f"combine flow: {reason}; degrading to the exact "
                    f"scatter fallback (serialized on XLA:CPU). The "
                    f"chunked stream flow keeps large pair streams on the "
                    f"one-hot path.", on_fallback)
            impl = "scatter"
        else:
            impl = "segment"

    if impl == "scatter":
        if spec.strategy == C.STRATEGY_SIZE:
            counts = jnp.zeros((stream.key_space,), jnp.int32).at[
                stream.keys].add(stream.valid.astype(jnp.int32), mode="drop")
            tables = ()
        else:
            tables, counts = combine_scatter(spec, stream)
    elif impl == "onehot":
        tables, counts = combine_onehot(spec, stream, onehot_fn=onehot_fn)
    elif impl == "first":
        tables, counts = combine_first(spec, stream)
    elif impl == "segment":
        tables, counts = combine_segment(spec, stream)
    else:
        raise ValueError(f"unknown combine impl {impl!r}")
    return finalize_tables(spec, tables, counts, stream.key_space)


# ---------------------------------------------------------------------------
# Streaming combine flow (map+combine fusion)
# ---------------------------------------------------------------------------


#: largest chunk_pairs × key_block masked expansion (mask elements) the
#: non-additive dense folds may materialize per chunk (64 MB at f32).  Key
#: blocking divides the expansion — a blocked fold materializes one
#: [chunk, key_block] mask at a time — so large-K apps stay on the dense
#: path by shrinking the block instead of regressing to serialized scatters.
DENSE_FOLD_ELEMS_BUDGET = 1 << 24

#: largest per-fold pair count for which the pure-JAX one-hot contraction
#: stays scatter-free AND on-chip: XLA's dot strength reduction keeps the
#: ``[N, K]`` one-hot fused into the contraction (never materialized in
#: HBM) while the reduction axis N is small — measured on XLA:CPU the
#: regime holds to N≈3072 for ANY key space and breaks at N=4096, where
#: the full expansion round-trips HBM.  The streaming flow's chunking is
#: what keeps every fold inside this regime (the legacy combine flow
#: cannot: it contracts all N pairs at once).  The Pallas kernels are
#: exempt — their one-hot tile is VMEM-resident by construction.
ADDITIVE_FOLD_PAIRS_FUSED = 2048


#: chip rates of the two exact lowerings of a pure-JAX additive fold on a
#: TPU v5e, per channel (values and counts each pay them), read from the
#: benchmark's traces.  An int32 one-hot contraction has no MXU path: it
#: runs as a compare-select-reduce on the vector unit, 4,194 ms a job for
#: 1.68e7 pairs x 131,072 keys in ``wc_large.batch``.  The scatter-add
#: costs 0.496 ms per 65,536-pair chunk in ``wc_large.ingest``.
TPU_ONEHOT_S_PER_PAIR_KEY = 1.9e-12
TPU_SCATTER_S_PER_PAIR = 7.6e-9

#: the key space above which a TPU folds additive holders by scatter-add:
#: the one-hot costs grow with K, the scatter's do not (4,000 keys).
TPU_SCATTER_MIN_KEYS = round(TPU_SCATTER_S_PER_PAIR
                             / TPU_ONEHOT_S_PER_PAIR_KEY)


def fold_platform() -> str:
    """Platform the folds are built for: the default devices' (as
    ``roofline.peaks`` reads the running device's kind)."""
    return jax.default_backend()


def scatter_fold_chosen(spec: C.CombinerSpec, key_space: int, *,
                        kernel_additive: bool = False) -> bool:
    """Whether the pure-JAX additive fold lowers to an exact scatter-add.

    On a TPU above :data:`TPU_SCATTER_MIN_KEYS` the one-hot contraction
    costs more per pair than the scatter.  XLA:CPU keeps the one-hot
    contraction, where the scatter is a serialized per-pair loop, and so
    does the Pallas fold kernel."""
    return (spec.mxu_lowerable and spec.scatter_lowerable
            and not kernel_additive and fold_platform() == "tpu"
            and key_space > TPU_SCATTER_MIN_KEYS)


def stream_mode(spec: C.CombinerSpec, *, dense_ok: bool = True,
                additive_ok: bool | None = None,
                scatter_additive: bool = False) -> str:
    """Pick the per-chunk fold lowering for the streaming collector.

    ``dense_ok`` gates the masked-expansion folds (max/min/mul/bool);
    ``additive_ok`` gates the one-hot matmul fold (defaults to ``dense_ok``
    for backward compatibility — the budgets differ, see above);
    ``scatter_additive`` folds additive specs by scatter-add
    (:func:`scatter_fold_chosen`).
    """
    if additive_ok is None:
        additive_ok = dense_ok
    if spec.strategy == C.STRATEGY_SIZE:
        return "size"
    if spec.strategy == C.STRATEGY_FIRST:
        return "first"
    if spec.mxu_lowerable and scatter_additive:
        return "scatter"
    if spec.mxu_lowerable and additive_ok:
        return "additive"
    if spec.scatter_lowerable:
        return "dense" if dense_ok else "scatter"
    return "sequential"


def is_wide(dtype) -> bool:
    """A 64-bit integer column (a wide job's values and holders)."""
    return (jnp.issubdtype(dtype, jnp.integer)
            and jnp.dtype(dtype).itemsize == 8)


def _wide_sums(contract: Callable, flat: jax.Array) -> jax.Array:
    """``contract``'s per-key sums of a 64-bit integer ``[n, D]`` matrix,
    exact, from int32 contractions alone.

    A TPU has no 64-bit contraction (XLA:TPU refuses an s64 ``dot``), so
    each value splits into int32 limbs of ``w = 31 - bit_length(n)`` bits:
    the low limbs unsigned, the top one keeping the sign.  ``n`` limbs of
    under ``2^w`` each sum below ``2^31``, so one int32 contraction of
    every limb column is exact; the limb sums are then shifted back into
    place once, in 64 bits.  The result is the 64-bit sum modulo
    ``2^64``, as a native 64-bit sum would wrap."""
    n, d = flat.shape
    w = 31 - max(n, 1).bit_length()
    n_limbs = -(-64 // w)
    mask = (1 << w) - 1
    limbs = [((flat >> (w * i)) & mask) if i < n_limbs - 1
             else flat >> (w * i) for i in range(n_limbs)]
    sums = contract(jnp.concatenate(limbs, axis=1).astype(jnp.int32))
    total = jnp.zeros(sums.shape[:1] + (d,), flat.dtype)
    for i in range(n_limbs):
        total += sums[:, i * d:(i + 1) * d].astype(flat.dtype) << (w * i)
    return total


def pow2_floor(x: int) -> int:
    """Largest power of two <= max(x, 1)."""
    return 1 << (max(int(x), 1).bit_length() - 1)


def choose_dense_key_block(key_space: int, chunk_pairs: int | None,
                     *, budget: int = DENSE_FOLD_ELEMS_BUDGET) -> int:
    """Largest power-of-two key block whose ``chunk × block`` masked
    expansion fits ``budget``; ``key_space`` itself when no blocking is
    needed.  Floor of 8 keys (the masked fold needs a non-trivial tile)."""
    if chunk_pairs is None or chunk_pairs * key_space <= budget:
        return key_space
    return pow2_floor(max(budget // max(chunk_pairs, 1), 8))


class StreamCombiner:
    """Chunked fold of a pair stream into carried holder tables.

    The engine's streaming flow threads ``state`` through a ``lax.scan`` over
    map chunks; :meth:`fold_chunk` folds one chunk's emitted pairs into the
    state.  The emitted-pair buffer therefore only ever exists one chunk at a
    time — the fused version of the paper's combining collector ("the combine
    happens at emit time"), which is what un-inverts the Figs 8/9 bytes
    story: the legacy combine flow materialized the full ``N × capacity``
    pair buffer before folding.

    Per-chunk lowerings (dense/scatter-free wherever the chunk × key-space
    expansion fits :data:`DENSE_FOLD_ELEMS_BUDGET` — a per-pair table
    scatter is what XLA:CPU serializes into an O(N·K)-bytes while loop):

    * additive — one fused ``one_hot(keys)ᵀ @ [channels | 1]`` matmul per
      chunk into an f32 accumulator ``[K, ΣD + 1]``; the trailing ones
      column carries the counts, so the one-hot matrix is touched once.
      ``fold_fn(keys, mat, acc)`` may be the Pallas grid-accumulation kernel
      (kernels/ops.onehot_fold); defaults to a pure-JAX dot (CPU fallback).
    * dense    — per-monoid identity-masked reduction over the chunk axis,
      merged into the tables with the monoid op (max/min/mul/bool).
      ``monoid_fold_fn(keys, mat, acc, op)`` may be the Pallas chunk kernel.
    * first    — vectorized first-occurrence gather, kept only where the
      carried count is still zero.
    * size     — counts only.
    * scatter  — exact ``table.at[keys].<op>`` folds of every leaf and of
      the counts.  The lowering of pure-JAX additive folds on a TPU above
      :data:`TPU_SCATTER_MIN_KEYS` (:func:`scatter_fold_chosen`);
      elsewhere selected only when the scatter-free lowerings cannot stay
      on-chip (pure-JAX additive folds: ``chunk`` beyond
      :data:`ADDITIVE_FOLD_PAIRS_FUSED` — the Pallas kernel path has no
      such limit; masked folds: ``chunk × key_block`` beyond
      :data:`DENSE_FOLD_ELEMS_BUDGET` at the minimum block).  Emits
      :class:`LoweringFallbackWarning` when an MXU-lowerable spec degrades
      this way.
    * sequential — per-pair gather/combine/write-back scan (coupled holders).

    ``key_block`` partitions the ``[K, D]`` holder tables into
    ``ceil(K / key_block)`` key blocks: the dense folds materialize (and the
    Pallas kernels keep VMEM-resident) one block at a time, so large key
    spaces keep the scatter-free lowerings.  ``None`` means unblocked.
    ``mode`` forces a specific fold lowering (benchmark A/B hook).
    """

    def __init__(self, spec: C.CombinerSpec, key_space: int, value_aval,
                 *, fold_fn: Callable | None = None,
                 monoid_fold_fn: Callable | None = None,
                 chunk_pairs: int | None = None,
                 key_block: int | None = None,
                 mode: str | None = None,
                 on_fallback: Callable | None = None):
        self.spec = spec
        self.key_space = key_space
        self.value_aval = value_aval
        self.fold_fn = fold_fn
        self.monoid_fold_fn = monoid_fold_fn
        if key_block is not None:
            key_block = max(1, min(int(key_block), key_space))
            if key_block == key_space:
                key_block = None  # single block == unblocked
        self.key_block = key_block
        eff_block = key_block if key_block is not None else key_space
        holder = spec.holder_avals(value_aval)
        self._holder_leaves, self._holder_treedef = jax.tree.flatten(holder)
        # kernel-path exemptions from the pure-JAX budgets apply only when
        # the kernels will actually run: the fused additive kernel needs
        # all-float holders (see _fused_acc), the monoid kernel f32 tables
        # and add/max/min monoids (see _fold_dense's per-leaf kern_ok).
        kernel_additive = (fold_fn is not None
                          and spec.kernel_additive_ok(value_aval))
        kernel_monoid = (monoid_fold_fn is not None
                         and spec.kernel_monoid_ok(value_aval))
        self._dense_ok = (kernel_monoid or chunk_pairs is None or
                          chunk_pairs * eff_block <= DENSE_FOLD_ELEMS_BUDGET)
        # the Pallas fold kernel keeps its one-hot tile VMEM-resident at any
        # chunk size; the pure-JAX contraction stays fused (on-chip) only
        # while the per-fold pair count is inside the fused regime.
        additive_ok = (kernel_additive or chunk_pairs is None or
                       chunk_pairs <= ADDITIVE_FOLD_PAIRS_FUSED)
        scatter_additive = scatter_fold_chosen(
            spec, key_space, kernel_additive=kernel_additive)
        self.mode = (mode if mode is not None else
                     stream_mode(spec, dense_ok=self._dense_ok,
                                 additive_ok=additive_ok,
                                 scatter_additive=scatter_additive))
        plan_cache.STATS.folds[self.mode] += 1
        kernels_used = ((kernel_additive and self.mode == "additive")
                        or (kernel_monoid and self.mode == "dense"))
        if (fold_fn is not None or monoid_fold_fn is not None) \
                and not kernels_used:
            _emit_fallback(
                f"stream flow: use_kernels=True, but the Pallas fold kernels "
                f"carry only f32 holder tables under add/max/min monoids "
                f"({spec.describe or spec.strategy} over {value_aval}, "
                f"fold mode {self.mode}); the chunks fold in pure JAX.",
                on_fallback)
        if (mode is None and spec.mxu_lowerable and self.mode == "scatter"
                and not scatter_additive):
            _emit_fallback(
                f"stream flow: dense fold budgets exceeded at key_space="
                f"{key_space}, chunk_pairs={chunk_pairs}, key_block="
                f"{eff_block}; degrading to the exact scatter fold "
                f"(serialized on XLA:CPU). Shrink stream_chunk_pairs or the "
                f"key block.", on_fallback)

    # -- state ---------------------------------------------------------------

    @property
    def _fused_acc(self) -> bool:
        # the Pallas fold kernel folds all channels + the counts column in
        # one grid-accumulated matmul, so its carry is one f32 matrix.
        # Float holders only: an f32 running accumulator caps exact integer
        # accumulation at 2^24 per key, while the per-leaf path below adds
        # exact per-chunk deltas into tables of the holder's own dtype.
        # (The fused counts column shares the 2^24-pairs-per-key bound.)
        return (self.mode == "additive" and self.fold_fn is not None
                and all(jnp.issubdtype(l.dtype, jnp.floating)
                        for l in self._holder_leaves))

    def init_state(self):
        if self.mode == "size":
            return jnp.zeros((self.key_space,), jnp.int32)
        if self._fused_acc:
            d_tot = sum(int(np.prod(l.shape)) for l in self._holder_leaves)
            return jnp.zeros((self.key_space, d_tot + 1), jnp.float32)
        return self.spec.init_tables(self.key_space, self.value_aval)

    def tables_counts(self, state) -> tuple[Any, jax.Array]:
        """Un-finalized (tables, counts) from the carried state."""
        if self.mode == "size":
            return (), state
        if self._fused_acc:
            acc = state
            tabs, off = [], 0
            for aval in self._holder_leaves:
                size = int(np.prod(aval.shape))
                tabs.append(acc[:, off:off + size]
                            .reshape((self.key_space,) + tuple(aval.shape))
                            .astype(aval.dtype))
                off += size
            tables = jax.tree.unflatten(self._holder_treedef, tabs)
            return tables, acc[:, -1].astype(jnp.int32)
        return state

    def finalize(self, state) -> Grouped:
        tables, counts = self.tables_counts(state)
        return finalize_tables(self.spec, tables, counts, self.key_space)

    # -- per-chunk folds -----------------------------------------------------

    def _onehot(self, keys: jax.Array, dtype=jnp.float32) -> jax.Array:
        k_iota = jnp.arange(self.key_space, dtype=jnp.int32)
        return (keys[:, None] == k_iota[None, :]).astype(dtype)

    def _block_lows(self) -> tuple[jax.Array, int, int]:
        """(block starts, block size, block count) of the key-block grid."""
        Kb = self.key_block
        nb = -(-self.key_space // Kb)
        return jnp.arange(nb, dtype=jnp.int32) * Kb, Kb, nb

    def _blocked(self, per_block: Callable):
        """Run ``per_block(lo) -> [Kb, ...]`` (or a pytree of such) over
        the key-block grid and reassemble the full ``[K, ...]`` axis.
        ``lax.map`` keeps the blocks sequential, so only one block's dense
        expansion is live at a time — the pure-JAX mirror of the kernels'
        key-block grid axis."""
        lows, Kb, nb = self._block_lows()
        blocks = lax.map(per_block, lows)  # pytree of [nb, Kb, ...]
        return jax.tree.map(
            lambda b: b.reshape((nb * Kb,) + b.shape[2:])[: self.key_space],
            blocks)

    def _block_hits(self, keys: jax.Array, lo: jax.Array) -> jax.Array:
        """[n, Kb] bool hit mask of ``keys`` against block ``[lo, lo+Kb)``.

        Sentinel keys (== key_space) either rebase outside ``[0, Kb)`` or
        land in the padded tail rows that ``_blocked`` crops off."""
        iota = jnp.arange(self.key_block, dtype=jnp.int32)
        return (keys[:, None] - lo) == iota[None, :]

    def _blocked_matmul(self, keys: jax.Array, flat: jax.Array) -> jax.Array:
        """[K, D] per-key sums of ``flat`` rows, one key block at a time."""
        def one(lo):
            oh = self._block_hits(keys, lo).astype(flat.dtype)
            return jnp.einsum("nk,nd->kd", oh, flat)
        return self._blocked(one)

    def _chunk_counts(self, stream: PairStream) -> jax.Array:
        if not self._dense_ok or self.mode == "scatter":
            return jnp.zeros((self.key_space,), jnp.int32).at[stream.keys].add(
                stream.valid.astype(jnp.int32), mode="drop")
        if self.key_block is not None:
            ones = stream.valid.astype(jnp.int32)[:, None]
            return self._blocked_matmul(stream.keys, ones)[:, 0]
        return jnp.sum(self._onehot(stream.keys, jnp.int32), axis=0,
                       dtype=jnp.int32)

    def fold_chunk(self, state, stream: PairStream):
        assert stream.key_space == self.key_space
        if self.mode == "size":
            return state + self._chunk_counts(stream)
        if self._fused_acc:
            n = stream.keys.shape[0]
            mapped = _premap_stream(self.spec, stream.values)
            cols = [l.reshape(n, -1).astype(jnp.float32)
                    for l in jax.tree.leaves(mapped)]
            cols.append(stream.valid.astype(jnp.float32)[:, None])  # counts
            return self.fold_fn(stream.keys, jnp.concatenate(cols, axis=1),
                                state)
        tables, counts = state
        if self.mode == "additive":
            return self._fold_additive(tables, counts, stream)
        if self.mode == "dense":
            return self._fold_dense(tables, counts, stream)
        if self.mode == "scatter":
            return self._fold_scatter(tables, counts, stream)
        if self.mode == "first":
            return self._fold_first(tables, counts, stream)
        return _sequential_fold(self.spec, tables, counts,
                                stream.keys, stream.values)

    def _fold_scatter(self, tables, counts, stream: PairStream):
        # same per-chunk semantics as combine_scatter, but folding into the
        # *carried* tables instead of identity ones
        mapped = _premap_stream(self.spec, stream.values)
        leaves = jax.tree.leaves(tables)
        if self.spec.mxu_lowerable and all(t.dtype == counts.dtype
                                           for t in leaves):
            # every channel and the counts in ONE [K, ΣD + 1] scatter-add:
            # on a TPU v5e, 65,536 pairs into K = 131,072 cost 0.77 ms this
            # way against 1.00 ms as a [K] scatter each.  The carried state
            # stays (tables, counts), the layout every reader of it takes.
            K, n = self.key_space, stream.keys.shape[0]
            acc = jnp.concatenate(
                [t.reshape(K, -1) for t in leaves] + [counts[:, None]], 1)
            upd = jnp.concatenate(
                [c.reshape(n, -1).astype(counts.dtype)
                 for c in jax.tree.leaves(mapped)]
                + [stream.valid.astype(counts.dtype)[:, None]], 1)
            acc = acc.at[stream.keys].add(upd, mode="drop")
            out, off = [], 0
            for t in leaves:
                size = int(np.prod(t.shape[1:]))
                out.append(acc[:, off:off + size].reshape(t.shape))
                off += size
            return jax.tree.unflatten(self._holder_treedef, out), acc[:, -1]
        out = []
        for mono, tab, chan in zip(self.spec.monoids, leaves,
                                   jax.tree.leaves(mapped)):
            upd = getattr(tab.at[stream.keys], mono.scatter_method)
            out.append(upd(chan.astype(tab.dtype), mode="drop"))
        tables = jax.tree.unflatten(self._holder_treedef, out)
        return tables, counts + self._chunk_counts(stream)

    def _fold_additive(self, tables, counts, stream: PairStream):
        # One ``one_hotᵀ @ channel`` contraction per holder leaf — the same
        # lowering as the legacy one-hot collector, which XLA fuses with the
        # one-hot generation (the [chunk, K] one-hot never reaches HBM; the
        # Pallas fold kernel behaves the same way, building the one-hot tile
        # in VMEM per grid step).  Integer channels contract in the table's
        # own integer dtype — exact over its full range, where an f32
        # contraction would round per-chunk sums beyond 2^24.
        n = stream.keys.shape[0]
        mapped = _premap_stream(self.spec, stream.values)

        def onehot(dtype):
            return jax.nn.one_hot(stream.keys, self.key_space, dtype=dtype)

        def delta_of(flat):
            if self.key_block is not None:  # key-blocked contraction
                return self._blocked_matmul(stream.keys, flat)
            return jnp.einsum("nk,nd->kd", onehot(flat.dtype), flat)

        # Deliberately one contraction per holder leaf plus one for the
        # counts — NOT a single concatenated [n, ΣD+1] matrix like the
        # fused kernel's accumulator: XLA:CPU's dot strength reduction
        # keeps a matvec-shaped (D=1) one-hot contraction fused/on-chip,
        # while a concatenated D>=2 matmat materializes the whole
        # [chunk, K] one-hot in HBM (measured: 0.014 MB vs 4.2 MB at
        # K=512, chunk=1024).  Integer channels also need their own
        # dtype's exact contraction; 64-bit ones contract as int32 limbs.
        out = []
        for tab, chan in zip(jax.tree.leaves(tables),
                             jax.tree.leaves(mapped)):
            if is_wide(tab.dtype):
                delta = _wide_sums(delta_of, chan.reshape(n, -1)
                                   .astype(tab.dtype))
            else:
                acc_dt = (tab.dtype if jnp.issubdtype(tab.dtype, jnp.integer)
                          else jnp.float32)
                delta = delta_of(chan.reshape(n, -1).astype(acc_dt))
            out.append(tab + delta.reshape(tab.shape).astype(tab.dtype))
        tables = jax.tree.unflatten(self._holder_treedef, out)
        counts = counts + delta_of(
            stream.valid.astype(jnp.int32)[:, None])[:, 0]
        return tables, counts

    def _fold_dense(self, tables, counts, stream: PairStream):
        mapped = _premap_stream(self.spec, stream.values)
        chans = jax.tree.leaves(mapped)
        tabs = jax.tree.leaves(tables)
        blocked = self.key_block is not None
        out: list = [None] * len(tabs)
        pending = []  # (slot, monoid, masked_reduce) for one shared sweep

        for i, (mono, tab, chan) in enumerate(zip(self.spec.monoids, tabs,
                                                  chans)):
            kern_ok = (self.monoid_fold_fn is not None
                       and tab.dtype == jnp.float32
                       and mono.name in ("add", "max", "min"))
            if kern_ok:
                n = chan.shape[0]
                red = self.monoid_fold_fn(
                    stream.keys, chan.reshape(n, -1).astype(jnp.float32),
                    tab.reshape(self.key_space, -1), mono.name)
                out[i] = red.reshape(tab.shape).astype(tab.dtype)
                continue

            def masked_reduce(hits, chan=chan, mono=mono,
                              ident=mono.identity(chan.dtype)):
                bshape = hits.shape + (1,) * (chan.ndim - 1)
                masked = jnp.where(hits.reshape(bshape), chan[:, None], ident)
                return mono.dense_reduce(masked, axis=0)

            pending.append((i, mono, masked_reduce))

        # one hit-mask pass serves every pending leaf AND the counts (the
        # blocked sweep builds each [chunk, key_block] mask exactly once —
        # separate lax.map calls cannot be CSE'd by XLA)
        if blocked:
            def per_block(lo):
                hits = self._block_hits(stream.keys, lo)
                return (tuple(mr(hits) for _, _, mr in pending),
                        jnp.sum(hits, axis=0, dtype=jnp.int32))
            reds, cnt = self._blocked(per_block)
        else:
            oh = self._onehot(stream.keys, jnp.bool_)
            reds = tuple(mr(oh) for _, _, mr in pending)
            cnt = jnp.sum(oh, axis=0, dtype=jnp.int32)
        for (i, mono, _), red in zip(pending, reds):
            out[i] = mono.op(tabs[i], red.astype(tabs[i].dtype))
        tables = jax.tree.unflatten(self._holder_treedef, out)
        return tables, counts + cnt

    def _fold_first(self, tables, counts, stream: PairStream):
        n = stream.keys.shape[0]
        mapped = _premap_stream(self.spec, stream.values)
        pos = jnp.arange(n, dtype=jnp.int32)
        if self._dense_ok and self.key_block is not None:
            first_pos = self._blocked(
                lambda lo: jnp.min(jnp.where(
                    self._block_hits(stream.keys, lo), pos[:, None], n),
                    axis=0))
        elif self._dense_ok:
            oh = self._onehot(stream.keys, jnp.bool_)
            first_pos = jnp.min(jnp.where(oh, pos[:, None], n), axis=0)
        else:  # large key space: scatter-min of arrival order (exact)
            first_pos = jnp.full((self.key_space,), n, jnp.int32).at[
                stream.keys].min(pos, mode="drop")
        fresh = (first_pos < n) & (counts == 0)
        safe = jnp.minimum(first_pos, n - 1)
        out = []
        for tab, chan in zip(jax.tree.leaves(tables),
                             jax.tree.leaves(mapped)):
            sel = fresh.reshape((self.key_space,) + (1,) * (chan.ndim - 1))
            out.append(jnp.where(sel, chan[safe], tab))
        tables = jax.tree.unflatten(self._holder_treedef, out)
        return tables, counts + self._chunk_counts(stream)


# ---------------------------------------------------------------------------
# Sort flow (radix-bucketed segment reduce)
# ---------------------------------------------------------------------------


def sort_radix_passes(n: int, key_space: int) -> int:
    """Packed-sort passes the pure-JAX stable key sort needs at this size.

    1 while ``(key, index)`` fits one 31-bit packed word; past that the
    multi-pass radix splits the key into ``31 - idx_bits``-wide digits and
    pays one packed sort per digit (the K = 256k–4M regime at the default
    chunk sizes).  The cost model prices the sort term with this."""
    idx_bits = max(n - 1, 0).bit_length()
    key_bits = max(key_space, 1).bit_length()  # sentinel == key_space
    if key_bits + idx_bits <= 31:
        return 1
    return -(-key_bits // max(31 - idx_bits, 1))


def stable_sort_by_key(keys: jax.Array, key_space: int, *,
                       impl: str = "auto") -> tuple[jax.Array, jax.Array]:
    """Stable key sort of ``keys`` (sentinel == key_space sorts last).

    Returns ``(sorted_keys, order)``.  When ``(key, index)`` fits 31 bits
    the sort runs as ONE int32 sort of the packed words — measurably faster
    on XLA:CPU than the two-operand comparator sort, which is the whole
    wall-clock budget of the pure-JAX sort flow.  Past 31 bits the sort no
    longer silently degrades to the comparator: ``impl="auto"`` runs the
    multi-pass LSD radix — a ``lax.scan`` over digit levels, one packed
    ``(digit, index)`` sort per level (digits are ``31 - idx_bits`` wide,
    so every level keeps the packed fast path; per-level stability makes
    the composition exactly the stable full-key sort).  Measured at
    K=1M, n=16384 the two-level radix is ~4.8× faster than the two-key
    comparator sort it replaces.  ``impl`` forces a lowering for A/B
    benchmarks: "packed" | "radix" | "two_key".  Keys must already be in
    ``[0, key_space]`` (the Emitter guarantees it).
    """
    n = keys.shape[0]
    idx_bits = max(n - 1, 0).bit_length()
    key_bits = max(key_space, 1).bit_length()  # sentinel == key_space
    iota = jnp.arange(n, dtype=jnp.int32)
    if impl == "auto":
        impl = "packed" if key_bits + idx_bits <= 31 else "radix"
    if impl == "packed":
        if key_bits + idx_bits > 31:
            raise ValueError(
                f"packed sort needs key_bits + idx_bits <= 31, got "
                f"{key_bits} + {idx_bits}; use impl='radix'")
        packed = (keys << idx_bits) | iota
        sp = lax.sort(packed)
        return sp >> idx_bits, sp & ((1 << idx_bits) - 1)
    if impl == "two_key":
        sk, order = lax.sort((keys, iota), num_keys=2)  # lexicographic
        return sk, order
    if impl == "radix":
        digit_bits = max(31 - idx_bits, 1)
        levels = -(-key_bits // digit_bits)
        digit_mask = (1 << digit_bits) - 1
        idx_mask = (1 << idx_bits) - 1

        def body(perm, shift):
            digit = (keys[perm] >> shift) & digit_mask
            sp = lax.sort((digit << idx_bits) | iota)
            return perm[sp & idx_mask], None

        shifts = jnp.arange(levels, dtype=jnp.int32) * digit_bits
        perm, _ = lax.scan(body, iota, shifts)
        return keys[perm], perm
    raise ValueError(f"unknown sort impl {impl!r}")


def segmented_scan(op: Callable, flags: jax.Array, vals: jax.Array
                   ) -> jax.Array:
    """Inclusive segmented scan: ``op``-accumulate, restarting at ``flags``.

    ``flags[i]`` marks the start of a new segment.  Standard associative
    lift: ``(fa, va) ⊕ (fb, vb) = (fa|fb, vb if fb else op(va, vb))`` —
    O(N log N) vectorized work, no serial dependency.
    """
    def comb(a, b):
        fa, va = a
        fb, vb = b
        sel = fb.reshape(fb.shape + (1,) * (va.ndim - fb.ndim))
        return fa | fb, jnp.where(sel, vb, op(va, vb))

    _, out = lax.associative_scan(comb, (flags, vals), axis=0)
    return out


def _run_aggregate(mono: C.Monoid, flat: jax.Array, is_start: jax.Array,
                   start_pos: jax.Array) -> jax.Array:
    """Per-run ``mono`` aggregate of a key-sorted channel, valid at run ends.

    Additive monoids use the cumsum-difference form (one pass); the rest go
    through :func:`segmented_scan`.
    """
    if mono.is_additive:
        csum = jnp.cumsum(flat, axis=0)
        prev = jnp.where(
            (start_pos > 0).reshape((-1,) + (1,) * (flat.ndim - 1)),
            csum[jnp.maximum(start_pos - 1, 0)], jnp.zeros_like(flat))
        return csum - prev
    return segmented_scan(mono.op, is_start, flat)


class SortCombiner:
    """Chunked sort-based fold: partition by key, reduce presorted segments.

    The fourth execution flow (``flow="sort"``): each chunk's pairs are
    stably sorted by key, per-run monoid aggregates are computed with
    vectorized segmented scans (cumsum-difference for additive monoids),
    and ONE aggregate per distinct key is merged into the carried holder
    tables with the monoid's scatter method — O(N·log N + K) compute and
    O(N + K) bytes per chunk, versus the one-hot fold's O(N·K) compute.
    This is what dominates the stream flow at large sparse key spaces
    (``core/cost_model.py`` quantifies the crossover).

    Under ``use_kernels`` the per-chunk fold runs as the Pallas radix
    pipeline instead: the histogram + bucket-scatter partition
    (``kernels/radix_partition.py``) feeding the ``segment_reduce``
    kernel bucket by bucket — ``sort_fold_fn(keys, mat, acc, op)`` with
    the same merge contract as the pure-JAX path (the bucket aggregates
    land in the carried holder tables through the monoid merge).  The
    pure-JAX lowering switches to the multi-pass packed radix sort (``stable_sort_by_key(impl="radix")``,
    a ``lax.scan`` over digit levels) once the packed 31-bit single-sort
    regime runs out; ``sort_impl`` forces a lowering for A/B benchmarks.
    Same interface as :class:`StreamCombiner` (init_state / fold_chunk /
    tables_counts / finalize) so the engine's chunk scan is shared.

    Modes: ``monoid`` (scatter-merge of run aggregates), ``first``
    (run-start gather — the stable sort makes the first pair of each run
    the first-arrived), ``size`` (run lengths only; the payload is never
    gathered), ``sequential`` (coupled holders: sorted sequential fold, the
    chunked form of ``combine_segment``).
    """

    def __init__(self, spec: C.CombinerSpec, key_space: int, value_aval,
                 *, sort_fold_fn: Callable | None = None,
                 mode: str | None = None, sort_impl: str = "auto"):
        self.spec = spec
        self.key_space = key_space
        self.value_aval = value_aval
        self.sort_impl = sort_impl
        holder = spec.holder_avals(value_aval)
        self._holder_leaves, self._holder_treedef = jax.tree.flatten(holder)
        if mode is None:
            if spec.strategy == C.STRATEGY_SIZE:
                mode = "size"
            elif spec.strategy == C.STRATEGY_FIRST:
                mode = "first"
            elif spec.scatter_lowerable:
                mode = "monoid"
            else:
                mode = "sequential"
        self.mode = mode
        # the radix kernel pipeline accumulates f32 and supports
        # add/max/min — same envelope as the chunk monoid-fold kernel
        self._use_kernel = (sort_fold_fn is not None and mode == "monoid"
                            and spec.kernel_monoid_ok(value_aval))
        self.sort_fold_fn = sort_fold_fn

    # -- state (same contract as StreamCombiner) -----------------------------

    @property
    def _fused_acc(self) -> bool:
        # all-additive float-holder specs carry one [K, D+1] f32 matrix so
        # the per-chunk run aggregates land in ONE scatter (channels + the
        # counts column share the cumsum and the merge) — same exactness
        # envelope as StreamCombiner's fused kernel accumulator (2^24
        # integer bound on the f32 counts column).
        return (self.mode == "monoid" and not self._use_kernel
                and self.spec.mxu_lowerable
                and all(jnp.issubdtype(l.dtype, jnp.floating)
                        for l in self._holder_leaves))

    def init_state(self):
        if self.mode == "size":
            return jnp.zeros((self.key_space,), jnp.int32)
        if self._fused_acc:
            d_tot = sum(int(np.prod(l.shape)) for l in self._holder_leaves)
            return jnp.zeros((self.key_space, d_tot + 1), jnp.float32)
        return self.spec.init_tables(self.key_space, self.value_aval)

    def tables_counts(self, state) -> tuple[Any, jax.Array]:
        if self.mode == "size":
            return (), state
        if self._fused_acc:
            acc = state
            tabs, off = [], 0
            for aval in self._holder_leaves:
                size = int(np.prod(aval.shape))
                tabs.append(acc[:, off:off + size]
                            .reshape((self.key_space,) + tuple(aval.shape))
                            .astype(aval.dtype))
                off += size
            tables = jax.tree.unflatten(self._holder_treedef, tabs)
            return tables, acc[:, -1].astype(jnp.int32)
        return state

    def finalize(self, state) -> Grouped:
        tables, counts = self.tables_counts(state)
        return finalize_tables(self.spec, tables, counts, self.key_space)

    # -- per-chunk fold ------------------------------------------------------

    def _run_layout(self, sk: jax.Array):
        """(is_start, start_pos, run_len, end_target) of the sorted runs."""
        n = sk.shape[0]
        pos = jnp.arange(n, dtype=jnp.int32)
        if n == 1:
            is_start = jnp.ones((1,), bool)
            is_end = jnp.ones((1,), bool)
        else:
            change = sk[1:] != sk[:-1]
            is_start = jnp.concatenate([jnp.ones((1,), bool), change])
            is_end = jnp.concatenate([change, jnp.ones((1,), bool)])
        start_pos = lax.cummax(jnp.where(is_start, pos, 0))
        run_len = pos - start_pos + 1
        # run ends scatter to their key; everything else to the dropped
        # sentinel slot.  Sentinel-key runs (== key_space) drop themselves.
        tgt = jnp.where(is_end, sk, self.key_space)
        return is_start, start_pos, run_len, tgt

    def fold_chunk(self, state, stream: PairStream):
        assert stream.key_space == self.key_space
        n = stream.keys.shape[0]
        if n == 0:
            return state
        if self.mode == "monoid" and self._use_kernel:
            return self._fold_kernel(state, stream)
        with jax.named_scope(trace.PARTITION):
            sk, order = stable_sort_by_key(stream.keys, self.key_space,
                                           impl=self.sort_impl)
        with jax.named_scope(trace.SEGMENT_REDUCE):
            return self._fold_sorted(state, stream, sk, order)

    def _fold_sorted(self, state, stream: PairStream, sk, order):
        """Merge one aggregate per run of the key-sorted chunk into the
        carried state."""
        n = stream.keys.shape[0]
        if self.mode == "size":
            _, _, run_len, tgt = self._run_layout(sk)
            return state.at[tgt].add(run_len, mode="drop")
        if self._fused_acc:
            svals = jax.tree.map(lambda v: v[order], stream.values)
            mapped = _premap_stream(self.spec, svals)
            is_start, start_pos, _, tgt = self._run_layout(sk)
            cols = [l.reshape(n, -1).astype(jnp.float32)
                    for l in jax.tree.leaves(mapped)]
            cols.append((sk < self.key_space).astype(jnp.float32)[:, None])
            agg = _run_aggregate(C.ADD, jnp.concatenate(cols, axis=1),
                                 is_start, start_pos)
            return state.at[tgt].add(agg, mode="drop")
        tables, counts = state
        if self.mode == "sequential":
            svals = jax.tree.map(lambda v: v[order], stream.values)
            return _sequential_fold(self.spec, tables, counts, sk, svals)
        svals = jax.tree.map(lambda v: v[order], stream.values)
        mapped = _premap_stream(self.spec, svals)
        is_start, start_pos, run_len, tgt = self._run_layout(sk)
        if self.mode == "first":
            return self._fold_first(tables, counts, mapped, sk,
                                    is_start, run_len, tgt)
        out = []
        for mono, tab, chan in zip(self.spec.monoids,
                                   jax.tree.leaves(tables),
                                   jax.tree.leaves(mapped)):
            acc_dt = (tab.dtype if jnp.issubdtype(tab.dtype, jnp.integer)
                      or tab.dtype == jnp.bool_ else jnp.float32)
            agg = _run_aggregate(mono, chan.astype(acc_dt), is_start,
                                 start_pos)
            upd = getattr(tab.at[tgt], mono.scatter_method)
            out.append(upd(agg.astype(tab.dtype), mode="drop"))
        tables = jax.tree.unflatten(self._holder_treedef, out)
        counts = counts.at[tgt].add(run_len, mode="drop")
        return tables, counts

    def _fold_first(self, tables, counts, mapped, sk, is_start, run_len,
                    tgt):
        """Keep the first-arriving value per key across chunk boundaries.

        The stable sort preserves emission order within a run, so the run
        START carries the chunk-first value; it lands only where the
        carried count is still zero."""
        K = self.key_space
        tgt_s = jnp.where(is_start, sk, K)
        cnt_delta = jnp.zeros((K,), jnp.int32).at[tgt].add(
            run_len, mode="drop")
        fresh = (counts == 0) & (cnt_delta > 0)
        out = []
        for tab, chan in zip(jax.tree.leaves(tables),
                             jax.tree.leaves(mapped)):
            cand = jnp.zeros_like(tab).at[tgt_s].set(
                chan.astype(tab.dtype), mode="drop")
            sel = fresh.reshape((K,) + (1,) * (chan.ndim - 1))
            out.append(jnp.where(sel, cand, tab))
        tables = jax.tree.unflatten(self._holder_treedef, out)
        return tables, counts + cnt_delta

    def _fold_kernel(self, state, stream: PairStream):
        """Radix partition + segment_reduce Pallas pipeline, per leaf.

        The counts column rides along with the first additive leaf (one
        partition serves channels + counts); only all-max/min specs pay a
        separate counts pass — each pipeline run re-partitions the keys,
        so sharing it matters."""
        tables, counts = state
        n = stream.keys.shape[0]
        mapped = _premap_stream(self.spec, stream.values)
        ones = stream.valid.astype(jnp.float32)[:, None]
        out = []
        new_counts = None
        for mono, tab, chan in zip(self.spec.monoids,
                                   jax.tree.leaves(tables),
                                   jax.tree.leaves(mapped)):
            flat = chan.reshape(n, -1).astype(jnp.float32)
            acc = tab.reshape(self.key_space, -1)
            if mono.name == "add" and new_counts is None:
                flat = jnp.concatenate([flat, ones], axis=1)
                acc = jnp.concatenate(
                    [acc, counts.astype(jnp.float32)[:, None]], axis=1)
                red = self.sort_fold_fn(stream.keys, flat, acc, "add")
                new_counts = red[:, -1].astype(jnp.int32)
                red = red[:, :-1]
            else:
                red = self.sort_fold_fn(stream.keys, flat, acc, mono.name)
            out.append(red.reshape(tab.shape).astype(tab.dtype))
        tables = jax.tree.unflatten(self._holder_treedef, out)
        if new_counts is None:
            new_counts = self.sort_fold_fn(
                stream.keys, ones, counts.astype(jnp.float32)[:, None],
                "add")[:, 0].astype(jnp.int32)
        return tables, new_counts


def sort_flow(
    spec: C.CombinerSpec,
    stream: PairStream,
    *,
    sort_fold_fn: Callable | None = None,
    mode: str | None = None,
    sort_impl: str = "auto",
) -> Grouped:
    """Single-shot sort flow: one chunk through :class:`SortCombiner`."""
    value_aval = jax.tree.map(
        lambda v: jax.ShapeDtypeStruct(v.shape[1:], v.dtype), stream.values)
    sc = SortCombiner(spec, stream.key_space, value_aval,
                      sort_fold_fn=sort_fold_fn, mode=mode,
                      sort_impl=sort_impl)
    state = sc.fold_chunk(sc.init_state(), stream)
    return sc.finalize(state)
