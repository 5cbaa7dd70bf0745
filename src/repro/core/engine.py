"""Execution engine: map phase + local and distributed runs of the flows.

Four execution flows:

* stream  — **fused map+combine** (the optimizer's default): the item axis is
  scanned in chunks; each chunk's emitted pairs are folded straight into the
  carried holder tables (``collector.StreamCombiner``).  The full
  ``N × emit_capacity`` pair buffer never exists — peak intermediate state is
  O(K + chunk_pairs).  This is what restores the paper's Figs 8/9 story at
  the bytes level: the legacy combine flow still materialized every pair
  before folding.
* sort    — **radix-bucketed segment reduce** (``collector.SortCombiner``):
  each chunk's pairs are partitioned by key (stable packed sort — multi-pass
  digit radix past the 31-bit packed regime — or the Pallas
  radix-partition kernel pipeline under ``use_kernels``) and ONE aggregate
  per distinct key merges into the carried tables — O(N·log N + K) compute
  where the one-hot stream fold pays O(N·K); the cost model
  (``core/cost_model.py``) picks it for large sparse key spaces.
* combine — the legacy combining collector (materialize pairs, fold once);
  kept for A/B benchmarks against the paper's optimized flow.
* reduce  — the paper's baseline (materialize, sort, group, per-key reduce).

Distribution (beyond the paper's multicore scope, toward the 1000-node
posture):

* stream/combine flow — each shard folds its local pairs into holder tables;
  tables merge across the data axis with monoid-aware collectives
  (psum/pmax/pmin, or an all-gather fold for generic merges).  Collective
  volume: **O(K)**.
* reduce flow — raw pairs are key-partitioned and exchanged with
  ``lax.all_to_all`` (fixed-capacity buckets, Phoenix-buffer style), then each
  shard sorts/groups/reduces its key range.  Collective volume: **O(N)**.
* sort flow — the shard key ranges ARE the top-level radix buckets: the same
  key-partitioned all-to-all as the reduce flow (O(N) traffic) hands every
  shard presorted-by-range segments, which it folds with the local sort
  collector — the reduce-flow shuffle machinery reused, without the O(K·Lmax)
  window gather on the far side.

The contrast is the distributed version of the paper's observation that the
combiner "minimizes data transfers before the reduce phase" (§2.2.1), and is
measured by the dry-run collective roofline term.

The all-to-all **wire format** itself lives in ``distributed/wire.py``: a
``WireFormat`` record (codec + capacity envelope + per-destination key
layout) with pluggable codecs — ``raw`` (the legacy layout, bitwise),
``delta`` (range-residual bit-packed keys, exact), ``packed`` (narrow
int8 values on top, opt-in).  This engine bucketizes/encodes sends and
decodes receives through that one layer, both around the live
``lax.all_to_all`` and in the resilient driver's checkpointable
per-shard partials — the format is defined in exactly one place.
"""

from __future__ import annotations

from functools import partial
from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from repro.core import collector as col
from repro.core import combiner as C
from repro.core import trace
from repro.distributed import wire as wirelib
from repro.distributed.wire import shuffle_bucket_capacity  # noqa: F401

# ---------------------------------------------------------------------------
# Emitter + map phase
# ---------------------------------------------------------------------------


class Emitter:
    """Fixed-capacity recording emitter handed to ``map``.

    ``emit(keys, values, valid=None)`` accepts scalars or 1-D vectors; calls
    append (at trace time) into the per-item pair buffer.  Total emitted slots
    must not exceed the capacity.  Invalid slots carry the sentinel key
    ``key_space`` and are dropped by the collectors.
    """

    def __init__(self, capacity: int, key_space: int,
                 value_aval: jax.ShapeDtypeStruct):
        self.capacity = capacity
        self.key_space = key_space
        self.value_aval = value_aval
        self._keys: list[jax.Array] = []
        self._vals: list[jax.Array] = []
        self._used = 0

    def __call__(self, keys, values, valid=None):
        return self.emit(keys, values, valid)

    def emit(self, keys, values, valid=None):
        keys = jnp.asarray(keys, jnp.int32)
        values = jnp.asarray(values, self.value_aval.dtype)
        if keys.ndim == 0:
            keys = keys[None]
            values = values[None]
        n = keys.shape[0]
        if valid is not None:
            valid = jnp.asarray(valid, bool)
            if valid.ndim == 0:
                valid = valid[None]
            keys = jnp.where(valid, keys, self.key_space)
        if self._used + n > self.capacity:
            raise ValueError(
                f"map emitted more than emit_capacity={self.capacity} pairs")
        expected = (n,) + tuple(self.value_aval.shape)
        if tuple(values.shape) != expected:
            raise ValueError(f"emitted values shape {values.shape} != {expected}")
        self._keys.append(keys)
        self._vals.append(values)
        self._used += n

    def pairs(self):
        Pcap = self.capacity
        vs_shape = tuple(self.value_aval.shape)
        ks = (jnp.concatenate(self._keys) if self._keys
              else jnp.zeros((0,), jnp.int32))
        vs = (jnp.concatenate(self._vals) if self._vals
              else jnp.zeros((0,) + vs_shape, self.value_aval.dtype))
        pad_n = Pcap - ks.shape[0]
        ks = jnp.concatenate([ks, jnp.full((pad_n,), self.key_space, jnp.int32)])
        vs = jnp.concatenate([vs, jnp.zeros((pad_n,) + vs_shape, vs.dtype)])
        ks = jnp.where((ks < 0) | (ks > self.key_space), self.key_space, ks)
        return ks, vs


@jax.named_scope(trace.MAP)
def map_phase(app, items) -> col.PairStream:
    """vmap the user map over input items -> flat PairStream."""

    def one(item):
        em = Emitter(app.emit_capacity, app.key_space, app.value_aval)
        app.map(item, em)
        return em.pairs()

    keys, vals = jax.vmap(one)(items)
    flat_keys = keys.reshape(-1)
    flat_vals = vals.reshape((-1,) + vals.shape[2:])
    return col.PairStream(flat_keys, flat_vals, app.key_space)


# ---------------------------------------------------------------------------
# Local run (single device / single shard)
# ---------------------------------------------------------------------------


def _onehot_kernel(use_kernels: bool) -> Callable | None:
    if not use_kernels:
        return None
    from repro.kernels import ops  # lazy: kernels are optional at runtime

    return ops.onehot_combine


def _fold_kernels(use_kernels: bool, key_block: int | None = None
                  ) -> tuple[Callable | None, Callable | None]:
    """(additive fold_fn, monoid_fold_fn) for the streaming collector.

    ``key_block`` binds the kernels' key-block grid axis (None lets the
    kernel wrapper auto-size the block against the VMEM budget)."""
    if not use_kernels:
        return None, None
    from repro.kernels import ops

    return (partial(ops.onehot_fold, block_k=key_block),
            partial(ops.chunk_monoid_fold, block_k=key_block))


def _sort_fold_kernel(use_kernels: bool, bucket_size: int | None = None
                      ) -> Callable | None:
    """Radix-partition + segment-reduce pipeline for the sort collector
    (``bucket_size=None`` lets the wrapper size the buckets)."""
    if not use_kernels:
        return None
    from repro.kernels import ops

    return partial(ops.sort_segment_fold, bucket_size=bucket_size)


def _sort_kernels_usable(spec, value_aval, use_kernels: bool,
                            on_fallback: Callable | None) -> bool:
    """Whether the kernel sort fold can carry ``spec``'s holders.

    The radix-partition pipeline folds f32 holder tables under add/max/min
    only; anything else fires a :class:`LoweringFallbackWarning` (once,
    through the plan sink) and folds with the pure-JAX sorted fold."""
    if use_kernels and not spec.kernel_monoid_ok(value_aval):
        col._emit_fallback(
            f"sort flow: use_kernels=True, but the radix-partition kernel "
            f"pipeline carries only f32 holder tables under add/max/min "
            f"monoids ({spec.describe or spec.strategy} over "
            f"{value_aval}); this key range folds with the pure-JAX sorted "
            f"fold.", on_fallback)
        return False
    return use_kernels


def _plan_fallback_cb(plan) -> Callable | None:
    """Per-plan fallback sink: warn ONCE per plan, record every diagnostic.

    The collectors used to ``warnings.warn`` at construction time, which
    fires again on every re-trace of the same plan (each chunked scan
    specialization, every new input shape).  Routing through the plan keeps
    the user-facing warning to one per plan while ``plan.diagnostics``
    stays complete for ``explain()``."""
    if plan is None:
        return None

    def cb(msg: str) -> None:
        import warnings

        from repro.core import collector as _col

        if not getattr(plan, "_fallback_warned", False):
            warnings.warn(msg, _col.LoweringFallbackWarning, stacklevel=4)
            plan._fallback_warned = True
        if msg not in plan.diagnostics:
            plan.diagnostics += (msg,)

    return cb


#: default bound on emitted pairs materialized per streaming chunk.  While
#: the whole pair buffer fits this budget the flow degenerates to a single
#: fully-fused chunk (XLA keeps the pairs out of HBM on its own at that
#: size); beyond it, chunking bounds peak intermediate state at the cost of
#: re-touching the O(K) tables once per chunk.  Tied to the fused
#: one-hot-contraction regime so the non-autotuned entry points
#: (run_distributed, direct stream_local_tables callers) keep the additive
#: fold on its scatter-free fused path by default.
DEFAULT_CHUNK_PAIRS = col.ADDITIVE_FOLD_PAIRS_FUSED


def _stream_combiner(app, spec, *, use_kernels=False,
                     chunk_pairs: int | None = None,
                     key_block: int | None = None,
                     fold_mode: str | None = None,
                     on_fallback: Callable | None = None
                     ) -> col.StreamCombiner:
    fold_fn, monoid_fold_fn = _fold_kernels(use_kernels, key_block)
    return col.StreamCombiner(spec, app.key_space, app.value_aval,
                              fold_fn=fold_fn, monoid_fold_fn=monoid_fold_fn,
                              chunk_pairs=chunk_pairs, key_block=key_block,
                              mode=fold_mode, on_fallback=on_fallback)


def _fold_items_chunked(app, combiner, items, chunk_items: int,
                        n_valid=None, state=None):
    """Scan the item axis in chunks, folding each chunk into the carried
    collector state (shared scaffolding of the stream and sort flows).

    Pad items run through the map like real ones; their emissions are
    masked to the sentinel key before the fold and so never land.
    ``n_valid`` (scalar, optional) additionally masks the tail of the item
    axis itself — the N-bucketed serving path (``Compiled``) pads inputs
    up to a shared bucket shape and passes the true count here, so one
    executable serves every batch size in the bucket.

    ``state`` seeds the fold with an existing carried state instead of
    ``combiner.init_state()`` — the continuous-ingestion path: a
    micro-batch folds into the tables accumulated by all prior batches,
    and because the per-chunk fold sequence is identical to a batch run
    over the concatenated items, the result is bitwise the batch answer.
    """
    n_items = jax.tree.leaves(items)[0].shape[0]
    n_chunks = -(-n_items // chunk_items)
    if state is None:
        state = combiner.init_state()
    if n_chunks <= 1:
        stream = map_phase(app, items)
        if n_valid is not None:
            mask = jnp.repeat(jnp.arange(n_items, dtype=jnp.int32) < n_valid,
                              app.emit_capacity)
            stream = col.PairStream(
                jnp.where(mask, stream.keys, app.key_space),
                stream.values, app.key_space)
        with jax.named_scope(trace.FOLD):
            return combiner.fold_chunk(state, stream)

    padded = n_chunks * chunk_items
    pad = padded - n_items
    items_p = jax.tree.map(
        lambda a: jnp.pad(a, [(0, pad)] + [(0, 0)] * (a.ndim - 1)), items)
    chunked = jax.tree.map(
        lambda a: a.reshape((n_chunks, chunk_items) + a.shape[1:]), items_p)
    valid_items = n_items if n_valid is None else n_valid
    item_mask = (jnp.arange(padded, dtype=jnp.int32) < valid_items).reshape(
        n_chunks, chunk_items)

    def body(state, xs):
        citems, cmask = xs
        stream = map_phase(app, citems)
        keys = jnp.where(jnp.repeat(cmask, app.emit_capacity),
                         stream.keys, app.key_space)
        with jax.named_scope(trace.FOLD):
            state = combiner.fold_chunk(
                state, col.PairStream(keys, stream.values, app.key_space))
        return state, None

    state, _ = lax.scan(body, state, (chunked, item_mask))
    return state


def stream_local_tables(app, spec, items, *, chunk_pairs: int = DEFAULT_CHUNK_PAIRS,
                        use_kernels: bool = False,
                        key_block: int | None = None,
                        fold_mode: str | None = None,
                        on_fallback: Callable | None = None,
                        n_valid=None):
    """Fused map+combine over ``items``: chunked scan, holder-table carry.

    Splits the item axis into chunks of ~``chunk_pairs`` emitted pairs, runs
    the user map on one chunk at a time and folds the chunk's pairs straight
    into the carried holder tables.  The full ``N × emit_capacity`` pair
    buffer of the legacy flows is never materialized — peak intermediate
    state is O(K + chunk_pairs), the paper's "minimize data transfers before
    the reduce phase" realized at the HBM level.

    Returns un-finalized ``(tables, counts)`` (for the distributed engine's
    collective merge); :func:`run_local_stream` finalizes.
    """
    n_items = jax.tree.leaves(items)[0].shape[0]
    cap = max(app.emit_capacity, 1)
    chunk_items = max(1, min(n_items, chunk_pairs // cap))
    n_chunks = -(-n_items // chunk_items)
    if (n_chunks <= 1 and key_block is not None and not use_kernels
            and spec.mxu_lowerable
            and n_items * cap <= col.ADDITIVE_FOLD_PAIRS_FUSED):
        # single-shot fold inside the fused-contraction regime: there is no
        # scan body to blow up, and the unblocked contraction stays on-chip
        # — blocking would only re-read the pairs once per block.
        key_block = None
    sc = _stream_combiner(app, spec, use_kernels=use_kernels,
                          chunk_pairs=chunk_items * cap,
                          key_block=key_block, fold_mode=fold_mode,
                          on_fallback=on_fallback)
    state = _fold_items_chunked(app, sc, items, chunk_items, n_valid=n_valid)
    return sc.tables_counts(state)


def run_local_stream(app, spec, items, *, chunk_pairs: int = DEFAULT_CHUNK_PAIRS,
                     use_kernels: bool = False, key_block: int | None = None,
                     fold_mode: str | None = None,
                     on_fallback: Callable | None = None,
                     n_valid=None):
    tables, counts = stream_local_tables(
        app, spec, items, chunk_pairs=chunk_pairs, use_kernels=use_kernels,
        key_block=key_block, fold_mode=fold_mode, on_fallback=on_fallback,
        n_valid=n_valid)
    grouped = col.finalize_tables(spec, tables, counts, app.key_space)
    return grouped.keys, grouped.values, grouped.counts


def build_stream_ingest(app, spec, *, batch_items: int,
                        chunk_pairs: int = DEFAULT_CHUNK_PAIRS,
                        use_kernels: bool = False,
                        key_block: int | None = None,
                        fold_mode: str | None = None,
                        on_fallback: Callable | None = None):
    """Incremental-fold entry point for the streaming service.

    Returns ``(combiner, ingest)`` where ``ingest(state, items, n_valid)``
    folds one micro-batch (padded to ``batch_items``) into the carried
    combiner state and returns the new state.  The function is pure and
    shape-static, so the API layer AOT-compiles it once and every
    subsequent micro-batch is a plain dispatch — no re-trace, no re-tune.

    Exactness: the per-chunk fold sequence is exactly the one
    :func:`stream_local_tables` runs over the concatenated items (same
    combiner mode, same chunk size, same masking), so N sequential
    ingests produce bitwise the tables of one batch run — the monoid
    partials that made resilient recovery exact make merge-on-arrival
    exact too.
    """
    cap = max(app.emit_capacity, 1)
    chunk_items = max(1, min(batch_items, chunk_pairs // cap))
    n_chunks = -(-batch_items // chunk_items)
    if (n_chunks <= 1 and key_block is not None and not use_kernels
            and spec.mxu_lowerable
            and batch_items * cap <= col.ADDITIVE_FOLD_PAIRS_FUSED):
        # mirror stream_local_tables: a single-shot fold inside the fused-
        # contraction regime keeps the unblocked contraction on-chip
        key_block = None
    sc = _stream_combiner(app, spec, use_kernels=use_kernels,
                          chunk_pairs=chunk_items * cap,
                          key_block=key_block, fold_mode=fold_mode,
                          on_fallback=on_fallback)

    def ingest(state, items, n_valid):
        return _fold_items_chunked(app, sc, items, chunk_items,
                                   n_valid=n_valid, state=state)

    return sc, ingest


#: default bound on pairs materialized per sort-flow chunk.  The sort flow
#: touches the O(K) tables once per chunk and its per-pair cost is
#: O(log chunk), so bigger chunks amortize the table pass; no
#: fused-contraction cap applies (nothing is contracted dense).
DEFAULT_SORT_CHUNK_PAIRS = 1 << 14


def sort_local_tables(app, spec, items, *,
                      chunk_pairs: int = DEFAULT_SORT_CHUNK_PAIRS,
                      use_kernels: bool = False,
                      bucket_size: int | None = None,
                      sort_mode: str | None = None,
                      sort_impl: str = "auto",
                      on_fallback: Callable | None = None,
                      n_valid=None):
    """Sort flow over ``items``: chunked scan, per-chunk radix/sort fold.

    Same chunk scaffolding as the stream flow; each chunk is partitioned by
    key and ONE aggregate per distinct key merges into the carried tables
    (``collector.SortCombiner``).  Returns un-finalized ``(tables, counts)``.
    """
    n_items = jax.tree.leaves(items)[0].shape[0]
    cap = max(app.emit_capacity, 1)
    chunk_items = max(1, min(n_items, chunk_pairs // cap))
    use_kernels = _sort_kernels_usable(spec, app.value_aval,
                                          use_kernels, on_fallback)
    sc = col.SortCombiner(
        spec, app.key_space, app.value_aval,
        sort_fold_fn=_sort_fold_kernel(use_kernels, bucket_size),
        mode=sort_mode, sort_impl=sort_impl)
    state = _fold_items_chunked(app, sc, items, chunk_items, n_valid=n_valid)
    return sc.tables_counts(state)


def run_local_sort(app, spec, items, *,
                   chunk_pairs: int = DEFAULT_SORT_CHUNK_PAIRS,
                   use_kernels: bool = False,
                   bucket_size: int | None = None,
                   sort_mode: str | None = None,
                   sort_impl: str = "auto",
                   on_fallback: Callable | None = None,
                   n_valid=None):
    tables, counts = sort_local_tables(
        app, spec, items, chunk_pairs=chunk_pairs, use_kernels=use_kernels,
        bucket_size=bucket_size, sort_mode=sort_mode, sort_impl=sort_impl,
        on_fallback=on_fallback, n_valid=n_valid)
    grouped = col.finalize_tables(spec, tables, counts, app.key_space)
    return grouped.keys, grouped.values, grouped.counts


def run_local(app, plan, items, *, combine_impl="auto", use_kernels=False,
              chunk_pairs: int | None = None,
              key_block: int | None = None,
              bucket_size: int | None = None,
              n_valid=None):
    if plan.flow == "stream":
        return run_local_stream(app, plan.spec, items,
                                chunk_pairs=(DEFAULT_CHUNK_PAIRS
                                             if chunk_pairs is None
                                             else chunk_pairs),
                                use_kernels=use_kernels,
                                key_block=key_block,
                                on_fallback=_plan_fallback_cb(plan),
                                n_valid=n_valid)
    if plan.flow == "sort":
        return run_local_sort(app, plan.spec, items,
                              chunk_pairs=(DEFAULT_SORT_CHUNK_PAIRS
                                           if chunk_pairs is None
                                           else chunk_pairs),
                              use_kernels=use_kernels,
                              bucket_size=bucket_size,
                              on_fallback=_plan_fallback_cb(plan),
                              n_valid=n_valid)
    stream = map_phase(app, items)
    if n_valid is not None:
        n_items = jax.tree.leaves(items)[0].shape[0]
        mask = jnp.repeat(jnp.arange(n_items, dtype=jnp.int32) < n_valid,
                          app.emit_capacity)
        stream = col.PairStream(jnp.where(mask, stream.keys, app.key_space),
                                stream.values, app.key_space)
    if plan.flow == "combine":
        grouped = col.combine_flow(
            plan.spec, stream, impl=combine_impl,
            onehot_fn=_onehot_kernel(use_kernels),
            on_fallback=_plan_fallback_cb(plan))
    else:
        grouped = col.reduce_flow(
            app.reduce, stream,
            max_values_per_key=app.max_values_per_key,
            pad_value=app.pad_value)
    return grouped.keys, grouped.values, grouped.counts


# ---------------------------------------------------------------------------
# Distributed: combine flow (monoid collectives, O(K) traffic)
# ---------------------------------------------------------------------------

_PCOLLECTIVE = {"add": lax.psum, "max": lax.pmax, "min": lax.pmin}


@jax.named_scope(trace.MERGE)
def merge_tables_collective(spec: C.CombinerSpec, tables, counts,
                            axis_name: str, *, scatter: bool = False):
    """Merge per-shard holder tables across ``axis_name``.

    scatter=True uses psum_scatter (output sharded over keys) where legal —
    halves the collective bytes versus a full all-reduce (hillclimb knob).
    """
    total_counts = lax.psum(counts, axis_name)

    if spec.monoids is not None and len(spec.monoids) == len(jax.tree.leaves(tables)):
        leaves, treedef = jax.tree.flatten(tables)
        merged = []
        for mono, leaf in zip(spec.monoids, leaves):
            coll = _PCOLLECTIVE.get(mono.name)
            if mono.name == "add" and scatter:
                merged.append(lax.psum_scatter(leaf, axis_name, tiled=True))
            elif coll is not None:
                merged.append(coll(leaf, axis_name))
            elif mono.name in ("and", "or"):
                as_int = leaf.astype(jnp.int32)
                red = (lax.pmin if mono.name == "and" else lax.pmax)(
                    as_int, axis_name)
                merged.append(red.astype(leaf.dtype))
            else:  # mul & friends: gather + vectorized fold
                g = lax.all_gather(leaf, axis_name)
                merged.append(jnp.prod(g, axis=0) if mono.name == "mul"
                              else g[0])
        if scatter and any(m.name == "add" for m in spec.monoids):
            total_counts = lax.psum_scatter(counts, axis_name, tiled=True)
        return jax.tree.unflatten(treedef, merged), total_counts

    # generic merge: gather all shard tables and fold with spec.merge
    g_tables = jax.tree.map(lambda t: lax.all_gather(t, axis_name), tables)
    g_counts = lax.all_gather(counts, axis_name)
    S = g_counts.shape[0]

    def fold(carry, xs):
        acc, na = carry
        tab, nb = xs
        out = jax.vmap(spec.merge)(acc, tab, na, nb)
        return (out, na + nb), None

    first = jax.tree.map(lambda t: t[0], g_tables)
    rest = jax.tree.map(lambda t: t[1:], g_tables)
    (merged, _), _ = lax.scan(fold, (first, g_counts[0]),
                              (rest, g_counts[1:]))
    return merged, total_counts


def _combine_local_tables(app, spec, stream: col.PairStream, *,
                          combine_impl, use_kernels):
    """Legacy combine flow's local fold to un-finalized ``(tables, counts)``
    — shared between the distributed shard fn (collective merge follows)
    and the resilient driver (host-side ``spec.merge`` follows)."""
    if spec.strategy == C.STRATEGY_SIZE:
        tables = ()
        counts = jnp.zeros((app.key_space,), jnp.int32).at[stream.keys].add(
            stream.valid.astype(jnp.int32), mode="drop")
    elif spec.strategy == C.STRATEGY_FIRST:
        tables, counts = col.combine_first(spec, stream)
    elif spec.scatter_lowerable and combine_impl in ("auto", "scatter"):
        tables, counts = col.combine_scatter(spec, stream)
    elif spec.mxu_lowerable and combine_impl == "onehot":
        tables, counts = col.combine_onehot(
            spec, stream, onehot_fn=_onehot_kernel(use_kernels))
    else:
        tables, counts = col.combine_segment(spec, stream)
    return tables, counts


def _combine_shard_fn(app, spec, *, combine_impl, use_kernels, axis_name,
                      scatter):
    def fn(local_items):
        stream = map_phase(app, local_items)
        # local fold to tables (un-finalized), then collective merge
        tables, counts = _combine_local_tables(
            app, spec, stream, combine_impl=combine_impl,
            use_kernels=use_kernels)
        return _merge_shard_tables(app, spec, tables, counts,
                                   axis_name=axis_name, scatter=scatter)

    return fn


def _stream_shard_fn(app, spec, *, use_kernels, axis_name, scatter,
                     chunk_pairs, key_block=None):
    """Streaming flow per shard: chunked local fold, then the same O(K)
    monoid collectives as the legacy combine flow."""

    def fn(local_items):
        tables, counts = stream_local_tables(
            app, spec, local_items, chunk_pairs=chunk_pairs,
            use_kernels=use_kernels, key_block=key_block)
        return _merge_shard_tables(app, spec, tables, counts,
                                   axis_name=axis_name, scatter=scatter)

    return fn


def _merge_shard_tables(app, spec, tables, counts, *, axis_name, scatter):
    """Merge per-shard holder tables (monoid collectives or reapply) and
    finalize — the shared tail of the combine and streaming shard fns."""
    if spec.merge is not None:
        tables, counts = merge_tables_collective(
            spec, tables, counts, axis_name, scatter=scatter)
        out = col.finalize_tables(spec, tables, counts,
                                  counts.shape[0])
        return out.keys, out.values, out.counts
    if spec.reapply_ok:
        # Hadoop contract: finalize local partials, re-reduce across shards
        local = col.finalize_tables(spec, tables, counts, app.key_space)
        g_vals = jax.tree.map(lambda v: lax.all_gather(v, axis_name),
                              local.values)
        g_cnt = lax.all_gather(counts, axis_name)  # [S, K]
        return _reapply_merge(app, g_vals, g_cnt)
    raise ValueError("combiner has no cross-shard merge strategy")


def _reapply_merge(app, g_vals, g_cnt):
    """Re-apply the user reduce across stacked per-shard finalized values
    ``[S, K, ...]`` / counts ``[S, K]`` — the Hadoop reapply contract.
    Shared between the all-gather merge and the resilient driver's
    host-side merge (same shard order, same zero-count masking, so the
    recovered merge is bitwise the collective one)."""

    def per_key(k, vals_k, cnt_k):
        # shards with zero count contribute pad values
        order = jnp.argsort(cnt_k == 0)  # valid shards first
        vals_s = jax.tree.map(
            lambda v: jnp.where(
                (cnt_k[order] > 0).reshape((-1,) + (1,) * (v.ndim - 1)),
                v[order], jnp.asarray(app.pad_value, v.dtype)),
            vals_k)
        nvalid = jnp.sum(cnt_k > 0).astype(jnp.int32)
        return app.reduce(k, vals_s, nvalid)

    vals_t = jax.tree.map(lambda v: jnp.moveaxis(v, 0, 1), g_vals)
    keys = jnp.arange(app.key_space, dtype=jnp.int32)
    merged = jax.vmap(per_key)(keys, vals_t, g_cnt.T)
    return keys, merged, jnp.sum(g_cnt, axis=0)


# ---------------------------------------------------------------------------
# Distributed: reduce flow (all-to-all shuffle, O(N) traffic)
# ---------------------------------------------------------------------------


def _wire_format_for(app, stream: col.PairStream, *, num_shards,
                     shuffle_capacity, shuffle_plan=None, wire="raw"):
    """Resolve the shuffle's :class:`wire.WireFormat` from a (possibly
    abstract) pair stream — the single capacity/layout resolution both
    the live all-to-all and the resilient partial builder go through."""
    return wirelib.wire_format(
        key_space=app.key_space, num_shards=num_shards,
        n_pairs=stream.keys.shape[0], value_avals=stream.values,
        codec=wire, capacity=shuffle_capacity, plan=shuffle_plan)


def _localize_recv(app, recv_keys, recv_vals, *, num_shards, shard_index,
                   shuffle_plan=None) -> tuple[col.PairStream, jax.Array]:
    """Rebase a received ``[S, B]`` bucket stack into the shard's local key
    range ``[0, K_local]`` (sentinel = K_local).  Shared between the
    all-to-all receive side and the resilient driver's host-side assembly
    (which concatenates the same buckets in the same source order the
    tiled all-to-all would).

    With a ``shuffle_plan`` the shard's range is its boundary span
    ``[b[i], b[i+1])`` rebased into the STATIC width ``plan.width`` (the
    widest span — shard_map out-widths must be uniform; narrow ranges pad
    with zero-count rows, the same posture as the legacy ceil padding).
    Hot keys are dropped to the sentinel here: their pairs fold into the
    separate hot-table path and re-enter the owner's range at the
    finalize patch."""
    K = app.key_space
    if shuffle_plan is None:
        K_local = -(-K // num_shards)
        lo = shard_index * K_local
        lkeys = jnp.where(recv_keys < K, recv_keys - lo, K_local)
        lkeys = jnp.where((lkeys >= 0) & (lkeys <= K_local), lkeys, K_local)
    else:
        K_local = shuffle_plan.width
        bnd = jnp.asarray(shuffle_plan.boundaries, jnp.int32)
        lo = bnd[shard_index]
        hi = bnd[shard_index + 1]
        inside = (recv_keys >= lo) & (recv_keys < hi)
        if shuffle_plan.hot_keys:
            hk = jnp.asarray(shuffle_plan.hot_keys, jnp.int32)
            inside = inside & ~jnp.any(
                recv_keys[..., None] == hk, axis=-1)
        lkeys = jnp.where(inside, recv_keys - lo, K_local)
    lstream = col.PairStream(
        lkeys.reshape(-1),
        jax.tree.map(lambda v: v.reshape((-1,) + v.shape[2:]), recv_vals),
        K_local)
    return lstream, lo


@jax.named_scope(trace.SHUFFLE)
def _shuffle_pairs(app, stream: col.PairStream, *, axis_name, num_shards,
                   shuffle_capacity, shuffle_plan=None, wire="raw"
                   ) -> tuple[col.PairStream, jax.Array, jax.Array,
                              tuple]:
    """Key-partitioned all-to-all of encoded pairs (the reduce-flow
    shuffle).

    The send buckets are built and encoded by the wire layer
    (``distributed/wire.py``) under the ``wire`` codec; every encoded
    leaf keeps a leading destination axis, so the tiled all-to-all
    routes the compressed tree unchanged and the receive side decodes
    its own rows back to exact ``(keys, vals)`` buckets.

    Returns the received local stream (keys rebased into ``[0, K_local]``),
    this shard's key offset, the shard's overflow count (valid pairs past
    the per-destination capacity — see :func:`wire.bucketize`), and the
    decoded flat received ``(keys, vals)`` — the hot-key split path folds
    its partial tables from the latter, since hot pairs are routed
    OUTSIDE their owner's range and dropped by the localization."""
    fmt = _wire_format_for(app, stream, num_shards=num_shards,
                           shuffle_capacity=shuffle_capacity,
                           shuffle_plan=shuffle_plan, wire=wire)
    sk, sv, overflow = wirelib.bucketize(fmt, stream, shuffle_plan)
    enc = wirelib.encode(fmt, sk, sv)

    recv_enc = jax.tree.map(
        lambda v: lax.all_to_all(v, axis_name, split_axis=0,
                                 concat_axis=0, tiled=True),
        enc)

    me = lax.axis_index(axis_name)
    recv_keys, recv_vals = wirelib.decode(fmt, recv_enc, me)
    lstream, lo = _localize_recv(app, recv_keys, recv_vals,
                                 num_shards=num_shards, shard_index=me,
                                 shuffle_plan=shuffle_plan)
    flat_recv = (recv_keys.reshape(-1),
                 jax.tree.map(lambda v: v.reshape((-1,) + v.shape[2:]),
                              recv_vals))
    return lstream, lo, overflow, flat_recv


def _reduce_range(app, lstream: col.PairStream, lo):
    """Reduce-flow tail for one key range: group the localized stream and
    re-apply the user reduce with globally-rebased keys.  Shared between
    the all-to-all shard fn and the resilient driver's per-range replay."""

    def reduce_global(k, vals, cnt):
        return app.reduce(k + lo, vals, cnt)

    grouped = col.reduce_flow(
        reduce_global, lstream,
        max_values_per_key=app.max_values_per_key,
        pad_value=app.pad_value)
    # output stays key-sharded: [K_local] per shard -> [S*K_local] global
    return grouped.keys + lo, grouped.values, grouped.counts


def _reduce_shard_fn(app, *, axis_name, num_shards, shuffle_capacity,
                     shuffle_plan=None, wire="raw"):
    def fn(local_items):
        stream = map_phase(app, local_items)
        lstream, lo, overflow, _ = _shuffle_pairs(
            app, stream, axis_name=axis_name, num_shards=num_shards,
            shuffle_capacity=shuffle_capacity, shuffle_plan=shuffle_plan,
            wire=wire)
        return _reduce_range(app, lstream, lo) + (overflow[None],)

    return fn


def _fold_hot_tables(app, spec, recv_keys, recv_vals, shuffle_plan):
    """Fold a shard's received hot-key pairs into ``[H, ...]`` partial
    holder tables (H = number of split keys; identity rows for hot keys
    this shard received nothing of).  The split destinations' partials
    recombine exactly through the monoid merge — the reason hot splitting
    is gated on :func:`skew.hot_split_ok`."""
    hk = jnp.asarray(shuffle_plan.hot_keys, jnp.int32)
    H = len(shuffle_plan.hot_keys)
    eq = recv_keys[:, None] == hk[None, :]
    hidx = jnp.where(jnp.any(eq, axis=1),
                     jnp.argmax(eq, axis=1).astype(jnp.int32), H)
    sc = col.StreamCombiner(spec, H, app.value_aval)
    state = sc.fold_chunk(sc.init_state(),
                          col.PairStream(hidx, recv_vals, H))
    return sc.tables_counts(state)


def _patch_hot_rows(spec, tables, counts, hot_tables, hot_counts,
                    shuffle_plan, shard_index, width):
    """Land the MERGED hot-key aggregates back into the range tables of
    each key's owner shard (rows the localization left at identity),
    right before finalize.  Non-owner shards patch the dropped sentinel
    row ``width`` (mode="drop")."""
    hk = jnp.asarray(shuffle_plan.hot_keys, jnp.int32)
    owners = jnp.asarray(
        [shuffle_plan.hot_owner(k) for k in shuffle_plan.hot_keys],
        jnp.int32)
    bnd = jnp.asarray(shuffle_plan.boundaries, jnp.int32)
    rows = jnp.where(owners == shard_index, hk - bnd[owners], width)
    counts = counts.at[rows].set(hot_counts.astype(counts.dtype),
                                 mode="drop")
    tables = jax.tree.map(
        lambda t, h: t.at[rows].set(h.astype(t.dtype), mode="drop"),
        tables, hot_tables)
    return tables, counts


def _sort_shard_fn(app, spec, *, axis_name, num_shards, shuffle_capacity,
                   use_kernels, chunk_pairs, bucket_size=None,
                   on_fallback=None, shuffle_plan=None,
                   wire="raw"):
    """Sort flow per shard: the reduce-flow key-partitioned all-to-all
    (bucket boundaries == shard key ranges, O(N) traffic), then the local
    sort collector folds the received presorted-by-range segment in
    ``chunk_pairs``-sized pieces and finalizes its key range.  Output
    key-sharded like the reduce flow.

    The shard key ranges are the top-level radix digits: the all-to-all
    is the distributed form of the partition (wire format unchanged from
    the reduce flow), and each shard sizes the buckets of its own ``K/S``
    range.

    With a skew ``shuffle_plan``, the ranges are the sampled balanced
    boundaries and each hot key's occurrences arrive split over several
    shards: every shard folds its hot slice into ``[H, ...]`` partial
    tables, a monoid collective merges them, and the owner shard patches
    the merged row into its range before finalize — exact by the monoid
    merge argument."""

    def fn(local_items):
        stream = map_phase(app, local_items)
        lstream, lo, overflow, flat_recv = _shuffle_pairs(
            app, stream, axis_name=axis_name, num_shards=num_shards,
            shuffle_capacity=shuffle_capacity, shuffle_plan=shuffle_plan,
            wire=wire)
        hot_patch = None
        if shuffle_plan is not None and shuffle_plan.hot_keys:
            ht, hc = _fold_hot_tables(app, spec, flat_recv[0],
                                      flat_recv[1], shuffle_plan)
            mt, mc = merge_tables_collective(spec, ht, hc, axis_name)
            me = lax.axis_index(axis_name)

            def hot_patch(tables, counts):
                return _patch_hot_rows(spec, tables, counts, mt, mc,
                                       shuffle_plan, me,
                                       lstream.key_space)
        out = _sort_range_fold(app, spec, lstream, lo,
                               use_kernels=use_kernels,
                               chunk_pairs=chunk_pairs,
                               bucket_size=bucket_size,
                               on_fallback=on_fallback,
                               hot_patch=hot_patch)
        return out + (overflow[None],)

    return fn


def _sort_range_tables(app, spec, lstream: col.PairStream, *,
                       use_kernels, chunk_pairs, bucket_size=None,
                       on_fallback=None):
    """Fold one localized key range to UN-finalized ``(tables, counts)``
    with the sort collector in ``chunk_pairs``-sized pieces — the shared
    core of :func:`_sort_range_fold` and the resilient driver's hot-split
    two-pass phase B (which must patch merged hot rows in between)."""
    K_local = lstream.key_space
    uk = _sort_kernels_usable(spec, app.value_aval, use_kernels,
                                 on_fallback)
    sc = col.SortCombiner(
        spec, K_local, app.value_aval,
        sort_fold_fn=_sort_fold_kernel(uk, bucket_size))
    state = sc.init_state()
    n = lstream.keys.shape[0]
    if n <= chunk_pairs:
        state = sc.fold_chunk(state, lstream)
    else:
        n_chunks = -(-n // chunk_pairs)
        pad = n_chunks * chunk_pairs - n
        keys_p = jnp.pad(lstream.keys, (0, pad),
                         constant_values=K_local).reshape(
            n_chunks, chunk_pairs)
        vals_p = jax.tree.map(
            lambda v: jnp.pad(
                v, [(0, pad)] + [(0, 0)] * (v.ndim - 1)).reshape(
                (n_chunks, chunk_pairs) + v.shape[1:]),
            lstream.values)

        def body(state, xs):
            ck, cv = xs
            return sc.fold_chunk(
                state, col.PairStream(ck, cv, K_local)), None

        state, _ = lax.scan(body, state, (keys_p, vals_p))
    return sc.tables_counts(state)


def _sort_range_fold(app, spec, lstream: col.PairStream, lo, *,
                     use_kernels, chunk_pairs, bucket_size=None,
                     on_fallback=None, hot_patch=None):
    """Sort-flow tail for one key range: fold the localized presorted-by-
    range segment with the local sort collector in ``chunk_pairs``-sized
    pieces and finalize the range.  Shared between the all-to-all shard fn
    and the resilient driver's per-range replay (identical chunking, so a
    recovered range is bitwise the no-failure range).  ``hot_patch`` (the
    skew hot-split path) rewrites the merged hot rows into the tables
    between the fold and the finalize."""
    K_local = lstream.key_space
    tables, counts = _sort_range_tables(
        app, spec, lstream, use_kernels=use_kernels,
        chunk_pairs=chunk_pairs, bucket_size=bucket_size,
        on_fallback=on_fallback)
    if hot_patch is not None:
        tables, counts = hot_patch(tables, counts)
    keys = jnp.arange(K_local, dtype=jnp.int32) + lo
    with jax.named_scope(trace.FINALIZE):
        vals = jax.vmap(spec.finalize)(keys, tables, counts)
    return keys, vals, counts


# ---------------------------------------------------------------------------
# Top-level distributed entry point
# ---------------------------------------------------------------------------


def _distributed_tiling(app, plan, items, num_shards, *, use_kernels,
                        chunk_pairs, key_block):
    """Per-shard streaming tiling for a distributed run: each shard sees
    ``ceil(n_items / S)`` items, so the autotune hint is the SHARD's pair
    count, not the global one.  Shared by ``run_distributed`` and
    ``run_resilient`` so the resilient per-shard partials are folded with
    exactly the tiling the no-failure shards use (bitwise parity)."""
    if plan.flow == "stream" and (chunk_pairs is None or key_block is None):
        from repro.core import autotune as at

        n_items = jax.tree.leaves(items)[0].shape[0]
        n_shard_pairs = (max(-(-n_items // num_shards), 1)
                         * max(app.emit_capacity, 1))
        tiling = at.autotune_stream(
            app, plan.spec, use_kernels=use_kernels,
            n_pairs_hint=n_shard_pairs)
        if chunk_pairs is None:
            chunk_pairs = tiling.chunk_pairs
        if key_block is None and tiling.blocked:
            key_block = tiling.key_block
    if plan.flow == "sort" and chunk_pairs is None:
        chunk_pairs = DEFAULT_SORT_CHUNK_PAIRS
    if chunk_pairs is None:
        chunk_pairs = DEFAULT_CHUNK_PAIRS
    return chunk_pairs, key_block


def _replicated(a):
    """``a`` whole on every device of its mesh (unchanged when it is not
    laid out over one)."""
    sh = getattr(a, "sharding", None)
    if isinstance(sh, jax.sharding.NamedSharding):
        return jax.device_put(a, jax.sharding.NamedSharding(sh.mesh, P()))
    return a


def _densify_ranges(keys, values, counts, shuffle_plan):
    """Scatter concatenated boundary-range outputs into the dense
    ``keys == arange(K)`` layout.

    The legacy fixed-width layout has row index == key by construction
    (contiguous ``ceil(K/S)`` spans, padding at the tail), so consumers
    may index values by key.  Balanced boundary ranges pad each shard to
    the WIDEST span, so row != key — this rebuilds the dense layout.

    Which rows are authoritative is STATIC: shard ``s``'s output row
    ``i`` is real iff ``i`` is inside its actual boundary span (rows past
    it are pads whose keys belong to the NEXT shard's range and must not
    shadow it).  Every key has exactly one authoritative row, so the
    scatter covers all of [0, K) — including count-0 keys, whose rows
    carry the flow's own absent-key value (finalize-of-identity /
    reduce-over-pads), keeping the dense result bitwise the single-host
    one."""
    import numpy as np

    K = shuffle_plan.key_space
    b = shuffle_plan.boundaries
    W = shuffle_plan.width
    # the rows are laid out over the mesh; the scatter below indexes them
    # by key, so it reads them whole (a mesh with explicit axes refuses
    # an index that mixes a sharded and an unsharded operand)
    keys, values, counts = jax.tree.map(_replicated, (keys, values, counts))
    spans = np.asarray([b[s + 1] - b[s]
                        for s in range(shuffle_plan.num_shards)])
    auth = jnp.asarray(
        (np.arange(W)[None, :] < spans[:, None]).reshape(-1))
    slot = jnp.where(auth, keys, K)
    dcounts = jnp.zeros((K,), counts.dtype).at[slot].set(
        jnp.where(auth, counts, 0), mode="drop")
    dvalues = jax.tree.map(
        lambda v: jnp.zeros((K,) + v.shape[1:], v.dtype)
        .at[slot].set(
            jnp.where(auth.reshape((-1,) + (1,) * (v.ndim - 1)), v,
                      jnp.zeros((), v.dtype)), mode="drop"),
        values)
    return jnp.arange(K, dtype=jnp.int32), dvalues, dcounts


def _surface_overflow(plan, overflow, *, strict: bool,
                      shuffle_capacity) -> None:
    """Report shuffle overflow (pairs past the per-destination capacity).

    ``overflow`` is the per-source-shard count array.  Concrete values are
    checked on the host: a nonzero count fires a
    :class:`LoweringFallbackWarning` through the plan sink (once, with the
    counts in ``plan.diagnostics``) or raises under ``strict``.  When the
    caller wrapped ``run_distributed`` in an outer ``jax.jit`` the counts
    are tracers and the check is SKIPPED: a host callback here would plant
    an all-gather + custom-call into the compiled graph, corrupting the
    collective roofline story the dry-run benchmarks measure (strict mode
    raises at trace time instead of failing silently).  The plain
    ``run_distributed`` call — which jits internally — always checks."""
    import numpy as np

    def report(ovf_host) -> None:
        ovf_host = np.asarray(ovf_host)
        total = int(ovf_host.sum())
        if total == 0:
            return
        msg = (f"distributed shuffle overflow: {total} pairs exceeded the "
               f"per-destination capacity "
               f"(shuffle_capacity={shuffle_capacity or 'auto(2x uniform)'}; "
               f"per-shard counts {ovf_host.reshape(-1).tolist()}) and were "
               f"dropped — the key distribution is skewed past the bucket "
               f"envelope; raise shuffle_capacity (or rebalance the key "
               f"ranges)")
        if strict:
            raise ValueError(msg)
        # warn UNconditionally, not through the once-per-plan fallback
        # latch: overflow means the OUTPUT is wrong, not that a lowering
        # downgraded, and must not be swallowed because some earlier
        # lowering fallback already spent the plan's one warning
        import warnings

        warnings.warn(msg, col.LoweringFallbackWarning, stacklevel=3)
        if plan is not None and msg not in plan.diagnostics:
            plan.diagnostics += (msg,)

    if isinstance(overflow, jax.core.Tracer):
        if strict:
            raise ValueError(
                "strict_shuffle=True cannot be checked under an outer "
                "jax.jit (the overflow count is a tracer); call "
                "run_distributed un-jitted or check plan.diagnostics")
        return
    with trace.span(trace.SYNC):
        overflow = np.asarray(overflow)
    report(overflow)


def run_distributed(
    app,
    plan,
    items,
    *,
    mesh,
    data_axis: str = "data",
    combine_impl: str = "auto",
    use_kernels: bool = False,
    scatter_output: bool = False,
    shuffle_capacity: int | None = None,
    chunk_pairs: int | None = None,
    key_block: int | None = None,
    bucket_size: int | None = None,
    strict_shuffle: bool = False,
    shuffle_plan=None,
    wire: str = "raw",
):
    """shard_map the chosen flow over ``data_axis`` of ``mesh``.

    Returns (keys, values, counts); stream/combine flow results are
    replicated (or key-sharded with ``scatter_output=True``), reduce and
    sort flow results are key-sharded over the data axis (padded to
    ceil(K/S)*S keys).

    ``chunk_pairs=None`` (the default) re-derives the streaming tiling from
    the PER-SHARD item count — each shard sees ``ceil(n_items / S)`` items,
    so reusing a tiling autotuned for the global workload would oversize
    the chunk (and undersize the key block) by the shard factor.  Pass an
    int to pin the per-shard chunk explicitly.

    The reduce/sort flows' all-to-all shuffle counts pairs past its
    per-destination capacity (key-skew overflow): a nonzero count fires a
    :class:`LoweringFallbackWarning` and lands in ``plan.diagnostics``, or
    raises a ``ValueError`` under ``strict_shuffle=True`` — it is never
    silently dropped anymore.
    """
    S = mesh.shape[data_axis]
    # per-shard autotune (not the local tiling): hint with the shard's
    # pair count so the chunk knee and the key block match what each
    # shard actually folds.
    chunk_pairs, key_block = _distributed_tiling(
        app, plan, items, S, use_kernels=use_kernels,
        chunk_pairs=chunk_pairs, key_block=key_block)
    jitted, post = build_distributed_fn(
        app, plan, mesh=mesh, data_axis=data_axis,
        combine_impl=combine_impl, use_kernels=use_kernels,
        scatter_output=scatter_output, shuffle_capacity=shuffle_capacity,
        chunk_pairs=chunk_pairs, key_block=key_block,
        bucket_size=bucket_size, shuffle_plan=shuffle_plan, wire=wire)
    return post(jitted(items), strict_shuffle=strict_shuffle)


def build_distributed_fn(
    app,
    plan,
    *,
    mesh,
    data_axis: str = "data",
    combine_impl: str = "auto",
    use_kernels: bool = False,
    scatter_output: bool = False,
    shuffle_capacity: int | None = None,
    chunk_pairs: int | None = None,
    key_block: int | None = None,
    bucket_size: int | None = None,
    shuffle_plan=None,
    wire: str = "raw",
):
    """Build the persistent distributed executable for one (plan, mesh).

    Returns ``(jitted, postprocess)``: ``jitted(items)`` is a jitted
    shard_map of the chosen flow (jit's own cache makes repeat calls with
    same-shaped items dispatch without re-tracing — the staged ``Compiled``
    holds this object across calls), and ``postprocess(out,
    strict_shuffle=...)`` surfaces shuffle overflow and strips the overflow
    channel, returning ``(keys, values, counts)``.  ``chunk_pairs`` /
    ``key_block`` must already be resolved to the PER-SHARD tiling (see
    :func:`_distributed_tiling`)."""
    S = mesh.shape[data_axis]
    if plan.flow in ("combine", "stream"):
        if plan.flow == "stream":
            fn = _stream_shard_fn(app, plan.spec, use_kernels=use_kernels,
                                  axis_name=data_axis, scatter=scatter_output,
                                  chunk_pairs=chunk_pairs,
                                  key_block=key_block)
        else:
            fn = _combine_shard_fn(app, plan.spec, combine_impl=combine_impl,
                                   use_kernels=use_kernels,
                                   axis_name=data_axis,
                                   scatter=scatter_output)
        out_spec = (P(data_axis) if scatter_output else P(),
                    P(data_axis) if scatter_output else P(),
                    P(data_axis) if scatter_output else P())
    elif plan.flow == "sort":
        fn = _sort_shard_fn(app, plan.spec, axis_name=data_axis,
                            num_shards=S, shuffle_capacity=shuffle_capacity,
                            use_kernels=use_kernels, chunk_pairs=chunk_pairs,
                            bucket_size=bucket_size,
                            on_fallback=_plan_fallback_cb(plan),
                            shuffle_plan=shuffle_plan, wire=wire)
        out_spec = (P(data_axis), P(data_axis), P(data_axis), P(data_axis))
    else:
        if shuffle_plan is not None and shuffle_plan.hot_keys:
            raise ValueError(
                "hot-key splitting needs the sort flow's monoid tables; "
                "the reduce flow takes boundary rebalancing only")
        fn = _reduce_shard_fn(app, axis_name=data_axis, num_shards=S,
                              shuffle_capacity=shuffle_capacity,
                              shuffle_plan=shuffle_plan, wire=wire)
        out_spec = (P(data_axis), P(data_axis), P(data_axis), P(data_axis))
    if (shuffle_plan is not None
            and plan.flow in ("reduce", "sort")
            and shuffle_plan.num_shards != S):
        raise ValueError(
            f"shuffle_plan was derived for {shuffle_plan.num_shards} "
            f"shards but the mesh data axis has {S}")

    sm = jax.shard_map(fn, mesh=mesh, in_specs=(P(data_axis),),
                       out_specs=out_spec, check_vma=False)
    jitted = jax.jit(sm)

    def postprocess(out, *, strict_shuffle: bool = False):
        if plan.flow not in ("reduce", "sort"):
            return out
        keys, values, counts, overflow = out
        _surface_overflow(plan, overflow, strict=strict_shuffle,
                          shuffle_capacity=shuffle_capacity)
        with trace.span(trace.POST):
            if shuffle_plan is not None:
                return _densify_ranges(keys, values, counts, shuffle_plan)
            return keys, values, counts

    return jitted, postprocess


# ---------------------------------------------------------------------------
# Fault-tolerant elastic driver: deterministic shard re-execution +
# partial-aggregate recovery (run_resilient)
# ---------------------------------------------------------------------------


def _merge_tables_host(spec, tables_seq, counts_seq):
    """Host-side UN-finalized merge of stacked partial holder tables —
    the shared monoid/``spec.merge`` core of :func:`merge_partial_tables`
    and the resilient hot-split recombine (which must patch the merged
    hot rows into range tables before finalizing)."""
    leaves_seq = [jax.tree.leaves(t) for t in tables_seq]
    treedef = jax.tree.structure(tables_seq[0])
    if (spec.monoids is not None
            and len(spec.monoids) == len(leaves_seq[0])):
        merged = []
        for i, mono in enumerate(spec.monoids):
            stack = jnp.stack([ls[i] for ls in leaves_seq])
            try:
                red = mono.dense_reduce(stack, axis=0)
            except KeyError:  # no dense lowering: shard-0 table (the
                red = stack[0]  # collective all-gather fallback's g[0])
            merged.append(red.astype(leaves_seq[0][i].dtype))
        return jax.tree.unflatten(treedef, merged)
    tables = tables_seq[0]
    na = counts_seq[0]
    for tab, nb in zip(tables_seq[1:], counts_seq[1:]):
        tables = jax.vmap(spec.merge)(tables, tab, na, nb)
        na = na + nb
    return tables


@jax.named_scope(trace.MERGE)
def merge_partial_tables(app, spec, tables_seq, counts_seq):
    """Merge per-shard partial holder tables in shard order, host side.

    The mirror of :func:`merge_tables_collective` without collectives: the
    derived combiner is a *monoid*, so partials re-merged after a failure
    (some recomputed, some restored from checkpoints) give bitwise the
    answer of the uninterrupted run — MapReduce's speculative re-execution
    recast at the combiner layer.  Per-leaf monoid reductions are taken
    over the stacked shard axis exactly like the collective lowering; the
    generic ``spec.merge`` and Hadoop-reapply paths replicate the
    collective versions' shard order.
    """
    counts_stack = jnp.stack(counts_seq)  # [S, K]
    total_counts = jnp.sum(counts_stack, axis=0).astype(counts_seq[0].dtype)

    if spec.merge is not None:
        tables = _merge_tables_host(spec, tables_seq, counts_seq)
        out = col.finalize_tables(spec, tables, total_counts,
                                  total_counts.shape[0])
        return out.keys, out.values, out.counts

    if spec.reapply_ok:
        g_vals = jax.tree.map(
            lambda *vs: jnp.stack(vs),
            *[col.finalize_tables(spec, t, c, app.key_space).values
              for t, c in zip(tables_seq, counts_seq)])
        return _reapply_merge(app, g_vals, counts_stack)
    raise ValueError("combiner has no cross-shard merge strategy")


def _rows_on_device(a, lo: int, hi: int, device):
    """Rows ``[lo, hi)`` of ``a`` committed to ``device``.

    A ``jax.Array`` split along its leading axis only (any sharding over a
    mesh, Explicit axes included) is assembled from the device shards that
    hold those rows, so the data moves chip to chip and never through the
    host; any other layout moves whole onto ``device`` first."""
    if not isinstance(a, jax.Array):
        return jax.device_put(a[lo:hi], device)
    full = tuple(slice(None) for _ in a.shape[1:])
    pieces = {}
    for sh in a.addressable_shards:
        rows = sh.index[0] if sh.index else slice(None)
        if tuple(sh.index[1:]) != full:
            return jax.device_put(a, device)[lo:hi]
        r0, r1, _ = rows.indices(a.shape[0])
        if r1 <= lo or r0 >= hi:
            continue
        if (r0, r1) not in pieces or sh.device == device:
            pieces[(r0, r1)] = sh.data
    parts = [jax.device_put(pieces[r][max(lo, r[0]) - r[0]:
                                      min(hi, r[1]) - r[0]], device)
             for r in sorted(pieces)]
    return parts[0] if len(parts) == 1 else jnp.concatenate(parts)


def run_resilient(
    app,
    plan,
    items,
    *,
    mesh=None,
    num_hosts: int | None = None,
    num_shards: int | None = None,
    data_axis: str = "data",
    step: int = 0,
    ckpt_dir: str | None = None,
    inject=None,
    timeout_s: float = 60.0,
    straggler_lag: int = 1,
    combine_impl: str = "auto",
    use_kernels: bool = False,
    shuffle_capacity: int | None = None,
    chunk_pairs: int | None = None,
    key_block: int | None = None,
    bucket_size: int | None = None,
    strict_shuffle: bool = False,
    shuffle_plan=None,
    wire: str = "raw",
    coord=None,
    retry=None,
    chaos=None,
    jit_cache: dict | None = None,
):
    """Fault-tolerant distributed MapReduce driver.

    Runs ``plan.flow`` over ``items`` partitioned into ``num_shards``
    deterministic shards (``fault.shard_for``'s stateless assignment over
    ``num_hosts`` ranks) and survives the failure modes a production
    deployment actually has:

    * **Shard loss** — every shard's partial aggregate (holder tables for
      the stream/combine flows; per-destination all-to-all send buckets
      for the reduce/sort flows) is a pure function of the shard's items,
      so a lost shard is *recomputed* on the deterministic backup rank
      (``fault.backup_assignment``) with a bitwise-identical result.
    * **Partial-aggregate recovery** — with ``ckpt_dir`` set, each shard
      snapshot lands in ``ckpt.shard_partial_dir(ckpt_dir, shard)``
      (atomic, ``checkpoint/ckpt.py``); recovery prefers restoring the
      checkpointed partial over re-execution, and the monoid merge makes
      restored and recomputed partials interchangeable.
    * **Stragglers** — hosts alive but lagging (``HeartbeatMonitor``) get
      their shards speculatively re-executed on the backup rank;
      determinism makes the race between original and backup a non-event.
    * **Elastic host-count change** — ``inject.resize_to`` (or a real
      cluster resize feeding the same path) remeshes over the surviving
      devices with ``elastic.best_mesh`` and re-runs ONLY the shards whose
      partials were lost with the removed hosts; the number of shards —
      and with it the all-to-all key ranges the sort/reduce flows
      partition by — stays fixed, so the re-partition boundary is the
      existing bucket layout and the merge is unchanged.

    Failure detection runs through a real :class:`fault.HeartbeatMonitor`
    over a synthetic clock; ``inject`` (a :class:`fault.FaultInjection`)
    scripts which hosts die, lag, or leave.  The recovery ledger is
    returned as a :class:`fault.RecoveryLog` and summarized onto
    ``plan.recovery`` (see ``MapReduce.explain()``).

    **Durable control plane** — with ``coord`` set (a
    ``coordination.CoordinationStore``, a ``KVStore``, or a directory
    path) or a ``chaos`` plan given, the control plane moves onto the
    durable store: heartbeats become ``hosts/<h>`` records, the
    coordinator holds a ``lease`` (``coordination.elect`` — lowest live
    rank — is the only host allowed to adopt an expired one), and every
    completed shard lands in the durable ``ledger/``.  If the
    coordinator dies, the lowest-ranked survivor adopts the lease AND
    the ledger from the store and resumes phase B from the durable
    per-shard partials — bitwise-identical, because partials are pure
    functions of their shards.  ``retry`` (a
    ``coordination.RetryPolicy``) bounds every store read/write and
    shard restore with a deterministic capped backoff; every retry,
    lease adoption, quarantine, and partition event is recorded onto
    ``plan.recovery`` — no silent retries.  ``chaos`` (a
    ``chaos.ChaosPlan``) scripts multi-fault drills on top:
    kill-coordinator, corrupt-checkpoint-N (detected by the checksum
    layer, quarantined to ``*.corrupt``, recovered by deterministic
    recompute), partition-host, delayed-store, stragglers.

    Returns ``(keys, values, counts, log)`` where the first three are
    bitwise what the fault-free ``run_distributed`` produces on a
    ``num_shards``-wide mesh: stream/combine results span the full key
    space; reduce/sort results are the key-range-concatenated
    ``ceil(K/S)*S`` layout.
    """
    import os

    import numpy as np

    from repro.checkpoint import ckpt
    from repro.distributed import chaos as chaoslib
    from repro.distributed import coordination as coordlib
    from repro.distributed import fault as flt

    inject = inject if inject is not None else flt.FaultInjection()
    if mesh is not None:
        mesh_hosts = mesh.shape[data_axis]
    else:
        mesh_hosts = None
    H = num_hosts if num_hosts is not None else (mesh_hosts or 1)
    S = num_shards if num_shards is not None else (mesh_hosts or H)
    if H <= 0 or S <= 0:
        raise ValueError(f"need positive host/shard counts, got {H}/{S}")
    n_items = jax.tree.leaves(items)[0].shape[0]
    if n_items % S:
        raise ValueError(
            f"n_items={n_items} must divide into num_shards={S} (the same "
            f"contract shard_map's data-axis partition enforces)")
    per = n_items // S
    spec = plan.spec
    flow = plan.flow
    cb = _plan_fallback_cb(plan)
    chunk_pairs, key_block = _distributed_tiling(
        app, plan, items, S, use_kernels=use_kernels,
        chunk_pairs=chunk_pairs, key_block=key_block)
    if flow in ("stream", "sort", "combine") and spec is None:
        raise ValueError(f"{flow} flow needs a derived combiner spec")

    # the host driver rebuilds its phase closures per call; `jit_cache`
    # (held by the caller, e.g. per-MapReduce) keys the jitted fns by every
    # capture that reaches a trace, so steady-state calls skip the
    # re-trace/re-compile and pay only dispatch
    _jits = jit_cache if jit_cache is not None else {}
    _jkey = (flow, H, S, per, chunk_pairs, key_block, use_kernels,
             combine_impl, shuffle_capacity, strict_shuffle, bucket_size,
             wire,
             shuffle_plan.epoch if shuffle_plan is not None else None)

    def _cached_jit(name, fn):
        got = _jits.get((name,) + _jkey)
        if got is None:
            got = _jits[(name,) + _jkey] = jax.jit(fn)
        return got

    # host h computes on device h of the mesh: a shard's items are
    # gathered there from the chips that hold them (an Explicit-axis mesh
    # cannot slice its sharded axis in place), and its partial stays there
    # until phase B gathers every partial on the coordinator's device
    host_devs = (list(mesh.devices.reshape(-1)) if mesh is not None
                 else None)

    def host_device(h: int):
        return host_devs[h % len(host_devs)]

    def shard_slice(s: int, h: int):
        lo, hi = s * per, (s + 1) * per
        if host_devs is None:
            return jax.tree.map(lambda a: a[lo:hi], items)
        log.devices[s] = host_device(h).id
        return jax.tree.map(
            lambda a: _rows_on_device(a, lo, hi, host_device(h)), items)

    shard_spec = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct((per,) + tuple(a.shape[1:]),
                                       a.dtype), items)

    # -- the per-shard partial: a pure deterministic function of the shard --
    if flow == "stream":
        def _partial(local_items):
            tables, counts = stream_local_tables(
                app, spec, local_items, chunk_pairs=chunk_pairs,
                use_kernels=use_kernels, key_block=key_block)
            return {"tables": tables, "counts": counts}
    elif flow == "combine":
        def _partial(local_items):
            tables, counts = _combine_local_tables(
                app, spec, map_phase(app, local_items),
                combine_impl=combine_impl, use_kernels=use_kernels)
            return {"tables": tables, "counts": counts}
    else:  # reduce | sort: the all-to-all wire format is the partial
        if (shuffle_plan is not None and shuffle_plan.hot_keys
                and flow != "sort"):
            raise ValueError(
                "hot-key splitting needs the sort flow's monoid tables; "
                "the reduce flow takes boundary rebalancing only")
        if shuffle_plan is not None and shuffle_plan.num_shards != S:
            raise ValueError(
                f"shuffle_plan was derived for {shuffle_plan.num_shards} "
                f"shards but run_resilient partitions into {S}")
        # the wire epoch rides in the checkpointable partial: it
        # fingerprints the FULL wire layout (codec, capacity envelope,
        # boundary/hot ranges via the skew plan's epoch, value dtypes),
        # so a durable partial bucketized under DIFFERENT boundaries or
        # encoded by a different codec is never merged with this run's —
        # restore rejects on mismatch and falls back to the
        # deterministic recompute, keeping recovery bitwise
        wire_fmt = _jits.get(("wire_fmt",) + _jkey)
        if wire_fmt is None:
            ak, av = jax.eval_shape(
                lambda it: (lambda st: (st.keys, st.values))(
                    map_phase(app, it)), shard_spec)
            wire_fmt = _jits[("wire_fmt",) + _jkey] = wirelib.wire_format(
                key_space=app.key_space, num_shards=S,
                n_pairs=ak.shape[0], value_avals=av,
                codec=wire, capacity=shuffle_capacity, plan=shuffle_plan)

        def _partial(local_items):
            sk, sv, overflow = wirelib.bucketize(
                wire_fmt, map_phase(app, local_items), shuffle_plan)
            return {"wire": wirelib.encode(wire_fmt, sk, sv),
                    "overflow": overflow,
                    "wire_epoch": jnp.full((1,), wire_fmt.epoch,
                                           jnp.uint32)}

    partial_fn = _cached_jit("partial", _partial)
    partial_example = _jits.get(("partial_example",) + _jkey)
    if partial_example is None:
        partial_example = _jits[("partial_example",) + _jkey] = (
            jax.eval_shape(_partial, shard_spec))

    def save_partial(s: int, p) -> None:
        if ckpt_dir is None:
            return

        def _save():
            ckpt.save(ckpt.shard_partial_dir(ckpt_dir, s), step, p)

        if coord is not None:
            coord.retried(f"save shard {s} partial", _save, kind="ckpt")
        else:
            _save()

    def try_restore(s: int):
        """Restore a shard's durable partial; a checksum failure is
        quarantined and logged, and the caller falls back to the
        deterministic recompute (bitwise-identical by construction)."""
        if ckpt_dir is None:
            return None
        d = ckpt.shard_partial_dir(ckpt_dir, s)
        if not ckpt.has_step(d, step):
            return None

        def _load():
            return ckpt.restore(d, partial_example, step=step)

        try:
            if coord is not None:
                tree, _ = coord.retried(f"restore shard {s} partial",
                                        _load, kind="ckpt")
            else:
                tree, _ = _load()
        except ckpt.CheckpointCorruptError as e:
            log.corrupt.append(s)
            events.append(
                f"checkpoint: shard {s} partial failed verification "
                f"({e.reason}); quarantined, falling back to "
                f"deterministic recompute")
            return None
        except (ValueError, KeyError):
            # the npz leaf structure no longer matches this run's wire
            # layout (e.g. the codec changed between runs): the partial
            # is stale by construction, treat like an epoch mismatch
            log.epoch_rejects.append(s)
            events.append(
                f"checkpoint: shard {s} partial has a different wire "
                f"layout than this run (codec/shape mismatch); discarded "
                f"and the deterministic recompute takes over")
            return None
        if flow in ("reduce", "sort"):
            got = int(np.asarray(tree["wire_epoch"]).reshape(-1)[0])
            if got != wire_fmt.epoch:
                log.epoch_rejects.append(s)
                events.append(
                    f"checkpoint: shard {s} partial carries wire epoch "
                    f"{got} != this run's {wire_fmt.epoch} (the skew "
                    f"boundaries or wire codec changed between runs); "
                    f"discarded — its send buckets mean different key "
                    f"ranges or bits — and the deterministic recompute "
                    f"takes over")
                return None
        return tree

    # -- durable control plane: coordination store + chaos resolution -------
    log = flt.RecoveryLog(num_hosts=H, num_shards=S, step=step)
    clock = flt.StepClock()
    coordinated = (coord is not None or chaos is not None
                   or retry is not None)
    lease = None
    partitioned: set[int] = set()
    if coordinated:
        if isinstance(coord, coordlib.CoordinationStore):
            coord.clock = clock  # rebind onto the drill's synthetic clock
            coord.sleep = clock.advance
            if retry is not None:
                coord.retry = retry
        else:
            if isinstance(coord, coordlib.KVStore):
                kv = coord
            elif isinstance(coord, str):
                kv = coordlib.FileKVStore(coord)
            elif ckpt_dir is not None:
                kv = coordlib.FileKVStore(os.path.join(ckpt_dir, "coord"))
            else:
                kv = coordlib.MemKVStore()
            coord = coordlib.CoordinationStore(
                kv, retry=retry, lease_ttl_s=timeout_s,
                clock=clock, sleep=clock.advance)
        events = coord.events
        coordinator = coordlib.elect(range(H))
        if chaos is not None:
            inject = chaos.resolve_injection(inject, coordinator)
            partitioned = set(chaos.partition_hosts)
            if chaos.store_fail_ops:
                coord.inject_store_faults(chaos.store_fail_ops,
                                          chaos.store_fail_kinds)
            for line in chaos.describe():
                events.append(f"chaos: {line}")
        mon = coordlib.DurableHeartbeatMonitor(
            coord, H, timeout_s=timeout_s, clock=clock)
        for ph in partitioned:
            mon.partition(ph)
        lease = coord.adopt(coordinator, range(H))
        log.coordinator = coordinator
    else:
        coord = None
        events = []
        mon = flt.HeartbeatMonitor(H, timeout_s=timeout_s, clock=clock)

    # -- phase A: primary execution under the stateless assignment ----------
    dead_script = set(inject.dead_hosts)
    strag_script = set(inject.straggler_hosts)
    owner = {s: h for h in range(H)
             for s in flt.shard_for(step, h, H, S)}
    partials: dict[int, Any] = {}
    computed_by: dict[int, int] = {}
    progress = {h: 0 for h in range(H)}
    for h in range(H):
        for j, s in enumerate(flt.shard_for(step, h, H, S)):
            clock.advance(1.0)
            if h in dead_script and j >= inject.die_after_shards:
                break  # host crashes: stops computing AND heartbeating
            if h in strag_script:
                mon.beat(h, step=0)  # alive, but no progress this round
                continue
            if h in partitioned:
                # the host keeps computing, but nothing it does reaches
                # the cluster: beats, checkpoints, and partials are all
                # dropped at the transport — survivors must recover its
                # shards as if it were dead
                partial_fn(shard_slice(s, h))
                progress[h] = j + 1
                mon.beat(h, step=progress[h])  # dropped by the monitor
                continue
            p = partial_fn(shard_slice(s, h))
            if h not in dead_script or inject.checkpoint_survives:
                save_partial(s, p)
            if h not in dead_script:
                # a dying host's in-memory partial dies with it; only the
                # checkpoint (if any) outlives the crash
                partials[s] = p
            if coord is not None:
                # the worker itself writes the durable ledger record, so
                # the recovery log survives a coordinator death
                coord.record_shard(s, h, step)
            computed_by[s] = h
            log.computed.append((s, h))
            progress[h] = j + 1
            mon.beat(h, step=progress[h])

    # -- failure detection: healthy hosts keep heartbeating while the
    # coordinator waits out the timeout; crashed hosts stay silent.  A
    # host that finished its WHOLE assignment beats the round-complete
    # step S — under an uneven S/H split the floor-count hosts legitimately
    # complete fewer shards than the ceil-count ones, and must not read as
    # stragglers for it --------------------------------------------------
    # -- chaos: corrupt durable partials (and the memory that held them) --
    if chaos is not None and chaos.corrupt_shards:
        for s in chaos.corrupt_shards:
            partials.pop(s, None)  # holder's memory died with the event
            if ckpt_dir is None:
                continue
            if chaoslib.corrupt_shard_partial(ckpt_dir, s, step) is None:
                continue
            d = ckpt.shard_partial_dir(ckpt_dir, s)
            try:
                ckpt.verify_step(d, step)
            except ckpt.CheckpointCorruptError as e:
                ckpt.quarantine_step(d, step)
                log.corrupt.append(s)
                events.append(
                    f"checkpoint: shard {s} partial failed verification "
                    f"({e.reason}); quarantined to *.corrupt, "
                    f"deterministic recompute scheduled")

    clock.advance(mon.timeout_s + mon.grace_s + 1.0)
    for h in range(H):
        if h not in dead_script:
            owned = len(flt.shard_for(step, h, H, S))
            mon.beat(h, step=(S if progress[h] >= owned else progress[h]))
            if (lease is not None and h == lease.holder
                    and h not in partitioned):
                lease = coord.renew(lease)  # healthy coordinator holds on
    detected_dead = mon.dead_hosts()
    detected_strag = mon.stragglers(lag=straggler_lag)
    log.dead_hosts = list(detected_dead)
    log.straggler_hosts = list(detected_strag)
    alive = mon.alive_hosts()
    backup_pool = [a for a in alive if a not in set(detected_strag)] or alive

    # -- lease failover: if the coordinator's lease lapsed (holder dead or
    # partitioned), the lowest-ranked survivor adopts the lease AND the
    # durable ledger, and resumes phase B from the store's partials -------
    if coord is not None and alive:
        cur = coord.lease()
        now = clock()
        if cur is not None and (cur.holder not in alive
                                or cur.expires_at <= now):
            new_holder = coordlib.elect(alive)
            lease = coord.adopt(new_holder, alive)
            ledger = coord.load_ledger(step)
            log.failover = (cur.holder, new_holder, lease.epoch)
            events.append(
                f"failover: host {new_holder} adopted the recovery "
                f"ledger ({len(ledger)} durable shard records) at epoch "
                f"{lease.epoch}; resuming phase B from durable partials")

    def recover(s: int, failed_host: int, ledger: list) -> None:
        backup, _ = flt.backup_assignment(step, failed_host, H, S,
                                          alive=backup_pool)
        restored = try_restore(s)
        if restored is not None:
            partials[s] = restored
            computed_by[s] = backup  # the restoring rank holds it now
            log.restored.append(s)
            return
        p = partial_fn(shard_slice(s, backup))  # deterministic re-execution
        partials[s] = p
        computed_by[s] = backup
        save_partial(s, p)
        ledger.append((s, backup))

    for h in detected_dead:
        for s in flt.shard_for(step, h, H, S):
            if s not in partials:
                recover(s, h, log.recomputed)
    for h in detected_strag:
        for s in flt.shard_for(step, h, H, S):
            if s not in partials:
                recover(s, h, log.speculated)

    # -- elastic host-count change: remesh, recompute only what moved -------
    final_mesh = mesh
    if inject.resize_to is not None and inject.resize_to != H:
        new_H = inject.resize_to
        if new_H <= 0:
            raise ValueError(f"resize_to must be positive, got {new_H}")
        if mesh is not None:
            from repro.distributed import elastic

            devs = list(mesh.devices.reshape(-1))
            devs = (devs[:new_H] if new_H <= len(devs)
                    else list(jax.devices())[:new_H])
            final_mesh = elastic.best_mesh(devs, axis_names=(data_axis,))
            host_devs = list(final_mesh.devices.reshape(-1))
        new_owner = {s: h for h in range(new_H)
                     for s in flt.shard_for(step, h, new_H, S)}
        log.moved = sorted(s for s in range(S)
                           if new_owner[s] != owner[s])
        removed = set(range(new_H, H))
        for s in list(partials):
            if computed_by.get(s) in removed:
                del partials[s]  # left with the departing host's memory
        for s in range(S):
            if s in partials:
                continue
            restored = try_restore(s)
            if restored is not None:
                partials[s] = restored
                computed_by[s] = new_owner[s]
                log.restored.append(s)
            else:
                partials[s] = partial_fn(shard_slice(s, new_owner[s]))
                computed_by[s] = new_owner[s]
                save_partial(s, partials[s])
                log.recomputed.append((s, new_owner[s]))
        log.resized = (H, new_H)
        H = new_H
        owner = new_owner

    # -- completeness sweep: any shard still missing (undetected loss) is
    # re-executed by its owner — no shard is ever silently absent ----------
    for s in range(S):
        if s not in partials:
            partials[s] = partial_fn(shard_slice(s, owner[s]))
            computed_by[s] = owner[s]
            save_partial(s, partials[s])
            log.recomputed.append((s, owner[s]))

    # -- phase B: monoid re-merge (tables) or key-range replay (shuffle) ----
    if host_devs is not None:  # on the coordinator: lowest live rank
        live = [a for a in alive if a < H]
        phase_b_dev = host_device(coordlib.elect(live) if live else 0)
        partials = {s: jax.device_put(p, phase_b_dev)
                    for s, p in partials.items()}
    if flow in ("stream", "combine"):
        keys, values, counts = merge_partial_tables(
            app, spec,
            [partials[s]["tables"] for s in range(S)],
            [partials[s]["counts"] for s in range(S)])
    else:
        overflow = jnp.stack([partials[s]["overflow"] for s in range(S)])
        log.shuffle_overflow = tuple(
            int(x) for x in np.asarray(overflow).reshape(-1))
        _surface_overflow(plan, overflow, strict=strict_shuffle,
                          shuffle_capacity=shuffle_capacity)

        def _assemble(*encs):
            # the host-side transpose of the tiled all-to-all: destination
            # r receives every source's r-th encoded row, in source order —
            # swapaxes turns the stacked (source, dest, ...) sends into a
            # (dest, source, ...) batch the vmapped phase B consumes
            # whole.  Works on the ENCODED tree, so checkpointed partials
            # stay compressed all the way to the per-range decode.
            return jax.tree.map(
                lambda *leaves: jnp.swapaxes(jnp.stack(leaves), 0, 1),
                *encs)

        def _flatten(stacked):
            # (S, W) range batches, flattened in shard order — identical
            # to concatenating the S per-range outputs
            keys = stacked[0].reshape(-1)
            values = jax.tree.map(
                lambda v: v.reshape((-1,) + v.shape[2:]), stacked[1])
            counts = stacked[2].reshape(-1)
            if shuffle_plan is not None:
                keys, values, counts = _densify_ranges(
                    keys, values, counts, shuffle_plan)
            return keys, values, counts

        encs = [partials[s]["wire"] for s in range(S)]
        ranks = jnp.arange(S, dtype=jnp.int32)

        skew_hot = (shuffle_plan is not None and shuffle_plan.hot_keys
                    and flow == "sort")
        if not skew_hot:
            def _range_out(r, renc):
                recv_keys, recv_vals = wirelib.decode(wire_fmt, renc, r)
                lstream, lo = _localize_recv(
                    app, recv_keys, recv_vals, num_shards=S,
                    shard_index=r, shuffle_plan=shuffle_plan)
                if flow == "reduce":
                    return _reduce_range(app, lstream, lo)
                return _sort_range_fold(
                    app, spec, lstream, lo, use_kernels=use_kernels,
                    chunk_pairs=chunk_pairs, bucket_size=bucket_size,
                    on_fallback=cb)

            # one dispatch for the whole phase B: it is embarrassingly
            # parallel over destinations, so vmap batches the S per-range
            # calls and the assemble/flatten/densify glue fuses alongside
            def _phase_b(encs):
                renc = _assemble(*encs)
                stacked = jax.vmap(_range_out)(ranks, renc)
                return _flatten(stacked)

            keys, values, counts = _cached_jit("phase_b", _phase_b)(encs)
        else:
            # hot-split recombine, host-driven in two passes: (1) each
            # range folds its un-finalized tables AND its slice of the
            # split hot keys' pairs; (2) the hot partials merge across
            # ranges on the host (the mesh-less mirror of the collective
            # monoid merge); (3) each range patches the merged hot rows
            # into the owner's table and finalizes — bitwise the
            # all-to-all shard fn's answer by the monoid merge argument.
            def _range_tabs(r, renc):
                recv_keys, recv_vals = wirelib.decode(wire_fmt, renc, r)
                lstream, _ = _localize_recv(
                    app, recv_keys, recv_vals, num_shards=S,
                    shard_index=r, shuffle_plan=shuffle_plan)
                tables, counts = _sort_range_tables(
                    app, spec, lstream, use_kernels=use_kernels,
                    chunk_pairs=chunk_pairs, bucket_size=bucket_size,
                    on_fallback=cb)
                fk = recv_keys.reshape(-1)
                fv = jax.tree.map(
                    lambda v: v.reshape((-1,) + v.shape[2:]), recv_vals)
                ht, hc = _fold_hot_tables(app, spec, fk, fv,
                                          shuffle_plan)
                return tables, counts, ht, hc

            def _range_fin(r, tables, counts, mt, mc):
                W = shuffle_plan.width
                lo = jnp.asarray(shuffle_plan.boundaries, jnp.int32)[r]
                tables, counts = _patch_hot_rows(
                    spec, tables, counts, mt, mc, shuffle_plan, r, W)
                keys = jnp.arange(W, dtype=jnp.int32) + lo
                vals = jax.vmap(spec.finalize)(keys, tables, counts)
                return keys, vals, counts

            def _hot_merge(ht, hc):
                mt = _merge_tables_host(
                    spec, [jax.tree.map(lambda v, r=r: v[r], ht)
                           for r in range(S)],
                    [hc[r] for r in range(S)])
                mc = jnp.sum(hc, axis=0).astype(hc.dtype)
                return mt, mc

            def _phase_b_hot(encs):
                renc = _assemble(*encs)
                tables, counts, ht, hc = jax.vmap(_range_tabs)(ranks, renc)
                mt, mc = _hot_merge(ht, hc)
                stacked = jax.vmap(_range_fin, in_axes=(0, 0, 0, None, None))(
                    ranks, tables, counts, mt, mc)
                return _flatten(stacked)

            keys, values, counts = _cached_jit("phase_b_hot", _phase_b_hot)(
                encs)

    if shuffle_plan is not None and flow in ("reduce", "sort"):
        log.skew_plan = shuffle_plan.describe()
        log.boundary_epoch = int(shuffle_plan.epoch)
    log.final_mesh = final_mesh
    log.partitioned = sorted(partitioned)
    log.store_events = tuple(events)
    plan.recovery += tuple(log.summary())
    return keys, values, counts, log
