"""Roofline-driven autotuner for the streaming fold's tiling knobs.

The paper's optimizer picks the execution strategy from MapReduce semantics
alone; this module extends the same principle to the strategy's *sizing*:
``stream_chunk_pairs`` and the key-block size are derived from the analytic
flow-bytes / peak-residency / VMEM working-set models in
``roofline.analysis`` instead of fixed constants, so large-K workloads keep
the scatter-free one-hot fold and the chunk size balances the two HBM terms
the streaming flow pays for.

Model-driven selection (the default, ``source="model"``):

* ``chunk_pairs`` — the streaming flow's modeled bytes are
  ``2·N·pair + 2·(N/chunk)·table``: monotonically improved by larger
  chunks, while peak residency ``chunk·pair + table`` grows with them.
  The knee is ``chunk·pair_bytes ≈ table_bytes`` (peak stays within 2× of
  the table floor while the table re-touch term stops dominating), clamped
  to ``[DEFAULT_CHUNK_PAIRS, MAX_CHUNK_PAIRS]``.  The pure-JAX additive
  fold is additionally capped at ``ADDITIVE_FOLD_PAIRS_FUSED`` pairs per
  fold — the measured regime in which XLA keeps the one-hot contraction
  on-chip (beyond it the ``[chunk, K]`` expansion round-trips HBM); the
  Pallas kernel path is exempt, its one-hot tile is VMEM-resident at any
  chunk size, and so is a fold the TPU lowers to scatter-add above
  ``collector.TPU_SCATTER_MIN_KEYS`` keys (``scatter_fold_chosen``).
* ``key_block`` — sized per lowering from its memory model: the Pallas
  fold kernels keep a ``[Kb, Td]`` table block plus a ``[Tn, Kb]`` one-hot
  tile VMEM-resident (``stream_working_set_bytes`` vs ``VMEM_BUDGET`` with
  double-buffer headroom); the pure-JAX folds keep one ``[chunk, Kb]``
  expansion live per block (``DENSE_FOLD_ELEMS_BUDGET``) — measured on
  XLA:CPU, an unblocked large-K fold inside the chunk scan materializes
  the whole ``[chunk, K]`` expansion (268 MB peak at K=32k), while the
  blocked fold stays fused (0.6 MB peak, O(K + chunk) for real).

``probe=True`` additionally times 3 candidate chunk sizes on a synthetic
workload (measured micro-probe mode) and keeps the fastest.
"""

from __future__ import annotations

import dataclasses
import json
import os
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import collector as col
from repro.roofline import analysis as roofline

#: env var pointing at the persistent per-app tuning cache (JSON file).
#: Unset (the default, and in CI) -> measured micro-probe results are not
#: persisted and every probing run re-measures.
TUNE_CACHE_ENV = "JAX_PALLAS_TUNE_CACHE"

#: chunk-size clamp: floor keeps small workloads on the pre-autotuner
#: single-chunk behaviour; the cap bounds compile-time unrolling and the
#: pair-chunk buffer.
MAX_CHUNK_PAIRS = 1 << 16


def _pow2_round(x: int) -> int:
    lo = col.pow2_floor(x)
    return lo * 2 if x - lo > 2 * lo - x else lo


@dataclasses.dataclass(frozen=True)
class StreamTiling:
    """The autotuner's decision record, carried on the ExecutionPlan so
    ``explain()`` and the roofline reports show the chosen tiling."""

    chunk_pairs: int
    key_block: int  # == key_space -> single block (unblocked)
    key_space: int
    mode: str  # expected stream fold lowering (collector.stream_mode)
    source: str  # "model" | "probe" | "manual"
    model_bytes: float  # analytic HBM bytes at n_pairs_hint
    model_peak_bytes: float  # analytic peak residency
    working_set_bytes: float  # per-grid-step VMEM model (kernel path)
    n_pairs_hint: int
    notes: tuple[str, ...] = ()
    #: packed-sort passes per chunk of the sort flow's pure-JAX lowering
    sort_passes: int = 1

    @property
    def n_key_blocks(self) -> int:
        return -(-self.key_space // self.key_block)

    @property
    def blocked(self) -> bool:
        return self.key_block < self.key_space

    def describe(self) -> str:
        if self.mode == "sort":
            blk = (f"buckets={self.n_key_blocks}×{self.key_block}keys"
                   if self.blocked else "buckets=1 (single full sort)")
            if self.sort_passes > 1:
                blk += f" sort_passes={self.sort_passes}"
        else:
            blk = (f"key_block={self.key_block}×{self.n_key_blocks}"
                   if self.blocked else f"key_block={self.key_block} (single)")
        return (f"chunk_pairs={self.chunk_pairs} {blk} mode={self.mode} "
                f"[{self.source}] peak≈{self.model_peak_bytes / 1e6:.2f}MB "
                f"vmem_step≈{self.working_set_bytes / 1e6:.2f}MB")


# ---------------------------------------------------------------------------
# Persistent per-app tuning cache (file-backed, opt-in via env var)
# ---------------------------------------------------------------------------


#: key prefix of the skew planner's histogram decisions (core/skew.py),
#: which share this cache file with the StreamTiling entries — same
#: micro-probe posture, same opt-in persistence.
SKEW_KEY_PREFIX = "skew|"


def tune_cache_path() -> str | None:
    """Path of the persistent tuning cache, or None when disabled."""
    p = os.environ.get(TUNE_CACHE_ENV, "").strip()
    return p or None


def _tune_cache_key(app, spec, *, use_kernels: bool,
                    n_pairs_hint: int | None) -> str:
    aval = app.value_aval
    return "|".join([
        type(app).__name__,
        f"K={app.key_space}",
        f"cap={app.emit_capacity}",
        f"v={jnp.dtype(aval.dtype).name}{tuple(aval.shape)}",
        f"spec={spec.describe or spec.strategy}",
        f"N={n_pairs_hint or 0}",
        f"kern={int(use_kernels)}",
    ])


def load_tune_cache(path: str) -> dict:
    """Read the cache file; IO/parse failures read as an empty cache."""
    try:
        with open(path) as f:
            data = json.load(f)
        return data if isinstance(data, dict) else {}
    except (OSError, ValueError):
        return {}


def store_tune_entry(path: str, key: str, entry: dict) -> bool:
    """Merge one measured entry into the cache file (advisory: best-effort,
    failures are swallowed — the cache must never break a run)."""
    try:
        cache = load_tune_cache(path)
        cache[key] = entry
        tmp = f"{path}.tmp.{os.getpid()}"
        with open(tmp, "w") as f:
            json.dump(cache, f, indent=1, sort_keys=True)
        os.replace(tmp, path)
        return True
    except OSError:
        return False


def choose_chunk_pairs(key_space: int, *, holder_bytes: int, pair_bytes: int,
                       emit_capacity: int = 1,
                       n_pairs_hint: int | None = None,
                       fused_cap: bool = False) -> int:
    """Model-balanced chunk size (see module docstring).

    ``fused_cap=True`` applies the pure-JAX additive fold's
    fused-contraction regime cap (``ADDITIVE_FOLD_PAIRS_FUSED``)."""
    from repro.core.engine import DEFAULT_CHUNK_PAIRS

    table_bytes = key_space * (holder_bytes + 4)  # + int32 counts
    chunk = _pow2_round(max(table_bytes // max(pair_bytes, 1), 1))
    chunk = max(DEFAULT_CHUNK_PAIRS, min(chunk, MAX_CHUNK_PAIRS))
    if fused_cap:
        chunk = min(chunk, col.ADDITIVE_FOLD_PAIRS_FUSED)
    chunk = max(chunk, emit_capacity)
    if n_pairs_hint is not None and n_pairs_hint > 0:
        # no point chunking beyond the workload (keeps single-chunk fusion)
        chunk = min(max(chunk, 1), max(_pow2_round(n_pairs_hint),
                                       emit_capacity))
        chunk = max(chunk, emit_capacity)
    return chunk


def choose_key_block(key_space: int, chunk_pairs: int, *, d: int,
                     use_kernels: bool) -> int:
    """Key-block size per lowering memory model (see module docstring)."""
    if use_kernels:
        from repro.kernels import ops

        tile_n = min(ops.FOLD_TILE_N, -(-chunk_pairs // 128) * 128)
        return ops.auto_key_block(key_space, d=d, tile_n=tile_n)
    # pure-JAX folds: one [chunk, Kb] expansion live per block — inside a
    # multi-chunk scan XLA materializes anything bigger (measured: an
    # unblocked K=32k fold in the scan body costs 268 MB peak / O(N·K)
    # bytes; blocked at this budget, 0.6 MB / O(N + K))
    return col.choose_dense_key_block(key_space, chunk_pairs)


def autotune_stream(
    app,
    spec,
    *,
    use_kernels: bool = False,
    chunk_pairs: int | str = "auto",
    key_block: int | str | None = "auto",
    n_pairs_hint: int | None = None,
    probe: bool = False,
    probe_pairs: int = 2048,
    probe_items: Any | None = None,
) -> StreamTiling:
    """Pick the streaming-fold tiling for ``app`` under ``spec``.

    ``chunk_pairs`` / ``key_block`` accept explicit ints to pin either knob
    (``source="manual"`` when both are pinned); ``key_block=None`` disables
    blocking.  ``probe=True`` enables the measured micro-probe refinement
    (on ``probe_items`` when given, else a synthetic workload).
    """
    from repro.core import plan_cache as pc

    pc.STATS.autotunes += 1
    notes: list[str] = []
    value_bytes = int(jnp.dtype(app.value_aval.dtype).itemsize *
                      max(1, int(np.prod(app.value_aval.shape))))
    pair_bytes = 4 + value_bytes
    d, holder_bytes = spec.holder_width(app.value_aval)
    K = app.key_space
    # kernel-path exemptions mirror StreamCombiner's (same CombinerSpec
    # predicates): when the kernels won't actually run — e.g. integer
    # holders under use_kernels=True — the pure-JAX budgets apply.
    kernel_additive = use_kernels and spec.kernel_additive_ok(app.value_aval)
    kernel_monoid = use_kernels and spec.kernel_monoid_ok(app.value_aval)
    scatter_additive = col.scatter_fold_chosen(
        spec, K, kernel_additive=kernel_additive)

    manual_chunk = isinstance(chunk_pairs, int)
    if manual_chunk:
        chunk = int(chunk_pairs)
    else:
        chunk = choose_chunk_pairs(
            K, holder_bytes=holder_bytes, pair_bytes=pair_bytes,
            emit_capacity=app.emit_capacity, n_pairs_hint=n_pairs_hint,
            fused_cap=(spec.mxu_lowerable and not kernel_additive
                       and not scatter_additive))

    manual_block = key_block is None or isinstance(key_block, int)
    def pick_block(chunk_now: int) -> int:
        if key_block is None:
            return K
        if isinstance(key_block, int):
            return max(1, min(int(key_block), K))
        if scatter_additive:
            return K  # the scatter folds the whole table at once
        if kernel_monoid and not spec.mxu_lowerable:
            # chunk_monoid_fold auto-sizes its own key block (its VMEM
            # model carries the extra [Tn, Kb, D] masked-expansion term);
            # pinning the additive model's block here could overflow it
            return K
        return choose_key_block(K, chunk_now, d=d + 1,
                                use_kernels=kernel_additive)

    blk = pick_block(chunk)
    measured = False
    cached = False
    if probe and not manual_chunk:
        # persistent micro-probe cache (opt-in via JAX_PALLAS_TUNE_CACHE):
        # a prior run's measured chunk for the same app/shape/lowering is
        # reused instead of re-timing the candidates.
        cache_path = tune_cache_path()
        ckey = (None if cache_path is None else
                _tune_cache_key(app, spec, use_kernels=use_kernels,
                                n_pairs_hint=n_pairs_hint))
        if cache_path is not None:
            hit = load_tune_cache(cache_path).get(ckey)
            if isinstance(hit, dict) and "chunk_pairs" in hit:
                chunk = int(hit["chunk_pairs"])
                cached = True
                notes.append(f"probe cache hit: chunk={chunk} "
                             f"({hit.get('t_us', 0):.0f}us/fold measured "
                             f"by a previous run)")
        if not cached:
            chunk, measured = _probe_chunk(
                app, spec, chunk, use_kernels=use_kernels,
                key_block=None if blk >= K else blk,
                probe_pairs=probe_pairs, notes=notes, items=probe_items)
            if measured and cache_path is not None:
                t_us = _last_probe_us(notes)
                if store_tune_entry(cache_path, ckey,
                                    {"chunk_pairs": int(chunk),
                                     "t_us": t_us}):
                    notes.append(f"probe cache: stored chunk={chunk} "
                                 f"under {cache_path}")
        blk = pick_block(chunk)  # block budgets depend on the chunk

    additive_ok = (kernel_additive
                   or chunk <= col.ADDITIVE_FOLD_PAIRS_FUSED)
    dense_ok = (kernel_monoid
                or chunk * blk <= col.DENSE_FOLD_ELEMS_BUDGET)
    mode = col.stream_mode(spec, dense_ok=dense_ok, additive_ok=additive_ok,
                           scatter_additive=scatter_additive)
    lowering = f"fold lowering: {mode} on {col.fold_platform()}"
    if scatter_additive:
        lowering += (f", key_space={K} > {col.TPU_SCATTER_MIN_KEYS} "
                     f"(one-hot {col.TPU_ONEHOT_S_PER_PAIR_KEY:g} s per "
                     f"pair·key, scatter-add {col.TPU_SCATTER_S_PER_PAIR:g} "
                     f"s per pair)")
    notes.append(lowering)
    if spec.mxu_lowerable and mode == "scatter" and not scatter_additive:
        notes.append(
            f"FALLBACK: chunk_pairs={chunk} is outside the fused one-hot "
            f"contraction regime (N <= {col.ADDITIVE_FOLD_PAIRS_FUSED} "
            f"pure-JAX) at key_space={K}; exact scatter fold selected — "
            f"serialized on XLA:CPU, O(N·K) bytes through the roofline "
            f"model. Shrink stream_chunk_pairs (or use_kernels=True) to "
            f"restore the one-hot path.")
    if blk < K:
        notes.append(f"key-blocked fold: {-(-K // blk)} blocks of {blk} "
                     f"keys (working set bounded per block)")

    hint = n_pairs_hint if n_pairs_hint else max(chunk * 4, 1 << 16)
    kb_arg = None if blk >= K else blk
    model_bytes = roofline.mapreduce_flow_bytes(
        "stream", n_pairs=hint, key_space=K, value_bytes=value_bytes,
        holder_bytes=holder_bytes, chunk_pairs=chunk, key_block=kb_arg)
    model_peak = roofline.mapreduce_flow_peak_bytes(
        "stream", n_pairs=hint, key_space=K, value_bytes=value_bytes,
        holder_bytes=holder_bytes, chunk_pairs=chunk, key_block=kb_arg)
    working_set = roofline.stream_working_set_bytes(
        chunk_pairs=chunk, key_block=blk, d=d + 1)

    source = ("manual" if manual_chunk and manual_block
              else "cache" if cached
              else "probe" if measured else "model")
    return StreamTiling(
        chunk_pairs=chunk, key_block=blk, key_space=K, mode=mode,
        source=source, model_bytes=model_bytes, model_peak_bytes=model_peak,
        working_set_bytes=working_set, n_pairs_hint=hint,
        notes=tuple(notes))


def _last_probe_us(notes: list) -> float:
    """Best-candidate time recorded by the last probe note (for the cache)."""
    for n in reversed(notes):
        if n.startswith("probe: measured") and "us/fold" in n:
            try:
                return float(n.rsplit("(", 1)[1].split("us/fold")[0])
            except (IndexError, ValueError):  # pragma: no cover
                return 0.0
    return 0.0


def autotune_sort(
    app,
    spec,
    *,
    use_kernels: bool = False,
    chunk_pairs: int | str = "auto",
    n_pairs_hint: int | None = None,
) -> StreamTiling:
    """Pick the sort-flow tiling: chunk size + radix bucket width.

    The sort flow touches the O(K) tables once per chunk and its per-pair
    cost grows only as log(chunk), so the chunk is sized as large as the
    clamp allows (bounded by the workload hint — no point chunking beyond
    the stream).  ``key_block`` records the radix bucket width the Pallas
    pipeline partitions with (``kernels/ops.auto_bucket_size``); the
    pure-JAX lowering sorts each chunk instead — ``sort_passes`` packed
    digit sorts once the 31-bit packed regime runs out (noted).
    """
    from repro.core import plan_cache as pc

    pc.STATS.autotunes += 1
    notes: list[str] = []
    value_bytes = int(jnp.dtype(app.value_aval.dtype).itemsize *
                      max(1, int(np.prod(app.value_aval.shape))))
    pair_bytes = 4 + value_bytes
    d, holder_bytes = spec.holder_width(app.value_aval)
    K = app.key_space

    manual_chunk = isinstance(chunk_pairs, int)
    if manual_chunk:
        chunk = int(chunk_pairs)
    else:
        from repro.core.engine import DEFAULT_SORT_CHUNK_PAIRS

        chunk = DEFAULT_SORT_CHUNK_PAIRS
        if n_pairs_hint is not None and n_pairs_hint > 0:
            chunk = min(chunk, _pow2_round(n_pairs_hint))
        chunk = max(min(chunk, MAX_CHUNK_PAIRS), app.emit_capacity, 1)

    from repro.kernels import ops

    bucket = ops.auto_bucket_size(K, d=d + 1)
    sort_passes = col.sort_radix_passes(min(chunk, MAX_CHUNK_PAIRS), K)
    if not use_kernels:
        if sort_passes > 1:
            notes.append(
                f"pure-JAX lowering: (key, index) no longer fits one "
                f"31-bit packed word at chunk={chunk} — multi-pass packed "
                f"radix sort, {sort_passes} digit sorts per chunk "
                f"(lax.scan over levels)")
        else:
            notes.append("pure-JAX lowering: one packed stable sort per "
                         "chunk (the radix buckets below are the kernel "
                         "pipeline's partition granularity)")

    hint = n_pairs_hint if n_pairs_hint else max(chunk * 4, 1 << 16)
    # bytes model per lowering: the kernel partition is one pass; the
    # pure-JAX multi-pass sort pays its per-pass traffic
    levels = 1 if use_kernels else sort_passes
    model_bytes = roofline.mapreduce_flow_bytes(
        "sort", n_pairs=hint, key_space=K, value_bytes=value_bytes,
        holder_bytes=holder_bytes, chunk_pairs=chunk, sort_levels=levels)
    model_peak = roofline.mapreduce_flow_peak_bytes(
        "sort", n_pairs=hint, key_space=K, value_bytes=value_bytes,
        holder_bytes=holder_bytes, chunk_pairs=chunk)
    working_set = (min(chunk, hint) * pair_bytes * 2.0 + bucket * (d + 1) * 4.0
                   if use_kernels else 0.0)
    return StreamTiling(
        chunk_pairs=chunk, key_block=bucket, key_space=K, mode="sort",
        source="manual" if manual_chunk else "model",
        model_bytes=model_bytes, model_peak_bytes=model_peak,
        working_set_bytes=working_set, n_pairs_hint=hint,
        notes=tuple(notes), sort_passes=sort_passes)


def _probe_chunk(app, spec, chunk: int, *, use_kernels: bool,
                 key_block: int | None, probe_pairs: int,
                 notes: list, items: Any | None = None) -> tuple[int, bool]:
    """Measured micro-probe: time the streaming fold at chunk/2, chunk and
    2·chunk on a real or synthetic workload and keep the fastest.  Costs a
    few jit compilations — opt-in, and advisory (failures keep the model's
    choice).  Returns ``(chunk, measured)``; ``measured`` is False when no
    candidate could be timed (e.g. the synthetic items don't match the
    app's item structure — pass ``probe_items`` in that case)."""
    import time

    from repro.core import engine as eng
    from repro.core import plan_cache as pc

    pc.STATS.probes += 1
    cap = max(app.emit_capacity, 1)
    if items is None:
        n_items = max(probe_pairs // cap, 4)
        rng = np.random.default_rng(0)
        shape = (n_items,) + tuple(app.value_aval.shape)
        if jnp.issubdtype(app.value_aval.dtype, jnp.integer):
            items = jnp.asarray(rng.integers(0, max(app.key_space, 2),
                                             size=shape).astype(np.int32))
        else:
            items = jnp.asarray(rng.standard_normal(shape).astype(np.float32))

    candidates = sorted({max(chunk // 2, cap), chunk,
                         min(chunk * 2, MAX_CHUNK_PAIRS)})
    best, best_t = chunk, float("inf")
    for c in candidates:
        try:
            fn = jax.jit(lambda x, c=c: eng.stream_local_tables(
                app, spec, x, chunk_pairs=c, use_kernels=use_kernels,
                key_block=key_block))
            out = fn(items)
            jax.block_until_ready(out)
            t0 = time.perf_counter()
            for _ in range(3):
                jax.block_until_ready(fn(items))
            t = (time.perf_counter() - t0) / 3
        except Exception as e:  # probe is advisory, never fatal
            notes.append(f"probe: chunk={c} failed ({type(e).__name__})")
            continue
        if t < best_t:
            best, best_t = c, t
    if best_t == float("inf"):
        notes.append("probe: no candidate measurable; keeping the model's "
                     "choice (pass probe_items shaped like the app's items)")
        return chunk, False
    notes.append(f"probe: measured {candidates} -> chunk={best} "
                 f"({best_t * 1e6:.0f}us/fold)")
    return best, True
