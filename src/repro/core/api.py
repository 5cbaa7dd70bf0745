"""Public MR4X API — mirrors the paper's Fig 2 user code shape.

The user supplies only a :class:`Mapper` and :class:`Reducer` (or subclasses
:class:`MapReduceApp`) and calls :meth:`MapReduce.run`.  Everything else —
combiner derivation, flow selection, lowering, distribution — is the
framework's job, "transparently to the user" (paper abstract).

Word count, for comparison with the paper's Fig 2::

    class WordCount(MapReduceApp):
        key_space = VOCAB
        value_aval = jax.ShapeDtypeStruct((), jnp.int32)

        def map(self, item, emit):          # item: [window] token ids
            emit(item, jnp.ones_like(item)) # one (word, 1) pair per token

        def reduce(self, key, values, count):
            return jnp.sum(values)

    result = MapReduce(WordCount()).run(token_windows)

Staged compilation (the JaCe/JAX-AOT stage architecture)::

    mr = MapReduce(WordCount())           # plan stage (cached by content)
    lowered = mr.lower(items)             # bind an item spec
    optimized = lowered.optimize()        # bind execution options
    compiled = optimized.compile()        # AOT compile (cached by content)
    result = compiled(items)              # dispatch only — zero re-traces

``run()``/``run_distributed()``/``run_resilient()`` are thin wrappers over
this path; every stage answers :meth:`explain`.  Execution-time knobs
travel in one :class:`ExecutionOptions` record accepted by all three run
methods — the pre-``ExecutionOptions`` scattered kwargs (deprecated with a
forwarding shim for one release) are now a ``TypeError``.

Long-lived serving: :meth:`MapReduce.serve` stages the same plan into a
:class:`repro.streaming.MapReduceService` — micro-batches fold
incrementally into persistent holder tables (mode="streaming"), with
windowed aggregation, live snapshots and checkpointed warm restarts.

Every entry point — ``run*``, ``Compiled.__call__`` and
``service.snapshot()`` — returns the same :class:`MapReduceResult`.
"""

from __future__ import annotations

import contextlib
import dataclasses
import warnings as _warnings
from functools import partial
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import autotune as at
from repro.core import collector as col
from repro.core import engine as eng
from repro.core import combiner as C
from repro.core import plan_cache as pc
from repro.core import skew as sk
from repro.core import trace
from repro.core.skew import ShuffleOptions
from repro.core.optimizer import Derivation, derive_combiner
from repro.core.plan import ExecutionPlan, plan_execution


class MapReduceApp:
    """Subclass and provide map/reduce; set the class attributes.

    Attributes
    ----------
    key_space: dense key-id capacity K (keys are int32 in [0, K)).
    value_aval: ShapeDtypeStruct of one emitted value.
    pad_value: padding used for the reduce-flow value windows.
    max_values_per_key: static Lmax bound for the reduce flow.
    emit_capacity: max pairs one ``map(item, ...)`` call may emit.
    """

    key_space: int = 0
    value_aval: jax.ShapeDtypeStruct = jax.ShapeDtypeStruct((), jnp.float32)
    pad_value: Any = 0
    max_values_per_key: int = 64
    emit_capacity: int = 16

    # -- user hooks ---------------------------------------------------------
    def map(self, item, emit) -> None:
        raise NotImplementedError

    def reduce(self, key, values, count):
        raise NotImplementedError

    # optional: supply a hand-written combiner (Phoenix-style) to bypass the
    # optimizer — used in benchmarks to compare manual vs derived combiners.
    manual_combiner: C.CombinerSpec | None = None


# Functional-style construction (paper Fig 2 uses anonymous classes).
def make_app(map_fn: Callable, reduce_fn: Callable, **attrs) -> MapReduceApp:
    app = MapReduceApp()
    app.map = map_fn  # type: ignore[method-assign]
    app.reduce = reduce_fn  # type: ignore[method-assign]
    for k, v in attrs.items():
        setattr(app, k, v)
    return app


#: re-exported: the emitter type handed to user map functions.
Emitter = eng.Emitter


# ---------------------------------------------------------------------------
# ExecutionOptions: the one execution-time kwarg surface
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class ExecutionOptions:
    """Execution-time knobs for ``run``/``run_distributed``/``run_resilient``.

    One record replaces the three methods' formerly scattered kwargs;
    fields irrelevant to a given method are simply ignored by it.  The
    ``None`` defaults on the lowering overrides mean "inherit the
    MapReduce constructor's choice".

    Distribution: ``mesh`` + ``data_axis`` select the shard_map data axis;
    ``scatter_output`` key-shards stream/combine results; ``shuffle``
    (a :class:`repro.core.skew.ShuffleOptions`) is the unified all-to-all
    surface — capacity/strict envelope plus the skew-adaptive planner
    (sampled histograms, balanced range boundaries, hot-key splitting).
    The flat ``shuffle_capacity``/``strict_shuffle`` fields are its
    deprecated spelling: non-default values forward into a
    ``ShuffleOptions`` with a ``DeprecationWarning`` (one release), and
    whenever ``shuffle`` is set it is authoritative — the flat fields are
    overwritten to mirror it.  Resilience (``run_resilient``): ``num_hosts`` /
    ``num_shards`` / ``ckpt_dir`` / ``step`` / ``inject`` / ``timeout_s``
    / ``straggler_lag``, plus the durable control plane ``coord`` /
    ``retry`` / ``chaos``.  Serving: ``items_bucket="pow2"`` pads the batch
    axis to the next power of two so nearby batch sizes share one compiled
    executable (pad rows are masked out; local runs only);
    ``cache=False`` bypasses the content-keyed plan/executable cache.
    """

    # distribution
    mesh: Any = None
    data_axis: str = "data"
    scatter_output: bool = False
    shuffle_capacity: int | None = None
    strict_shuffle: bool = False
    #: the unified shuffle surface (skew.ShuffleOptions); None + default
    #: flat fields keeps the bitwise-legacy fixed-width shuffle.
    shuffle: sk.ShuffleOptions | None = None
    # resilience
    num_hosts: int | None = None
    num_shards: int | None = None
    ckpt_dir: str | None = None
    step: int = 0
    inject: Any = None
    timeout_s: float = 60.0
    straggler_lag: int = 1
    #: durable control plane (coordination.CoordinationStore | KVStore |
    #: path); defaults to <ckpt_dir>/coord when chaos/retry ask for one.
    coord: Any = None
    #: coordination.RetryPolicy bounding store/restore ops (deterministic
    #: capped backoff; every retry lands on plan.recovery).
    retry: Any = None
    #: chaos.ChaosPlan multi-fault drill script.
    chaos: Any = None
    # lowering overrides (None -> the MapReduce constructor's choice)
    combine_impl: str | None = None
    use_kernels: bool | None = None
    chunk_pairs: int | None = None
    key_block: int | None = None
    bucket_size: int | None = None
    # serving
    items_bucket: str = "exact"
    cache: bool = True

    def __post_init__(self):
        sh = self.shuffle
        if sh is None:
            if self.shuffle_capacity is not None or self.strict_shuffle:
                _warnings.warn(
                    "ExecutionOptions(shuffle_capacity=..., "
                    "strict_shuffle=...) are deprecated; pass "
                    "shuffle=ShuffleOptions(capacity=..., strict=...) "
                    "instead", DeprecationWarning, stacklevel=3)
                object.__setattr__(self, "shuffle", sk.ShuffleOptions(
                    capacity=self.shuffle_capacity,
                    strict=self.strict_shuffle))
            return
        if not isinstance(sh, sk.ShuffleOptions):
            raise TypeError(
                f"ExecutionOptions.shuffle must be a skew.ShuffleOptions, "
                f"got {type(sh).__name__}")
        # the record is authoritative: mirror onto the flat fields so both
        # read surfaces agree and dataclasses.replace round-trips silently
        object.__setattr__(self, "shuffle_capacity", sh.capacity)
        object.__setattr__(self, "strict_shuffle", sh.strict)


_OPTION_FIELDS = {f.name for f in dataclasses.fields(ExecutionOptions)}


def _resolve_options(options: ExecutionOptions | None, legacy: dict,
                     *, method: str, mesh=None) -> ExecutionOptions:
    """Reject the retired scattered kwargs; resolve the options record.

    ``mesh`` stays a first-class argument on the distributed entry points.
    The pre-``ExecutionOptions`` scattered kwargs went through one release
    of ``DeprecationWarning``-and-forward; the forwarding is now removed
    and both known-but-retired and unknown kwargs raise ``TypeError`` —
    the former with a pointer at the replacement field."""
    opts = options if options is not None else ExecutionOptions()
    if legacy:
        retired = sorted(set(legacy) & _OPTION_FIELDS)
        if retired:
            raise TypeError(
                f"{method}({', '.join(retired)}=...) scattered keyword "
                f"arguments were removed; pass "
                f"options=ExecutionOptions({retired[0]}=...) instead")
        raise TypeError(f"{method}() got unexpected keyword arguments "
                        f"{sorted(legacy)}")
    if mesh is not None:
        opts = dataclasses.replace(opts, mesh=mesh)
    return opts


@dataclasses.dataclass
class MapReduceResult:
    """The one result record of every execution surface.

    ``run()``, ``run_distributed()``, ``run_resilient()``,
    ``Compiled.__call__`` and ``MapReduceService.snapshot()`` all return
    this; the entry points differ only in which optional fields are
    populated (``recovery`` from resilient runs, ``batch_id`` from
    service snapshots)."""

    keys: jax.Array  # [K] = arange(K)
    values: Any  # [K, ...]
    counts: jax.Array  # [K]; 0 == key never emitted
    plan: "ExecutionPlan | None" = None
    #: fault.RecoveryLog when the result came from run_resilient.
    recovery: Any = None
    #: id of the last micro-batch folded in, when the result is a
    #: MapReduceService snapshot (None for batch runs).
    batch_id: int | None = None

    @property
    def diagnostics(self) -> tuple[str, ...]:
        """The plan's optimizer/lowering diagnostics (empty without a
        plan) — one accessor across all entry points."""
        return self.plan.diagnostics if self.plan is not None else ()

    def __iter__(self):
        """Bare-tuple unpacking shim: ``keys, values, counts = result``
        still works but is deprecated — use the named fields."""
        _warnings.warn(
            "unpacking MapReduceResult as a bare (keys, values, counts) "
            "tuple is deprecated; use the named fields "
            "(.keys/.values/.counts)", DeprecationWarning, stacklevel=2)
        return iter((self.keys, self.values, self.counts))

    def to_dict(self) -> dict:
        """Host-side {key: value} for present keys (tests / small results)."""
        import numpy as np

        counts = np.asarray(self.counts)
        vals = np.asarray(self.values)
        return {int(k): vals[k] for k in np.nonzero(counts > 0)[0]}


def wide_job(app) -> bool:
    """Whether ``app`` is a *wide* job: one of its declared value columns
    is a 64-bit integer (``value_aval``).

    JAX holds arrays at 32 bits unless ``jax_enable_x64`` is on, and a TPU
    emulates 64-bit integers, so no global flag is asked of the user: the
    entry points plan, compile and run a wide job in a 64-bit scope
    (:meth:`MapReduce.width`).  Every array the engine makes for itself keeps
    an explicit 32-bit dtype there (key ids, counts, masks, iotas); only
    the declared columns, the premap's products and their holders are
    64-bit.  A wide job takes 32-bit inputs and widens them in its map."""
    return any(col.is_wide(leaf.dtype)
               for leaf in jax.tree.leaves(app.value_aval))


class MapReduce:
    """``MapReduce(app).run(items)`` — the framework entry point.

    flow:
      * "auto"    derive a combiner; when possible, run the optimizer's
                  recommended flow, else reduce (the paper's optimizer
                  behaviour).  With ``n_pairs_hint`` the recommendation
                  comes from the roofline+compute cost model
                  (``core/cost_model.py``), which ranks the stream and
                  sort flows for that workload size; without a hint the
                  streaming fused flow is kept (one-flag behaviour).
      * "stream"  force the streaming map+combine fusion (error if not
                  derivable): map chunks fold straight into holder tables,
                  the full pair buffer is never materialized
      * "sort"    force the sort-based flow (error if not derivable):
                  chunks are radix-partitioned / stably sorted by key and
                  ONE aggregate per distinct key merges into the holder
                  tables — O(N·log N + K) compute vs the one-hot fold's
                  O(N·K), the winner at large sparse key spaces.  Past the
                  31-bit packed regime the pure-JAX sort runs a multi-pass
                  packed digit radix — ``explain()`` shows the buckets and
                  sort passes
      * "combine" force the legacy combine flow (materialize pairs, fold
                  once); kept for A/B benchmarks
      * "reduce"  force the baseline flow (paper's un-optimized MR4J)

    n_pairs_hint — expected emitted pairs per run; enables cost-model flow
    selection under ``flow="auto"`` and sharpens the autotuned tiling.

    stream_chunk_pairs bounds the emitted pairs materialized per streaming
    chunk (peak intermediate state ≈ key_space + stream_chunk_pairs).  The
    default ``"auto"`` lets the roofline-driven autotuner size it (and the
    key-block partition of the holder tables) from the analytic flow-bytes
    and VMEM working-set models; pass an int to pin it.  stream_key_block
    partitions the ``[K, D]`` holder tables for large key spaces
    ("auto" / int / None to disable blocking).  autotune_probe=True adds
    the measured micro-probe refinement on top of the model (persisted
    across runs when ``JAX_PALLAS_TUNE_CACHE`` points at a cache file).
    The decision is recorded on the plan — see :meth:`explain`.

    Construction is the **plan stage** of the staged pipeline and is
    content-cached (``core/plan_cache.py``): a second MapReduce over an
    app with identical reduce jaxpr, shapes and knobs reuses the first's
    derivation, flow choice and tiling without re-running the optimizer
    (``cache=False`` opts out).  ``lower()`` → ``optimize()`` →
    ``compile()`` continue the stages; ``run*`` wrap them.

    ``streaming=True`` plans for continuous ingestion: the flow is pinned
    to "stream" and a combiner must be derivable (an unbounded stream
    cannot be buffered for the reduce flow); :meth:`serve` then stages
    the plan into a long-lived ``MapReduceService``.
    """

    def __init__(
        self,
        app: MapReduceApp,
        *,
        flow: str = "auto",
        trust_semantics: bool = False,
        combine_impl: str = "auto",
        use_kernels: bool = False,
        n_pairs_hint: int | None = None,
        stream_chunk_pairs: int | str = "auto",
        stream_key_block: int | str | None = "auto",
        autotune_probe: bool = False,
        donate: bool = False,
        cache: bool = True,
        streaming: bool = False,
    ):
        if app.key_space <= 0:
            raise ValueError("app.key_space must be positive")
        self.app = app
        self.flow = flow
        self.combine_impl = combine_impl
        self.use_kernels = use_kernels
        self.cache = cache
        self.streaming = streaming
        self.wide = wide_job(app)
        if self.wide:
            pc.STATS.wide_jobs += 1
        with self.width():
            self._plan_key = pc.plan_key(
                app, flow=flow, trust_semantics=trust_semantics,
                n_pairs_hint=n_pairs_hint, use_kernels=use_kernels,
                combine_impl=combine_impl, chunk_pairs=stream_chunk_pairs,
                key_block=stream_key_block, autotune_probe=autotune_probe,
                streaming=streaming, wide=self.wide)

            entry = pc.plan_get(self._plan_key) if cache else None
            if entry is not None:
                # full in-memory hit: reuse the derivation (live combiner
                # closures), flow choice and tiling — zero optimizer traces,
                # zero autotune calls.  Fresh plan INSTANCE per MapReduce so
                # run-time diagnostics never pollute the cached template.
                self.plan = dataclasses.replace(
                    entry.plan, recovery=(), stage="planned",
                    cache_key=self._plan_key, cache_event="hit")
                self.tiling = entry.tiling
                self.stream_chunk_pairs = entry.stream_chunk_pairs
                self._key_block = entry.key_block
                self._bucket_size = entry.bucket_size
                self._record_holder_bytes()
                return

            cache_event = "miss" if cache else ""
            fentry = pc.file_get(self._plan_key) if cache else None
            if (fentry is not None and not isinstance(stream_chunk_pairs, int)
                    and fentry["flow"] in ("stream", "sort")):
                # cross-process advisory hit: pin the persisted tiling decision
                # so the (potentially measured) autotune probes are skipped;
                # derivation and compilation still run — closures and
                # executables don't serialize.
                stream_chunk_pairs = int(fentry["chunk_pairs"])
                if fentry.get("key_block") is not None \
                        and not isinstance(stream_key_block, int):
                    stream_key_block = int(fentry["key_block"])
                cache_event = "file-hit"

            self.plan = plan_execution(app, flow=flow,
                                       trust_semantics=trust_semantics,
                                       n_pairs_hint=n_pairs_hint,
                                       streaming=streaming)
            self.tiling = None
            key_block = None
            bucket_size = None
            if self.plan.flow == "stream":
                self.tiling = at.autotune_stream(
                    app, self.plan.spec, use_kernels=use_kernels,
                    chunk_pairs=stream_chunk_pairs, key_block=stream_key_block,
                    n_pairs_hint=n_pairs_hint, probe=autotune_probe)
                self.plan.tiling = self.tiling
                stream_chunk_pairs = self.tiling.chunk_pairs
                key_block = (self.tiling.key_block if self.tiling.blocked
                             else None)
                spec = self.plan.spec
                chosen = col.scatter_fold_chosen(
                    spec, app.key_space, kernel_additive=(
                        use_kernels and spec.kernel_additive_ok(app.value_aval)))
                if (self.tiling.mode == "scatter" and spec.mxu_lowerable
                        and not chosen):
                    self.plan.diagnostics += (
                        "stream fold degraded to exact scatter (dense budgets "
                        "exceeded) — see tiling notes",)
            elif self.plan.flow == "sort":
                self.tiling = at.autotune_sort(
                    app, self.plan.spec, use_kernels=use_kernels,
                    chunk_pairs=stream_chunk_pairs, n_pairs_hint=n_pairs_hint)
                self.plan.tiling = self.tiling
                stream_chunk_pairs = self.tiling.chunk_pairs
                bucket_size = (self.tiling.key_block if self.tiling.blocked
                               else None)
            elif not isinstance(stream_chunk_pairs, int):
                stream_chunk_pairs = eng.DEFAULT_CHUNK_PAIRS
            if (self.plan.flow == "combine" and self.plan.spec is not None
                    and self.plan.spec.mxu_lowerable
                    and app.key_space > col.ONEHOT_MAX_KEYS):
                # below the legacy key-space cutoff the one-hot path holds at
                # any pair count — nothing to flag there
                if use_kernels:
                    self.plan.diagnostics += (
                        f"combine flow: key_space={app.key_space} > "
                        f"{col.ONEHOT_MAX_KEYS} exceeds the onehot_combine "
                        f"kernel's VMEM-resident table cutoff; the collector "
                        f"uses the exact scatter fallback "
                        f"(LoweringFallbackWarning at trace time) — the "
                        f"streaming flow's key-blocked fold kernel has no such "
                        f"limit",)
                else:
                    self.plan.diagnostics += (
                        f"combine flow: at key_space={app.key_space} > "
                        f"{col.ONEHOT_MAX_KEYS} the one-hot lowering holds up "
                        f"to {col.ADDITIVE_FOLD_PAIRS_FUSED} pairs (the fused-"
                        f"contraction regime); beyond that the collector "
                        f"degrades to the exact scatter fallback "
                        f"(LoweringFallbackWarning at trace time) — the "
                        f"chunked stream flow has no such limit",)
            self.stream_chunk_pairs = stream_chunk_pairs
            self._key_block = key_block
            self._bucket_size = bucket_size
            self.plan.stage = "planned"
            self.plan.cache_key = self._plan_key
            self.plan.cache_event = cache_event
            if cache:
                # snapshot NOW: the template must not see diagnostics a later
                # run of this instance appends
                pc.plan_put(self._plan_key, pc.PlanEntry(
                    plan=dataclasses.replace(self.plan),
                    tiling=self.tiling,
                    stream_chunk_pairs=stream_chunk_pairs,
                    key_block=key_block, bucket_size=bucket_size))
                pc.file_put(self._plan_key,
                            pc.file_entry_from(self.plan, self.tiling))
            self._record_holder_bytes()

    def _record_holder_bytes(self) -> None:
        """``plan_cache.STATS.holder_bytes[plan key]``: the bytes of holder
        state this plan folds per emitted pair, every holder column's
        itemsize x width plus the int32 count (nothing for the reduce
        flow, which folds no holders)."""
        if self.plan.spec is not None:
            _, nbytes = self.plan.spec.holder_width(self.app.value_aval)
            pc.STATS.holder_bytes[self._plan_key] = nbytes + 4

    def width(self):
        """The scope this job is traced and run in: 64-bit for a wide job
        (:func:`wide_job`), unchanged for any other."""
        return (jax.enable_x64(True) if self.wide
                else contextlib.nullcontext())

    # -- lowering knob resolution ------------------------------------------

    def _knobs(self, opts: ExecutionOptions) -> dict:
        """Engine kwargs for this plan under ``opts`` overrides."""
        return dict(
            combine_impl=(self.combine_impl if opts.combine_impl is None
                          else opts.combine_impl),
            use_kernels=(self.use_kernels if opts.use_kernels is None
                         else opts.use_kernels),
            chunk_pairs=(self.stream_chunk_pairs if opts.chunk_pairs is None
                         else opts.chunk_pairs),
            key_block=(self._key_block if opts.key_block is None
                       else opts.key_block),
            bucket_size=(self._bucket_size if opts.bucket_size is None
                         else opts.bucket_size),
        )

    # -- staged execution surface ------------------------------------------

    def lower(self, items, *, options: ExecutionOptions | None = None,
              mode: str | None = None) -> "Lowered":
        """Stage 1: bind this plan to an item spec (concrete arrays or a
        ShapeDtypeStruct pytree).  ``mode`` defaults to "local", or
        "distributed" when ``options.mesh`` is set.

        With ``options.shuffle.skew="auto"`` and concrete items, this is
        where the skew planner samples the emitted key histogram and bakes
        balanced boundaries / hot-key splits into the frozen
        ``ShuffleOptions`` (spec-only lowering skips the probe and keeps
        the fixed-width ranges)."""
        opts = options if options is not None else ExecutionOptions()
        rmode = _infer_mode(opts, mode)
        if rmode in ("distributed", "resilient"):
            with self.width():  # the skew planner runs the map
                opts = self._resolve_shuffle(opts, items, rmode)
        return Lowered(self, pc.items_spec_of(items), opts, mode=rmode)

    def _resolve_shuffle(self, opts: ExecutionOptions, items,
                         mode: str) -> ExecutionOptions:
        """Lower()-time skew resolution: sample/recall the key histogram
        and return options with the decision baked into ``opts.shuffle``;
        provenance lands on ``plan.skew`` (shown by ``explain()``).  A
        non-raw wire codec additionally lands its modeled
        encoded-vs-raw bytes on ``plan.wire``."""
        sh = opts.shuffle
        if sh is not None and sh.wire != "raw":
            self.plan.wire = self._wire_provenance(opts, items, mode)
        if sh is None or (sh.skew != "auto" and sh.boundaries is None):
            return opts
        leaves = jax.tree.leaves(items)
        if any(isinstance(l, jax.ShapeDtypeStruct) for l in leaves):
            return opts  # spec-only lowering: nothing to sample
        S = _shard_count(opts, mode)
        if S is None or S <= 1:
            return opts
        resolved, profile = sk.resolve_shuffle_options(
            self.app, self.plan, items, num_shards=S, options=sh)
        lines: list[str] = []
        if profile is not None:
            lines.extend(profile.describe())
        splan = sk.plan_from_options(
            self.app.key_space, S, resolved, flow=self.plan.flow,
            spec=self.plan.spec, value_aval=self.app.value_aval)
        if splan is not None:
            lines.extend(splan.describe())
        elif profile is not None and resolved.boundaries is None:
            lines.append(
                f"plan: fixed-width ranges kept (imbalance at/under the "
                f"{sk.SNAP_IMBALANCE}x snap threshold)")
        if lines:
            self.plan.skew = tuple(lines)
        if resolved is sh:
            return opts
        return dataclasses.replace(opts, shuffle=resolved)

    def _wire_provenance(self, opts: ExecutionOptions, items,
                         mode: str) -> tuple[str, ...]:
        """``explain()`` lines for a non-raw shuffle wire codec: which
        codec the all-to-all (and the resilient driver's checkpointed
        partials) ride under, plus the modeled encoded-vs-raw bytes when
        the item count is known at lower() time."""
        from repro.roofline import analysis as roofline

        sh = opts.shuffle
        lines = [f"codec {sh.wire} on the all-to-all + checkpointed "
                 f"partials (distributed/wire.py)"]
        S = _shard_count(opts, mode)
        leaves = jax.tree.leaves(items)
        if (S and S > 1 and leaves
                and not any(isinstance(l, jax.ShapeDtypeStruct)
                            for l in leaves)):
            n_pairs = int(leaves[0].shape[0]) * self.app.emit_capacity
            value_bytes = int(
                jnp.dtype(self.app.value_aval.dtype).itemsize
                * max(1, int(np.prod(self.app.value_aval.shape))))
            kw = dict(n_pairs=n_pairs, key_space=self.app.key_space,
                      num_shards=S, value_bytes=value_bytes,
                      value_dtype=str(self.app.value_aval.dtype),
                      capacity=sh.capacity)
            enc_b = roofline.shuffle_wire_bytes(sh.wire, **kw)
            raw_b = roofline.shuffle_wire_bytes("raw", **kw)
            if raw_b > 0:
                lines.append(
                    f"modeled wire bytes/shard: {enc_b / 1e3:.1f}kB "
                    f"({enc_b / raw_b:.2f}x raw {raw_b / 1e3:.1f}kB) "
                    f"at S={S}")
        return tuple(lines)

    def run(self, items, *, options: ExecutionOptions | None = None,
            **legacy) -> MapReduceResult:
        opts = _resolve_options(options, legacy, method="run")
        with trace.span(trace.RUN, rid=trace.next_job()):
            return self.lower(items, options=opts, mode="local"
                              ).optimize().compile()(items)

    def run_distributed(self, items, *, mesh=None,
                        options: ExecutionOptions | None = None,
                        **legacy) -> MapReduceResult:
        """Distributed run — shard_map over the mesh's data axis.

        ``options`` carries ``scatter_output``, ``shuffle_capacity``,
        ``strict_shuffle``, ...; the mesh may come as the ``mesh=``
        argument or on the options."""
        opts = _resolve_options(options, legacy, method="run_distributed",
                                mesh=mesh)
        if opts.mesh is None:
            raise TypeError("run_distributed requires a mesh (pass mesh=... "
                            "or options=ExecutionOptions(mesh=...))")
        with trace.span(trace.RUN_DISTRIBUTED, rid=trace.next_job()):
            return self.lower(items, options=opts, mode="distributed"
                              ).optimize().compile()(items)

    def run_resilient(self, items, *, mesh=None,
                      options: ExecutionOptions | None = None,
                      **legacy) -> MapReduceResult:
        """Fault-tolerant distributed run (``engine.run_resilient``):
        deterministic shard re-execution, checkpointed partial-aggregate
        recovery (``ckpt_dir=...``), straggler speculation and elastic
        remesh — the result is bitwise the fault-free
        :meth:`run_distributed` answer.  The recovery ledger lands on
        ``result.recovery`` and, summarized, on ``plan.recovery`` (shown
        by :meth:`explain`)."""
        opts = _resolve_options(options, legacy, method="run_resilient",
                                mesh=mesh)
        return self.lower(items, options=opts, mode="resilient"
                          ).optimize().compile()(items)

    def serve(self, *, batch_capacity: int, window=None,
              options: ExecutionOptions | None = None,
              item_spec=None,
              ckpt_dir: str | None = None, ckpt_every: int = 0,
              keep_ckpts: int = 3, retry_policy=None):
        """Stage this plan into a long-lived
        :class:`repro.streaming.MapReduceService`.

        The staged path runs once (``lower().optimize().compile()`` at
        mode="streaming"); every subsequent ``service.ingest(items)`` is a
        plain dispatch of the AOT ingest executable — no re-trace, no
        re-tune, no re-compile.  Micro-batches of up to ``batch_capacity``
        items fold incrementally into persistent holder tables;
        ``window`` (a :class:`repro.streaming.Window`) bounds aggregation
        to the trailing micro-batches; ``ckpt_dir``/``ckpt_every`` enable
        periodic atomic table checkpoints for warm restarts
        (:meth:`MapReduceService.restore`).

        ``item_spec`` (a ShapeDtypeStruct pytree of ONE item) compiles the
        ingest executable eagerly — required before ``restore()`` on a
        fresh service; omitted, staging happens at the first ingest.
        """
        from repro.streaming import MapReduceService

        return MapReduceService(
            self, batch_capacity=batch_capacity, window=window,
            options=options, item_spec=item_spec, ckpt_dir=ckpt_dir,
            ckpt_every=ckpt_every, keep_ckpts=keep_ckpts,
            retry_policy=retry_policy)

    def explain(self) -> str:
        """The optimizer's decision record: flow, derived combiner, the
        autotuned tiling and any lowering diagnostics."""
        return self.plan.explain()


# ---------------------------------------------------------------------------
# The explicit stages: Lowered -> Optimized -> Compiled
# ---------------------------------------------------------------------------


def _shard_count(opts: ExecutionOptions, mode: str) -> int | None:
    """Shard count a run in ``mode`` will see — mirrors
    ``engine.run_resilient``'s host/shard resolution so the skew plan is
    derived for the exact all-to-all it will route.  None when the mesh
    is not known yet (distributed mode without a mesh)."""
    mesh_hosts = (int(opts.mesh.shape[opts.data_axis])
                  if opts.mesh is not None else None)
    if mode == "distributed":
        return mesh_hosts
    H = opts.num_hosts if opts.num_hosts is not None else (mesh_hosts or 1)
    return int(opts.num_shards if opts.num_shards is not None
               else (mesh_hosts or H))


def _infer_mode(opts: ExecutionOptions, mode: str | None) -> str:
    if mode is not None:
        if mode not in ("local", "distributed", "resilient", "streaming"):
            raise ValueError(f"unknown execution mode {mode!r}")
        return mode
    return "local" if opts.mesh is None else "distributed"


class Lowered:
    """Stage 1 of the staged path: plan × item spec.

    ``optimize(...)`` binds/overrides execution options; ``compile()`` is
    the shortcut ``optimize().compile()`` (kept so the long-standing
    ``mr.lower(items).compile()`` introspection idiom works unchanged)."""

    def __init__(self, mr: MapReduce, items_spec, options: ExecutionOptions,
                 *, mode: str | None = None):
        self.mr = mr
        self.items_spec = items_spec
        self.options = options
        self.mode = _infer_mode(options, mode)

    def optimize(self, options: ExecutionOptions | None = None,
                 **hints) -> "Optimized":
        """Stage 2: fix the execution options.  ``hints`` are individual
        ExecutionOptions field overrides (e.g. ``items_bucket="pow2"``)."""
        opts = options if options is not None else self.options
        if hints:
            unknown = sorted(set(hints) - _OPTION_FIELDS)
            if unknown:
                raise TypeError(f"optimize() got unknown hints {unknown}")
            opts = dataclasses.replace(opts, **hints)
        return Optimized(self.mr, self.items_spec, opts, mode=self.mode)

    def compile(self) -> "Compiled":
        return self.optimize().compile()

    def explain(self) -> str:
        plan = dataclasses.replace(self.mr.plan, stage="lowered")
        return (plan.explain()
                + f"\nitems: {pc._spec_sig(self.items_spec)}")


class Optimized:
    """Stage 2: plan × item spec × execution options (mode resolved)."""

    def __init__(self, mr: MapReduce, items_spec, options: ExecutionOptions,
                 *, mode: str):
        self.mr = mr
        self.items_spec = items_spec
        self.options = options
        self.mode = mode
        n = jax.tree.leaves(items_spec)[0].shape[0]
        self.n_items = int(n)
        if options.items_bucket != "exact" and mode != "local":
            # pow2 batch bucketing needs the local flows' n_valid masking;
            # the shard_map'd paths keep jit's exact-shape contract.
            self.n_bucket = self.n_items
        else:
            self.n_bucket = pc.bucket_items(self.n_items,
                                            options.items_bucket)
        with mr.width():  # the key traces the map
            self.cache_key = self._cache_key()

    def _cache_key(self) -> str | None:
        if self.mode == "resilient":
            return None  # host driver: rebuilt per call, nothing compiled
        opts = self.options
        knobs = self.mr._knobs(opts)
        spec = self.items_spec
        padded = self.n_bucket != self.n_items
        if padded:
            # pow2 bucketing: the executable is traced at the padded shape,
            # so every N in the bucket must map to the same key
            spec = jax.tree.map(
                lambda a: jax.ShapeDtypeStruct(
                    (self.n_bucket,) + tuple(a.shape[1:]), a.dtype), spec)
        return pc.compiled_key(
            self.mr.app, spec, plan_key=self.mr._plan_key,
            flow=self.mr.plan.flow, n_bucket=self.n_bucket, mesh=opts.mesh,
            data_axis=opts.data_axis, mode=self.mode,
            # `padded` distinguishes the (items, n_valid) calling convention
            # from the exact (items,) one at the same traced shape — e.g. a
            # pow2 batch of 5 padded to 8 vs an exact-fit batch of 8
            # repr(opts.shuffle) digests the FULL resolved shuffle record —
            # capacity/strict plus the skew planner's boundaries and hot
            # splits — so warm repeats re-derive nothing and two plans with
            # different boundary layouts never share an executable
            extra=(f"padded={padded}", f"bucket={opts.items_bucket}",
                   opts.scatter_output, opts.shuffle_capacity,
                   repr(opts.shuffle),
                   knobs["combine_impl"], knobs["use_kernels"],
                   knobs["chunk_pairs"], knobs["key_block"],
                   knobs["bucket_size"]))

    def compile(self) -> "Compiled":
        """Stage 3: produce the executable.  Content-cached — a warm hit
        returns the stored executable with zero traces, zero autotune
        calls and zero XLA compiles."""
        use_cache = self.options.cache and self.cache_key is not None
        if use_cache:
            ent = pc.compiled_get(self.cache_key)
            if ent is not None:
                return Compiled(self, ent, cache_event="hit")
        with self.mr.width():
            ent = self._build()
        if use_cache:
            pc.compiled_put(self.cache_key, ent)
        return Compiled(self, ent,
                        cache_event="miss" if use_cache else "")

    def _build(self) -> pc.CompiledEntry:
        mr, opts = self.mr, self.options
        knobs = mr._knobs(opts)
        plan = mr.plan
        if self.mode == "local":
            pc.STATS.compiles += 1
            if self.n_bucket == self.n_items:
                fn = jax.jit(partial(eng.run_local, mr.app, plan, **knobs))
                executable = fn.lower(self.items_spec).compile()
            else:
                padded = jax.tree.map(
                    lambda a: jax.ShapeDtypeStruct(
                        (self.n_bucket,) + tuple(a.shape[1:]), a.dtype),
                    self.items_spec)
                fn = jax.jit(lambda items, n_valid: eng.run_local(
                    mr.app, plan, items, n_valid=n_valid, **knobs))
                executable = fn.lower(
                    padded, jax.ShapeDtypeStruct((), jnp.int32)).compile()
            return pc.CompiledEntry(executable=executable, plan=plan,
                                    tiling=mr.tiling, n_bucket=self.n_bucket,
                                    mode="local")
        if self.mode == "streaming":
            if plan.flow != "stream":
                raise ValueError(
                    f"streaming mode requires the stream flow (plan chose "
                    f"{plan.flow!r}); construct MapReduce(app, "
                    f"streaming=True) or flow='stream'")
            pc.STATS.compiles += 1
            sc, ingest = eng.build_stream_ingest(
                mr.app, plan.spec, batch_items=self.n_bucket,
                chunk_pairs=knobs["chunk_pairs"],
                use_kernels=knobs["use_kernels"],
                key_block=knobs["key_block"],
                on_fallback=eng._plan_fallback_cb(plan))
            state_spec = jax.eval_shape(sc.init_state)
            # AOT: (state, padded items, n_valid) -> state.  One executable
            # serves every micro-batch size in [0, batch_capacity] — the
            # pad rows are masked to the sentinel key, contributing exact
            # zero to the fold.
            executable = jax.jit(ingest).lower(
                state_spec, self.items_spec,
                jax.ShapeDtypeStruct((), jnp.int32)).compile()
            return pc.CompiledEntry(executable=executable, plan=plan,
                                    tiling=mr.tiling, n_bucket=self.n_bucket,
                                    mode="streaming", aux=sc)
        if self.mode == "distributed":
            pc.STATS.compiles += 1
            S = opts.mesh.shape[opts.data_axis]
            chunk_pairs, key_block = eng._distributed_tiling(
                mr.app, plan, self.items_spec, S,
                use_kernels=knobs["use_kernels"],
                chunk_pairs=opts.chunk_pairs, key_block=opts.key_block)
            jitted, post = eng.build_distributed_fn(
                mr.app, plan, mesh=opts.mesh, data_axis=opts.data_axis,
                combine_impl=knobs["combine_impl"],
                use_kernels=knobs["use_kernels"],
                scatter_output=opts.scatter_output,
                shuffle_capacity=opts.shuffle_capacity,
                chunk_pairs=chunk_pairs, key_block=key_block,
                bucket_size=knobs["bucket_size"],
                shuffle_plan=sk.plan_from_options(
                    mr.app.key_space, S, opts.shuffle, flow=plan.flow,
                    spec=plan.spec, value_aval=mr.app.value_aval),
                wire=(opts.shuffle.wire if opts.shuffle is not None
                      else "raw"))
            # the persistent jitted shard_map IS the executable: repeat
            # calls hit jit's trace cache instead of rebuilding the
            # shard_map per call like the old run_distributed did
            return pc.CompiledEntry(executable=jitted, plan=plan,
                                    tiling=mr.tiling, n_bucket=self.n_bucket,
                                    mode="distributed", aux=post)

        S_res = _shard_count(opts, "resilient")
        res_plan = sk.plan_from_options(
            mr.app.key_space, S_res, opts.shuffle, flow=plan.flow,
            spec=plan.spec, value_aval=mr.app.value_aval)
        # resilient mode is never plan-cached (the drive closure is a host
        # driver, not an executable), so the jitted phase functions cache
        # on the MapReduce instance — repeat run_resilient() calls pay
        # dispatch, not re-trace/re-compile, of phases A and B
        jits = mr.__dict__.setdefault("_resilient_jits", {})

        def drive(items):  # resilient host driver — not XLA-compilable
            return eng.run_resilient(
                mr.app, plan, items, mesh=opts.mesh,
                num_hosts=opts.num_hosts, num_shards=opts.num_shards,
                data_axis=opts.data_axis, step=opts.step,
                ckpt_dir=opts.ckpt_dir, inject=opts.inject,
                timeout_s=opts.timeout_s, straggler_lag=opts.straggler_lag,
                combine_impl=knobs["combine_impl"],
                use_kernels=knobs["use_kernels"],
                shuffle_capacity=opts.shuffle_capacity,
                chunk_pairs=opts.chunk_pairs, key_block=opts.key_block,
                bucket_size=opts.bucket_size,
                strict_shuffle=opts.strict_shuffle,
                shuffle_plan=res_plan,
                wire=(opts.shuffle.wire if opts.shuffle is not None
                      else "raw"),
                coord=opts.coord, retry=opts.retry, chaos=opts.chaos,
                jit_cache=jits)

        return pc.CompiledEntry(executable=drive, plan=plan,
                                tiling=mr.tiling, n_bucket=self.n_bucket,
                                mode="resilient")

    def explain(self) -> str:
        plan = dataclasses.replace(self.mr.plan, stage="optimized")
        lines = [plan.explain(),
                 f"mode: {self.mode}",
                 f"items: {pc._spec_sig(self.items_spec)} "
                 f"(N={self.n_items} bucket={self.n_bucket} "
                 f"policy={self.options.items_bucket})"]
        if self.cache_key is not None:
            lines.append(f"compiled-cache key: {self.cache_key}")
        return "\n".join(lines)


class Compiled:
    """Stage 3: the executable.  ``compiled(items)`` dispatches (AOT for
    local runs; a persistent jitted shard_map for distributed); the XLA
    introspection surface (``as_text``/``memory_analysis``/
    ``cost_analysis``) passes through on local executables."""

    def __init__(self, opt: Optimized, entry: pc.CompiledEntry,
                 *, cache_event: str):
        self.options = opt.options
        self.mode = entry.mode
        self.items_spec = opt.items_spec
        self.n_items = opt.n_items
        self.n_bucket = entry.n_bucket
        self.cache_key = opt.cache_key
        self.cache_event = cache_event
        self._entry = entry
        self.width = opt.mr.width
        # a fresh copy of the plan the executable was traced with: run-time
        # diagnostics (shuffle overflow, lowering fallbacks) land here
        # without polluting other Compiled objects sharing the cache entry
        self.plan = dataclasses.replace(entry.plan, stage="compiled")

    def __call__(self, items) -> MapReduceResult:
        with self.width():
            return self._call(items)

    def _call(self, items) -> MapReduceResult:
        if self.mode == "streaming":
            raise TypeError(
                "a streaming-mode Compiled is an incremental ingest "
                "executable, not a batch job — drive it through "
                "MapReduceService (MapReduce.serve(...)) or via "
                "init_state()/ingest_state()")
        if self.mode == "local":
            items = jax.tree.map(jnp.asarray, items)
            if self.n_bucket != self.n_items:
                pad = self.n_bucket - self.n_items
                items = jax.tree.map(
                    lambda a: jnp.concatenate(
                        [a, jnp.zeros((pad,) + a.shape[1:], a.dtype)]),
                    items)
                with trace.span(trace.DISPATCH):
                    keys, values, counts = self._entry.executable(
                        items, jnp.int32(self.n_items))
            else:
                with trace.span(trace.DISPATCH):
                    keys, values, counts = self._entry.executable(items)
            return MapReduceResult(keys, values, counts, plan=self.plan)
        if self.mode == "distributed":
            with trace.span(trace.DISPATCH):
                out = self._entry.executable(items)
            keys, values, counts = self._entry.aux(
                out, strict_shuffle=self.options.strict_shuffle)
            return MapReduceResult(keys, values, counts, plan=self.plan)
        keys, values, counts, log = self._entry.executable(items)
        return MapReduceResult(keys, values, counts, plan=self.plan,
                               recovery=log)

    # -- streaming-mode surface (driven by repro.streaming.MapReduceService)

    def init_state(self):
        """Fresh carried combiner state (streaming mode)."""
        with self.width():
            return self._entry.aux.init_state()

    def ingest_state(self, state, items, n_valid):
        """Fold one padded micro-batch into ``state`` (streaming mode).

        Pure AOT dispatch: ``items`` must already be padded to the lowered
        ``batch_capacity`` shape; ``n_valid`` masks the tail."""
        with self.width():
            return self._entry.executable(state, items, jnp.int32(n_valid))

    def state_tables(self, state):
        """Un-finalized ``(tables, counts)`` view of a carried state."""
        with self.width():
            return self._entry.aux.tables_counts(state)

    def finalize_state(self, state):
        """Finalized ``Grouped(keys, values, counts)`` of a carried state."""
        with self.width():
            return self._entry.aux.finalize(state)

    # -- XLA introspection pass-through (local AOT executables) -------------

    def as_text(self) -> str:
        return self._entry.executable.as_text()

    def memory_analysis(self):
        return self._entry.executable.memory_analysis()

    def cost_analysis(self):
        return self._entry.executable.cost_analysis()

    def explain(self) -> str:
        lines = [self.plan.explain(), f"mode: {self.mode}"]
        if self.cache_key is not None:
            lines.append(f"compiled-cache: {self.cache_event or 'off'} "
                         f"key={self.cache_key}")
        if self.n_bucket != self.n_items:
            lines.append(f"items: padded N={self.n_items} -> "
                         f"bucket={self.n_bucket} (pad rows masked)")
        return "\n".join(lines)
