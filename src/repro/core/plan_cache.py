"""Content-keyed compiled-plan cache (ROADMAP: the serving unlock).

``MapReduce.run`` used to re-run the optimizer (a jaxpr trace + numeric
validation probes), re-autotune the tiling and rebuild its jitted
executable on every construction — the opposite of the serving posture,
where the same app shape arrives millions of times.  This module gives the
staged ``lower()/optimize()/compile()`` path (``core/api.py``) a
process-wide cache keyed by *content*, not object identity:

    reduce-jaxpr hash x map-jaxpr hash x K x value dtype/shape x N-bucket
    x flow x lowering knobs x mesh shape

so repeat traffic — same app semantics, same shapes — never re-derives,
never re-tunes and never re-compiles, no matter how many ``MapReduce`` /
``Pipeline`` objects the caller constructs.  The JaCe/JAX AOT stage
architecture is the model: the cache sits between ``optimize()`` and
``compile()`` and stores the whole stage-chain result.

Two layers:

* **in-memory** (``_PLANS`` / ``_COMPILED``) — full hits: the cached
  ``ExecutionPlan`` (with its live ``CombinerSpec`` closures), the
  autotuned ``StreamTiling`` and the compiled executable are reused
  directly.  Zero optimizer traces, zero autotune calls, zero XLA
  compiles on a hit (asserted via :data:`STATS` counters in the tests).
* **file-backed** (opt-in via ``JAX_PALLAS_PLAN_CACHE``) — a JSON side
  file persisting the *decisions* (flow, chunk size, key block, level
  fan-outs) across processes.  Combiner closures and executables cannot
  be serialized, so a file hit still derives and compiles — but skips the
  autotune probes.  Exactly like ``JAX_PALLAS_TUNE_CACHE`` the file layer
  is advisory and corrupt-safe: unreadable files, malformed entries and
  stale schemas are ignored, never fatal.

Counters (``STATS``) are bumped at the places the cache is meant to make
idle — ``optimizer.derive_combiner`` (the optimizer's trace), the
``autotune_stream``/``autotune_sort`` calls, the measured micro-probe, and
the staged ``compile()`` — so tests can assert "warm traffic does none of
this" instead of trusting the docs.  They are process-wide totals; the
traces and compiles inside one call are on its span's record
(``repro.core.trace``).
"""

from __future__ import annotations

import dataclasses
import hashlib
import itertools
import json
import os
from typing import Any

#: env var pointing at the persistent plan-decision cache (JSON file).
#: Unset (the default, and in CI) -> plan decisions are not persisted.
PLAN_CACHE_ENV = "JAX_PALLAS_PLAN_CACHE"


# ---------------------------------------------------------------------------
# Counters: what the cache is supposed to save, made assertable
# ---------------------------------------------------------------------------


#: the streaming fold's lowerings (``collector.stream_mode``)
FOLD_LOWERINGS = ("additive", "dense", "scatter", "first", "size",
                  "sequential")


@dataclasses.dataclass
class CacheStats:
    """Process-wide event counters (see module docstring).

    ``derives`` counts optimizer runs (each is a jaxpr trace + validation
    probes), ``autotunes`` the tiling autotuner calls, ``probes`` the
    measured micro-probe invocations, ``compiles`` the staged XLA
    compiles.  ``hits``/``misses`` are in-memory compiled-plan lookups;
    ``plan_hits``/``plan_misses`` the plan-stage (pre-shape) lookups;
    ``file_hits`` the advisory file-layer hits.  ``folds`` counts the
    streaming folds built (``collector.StreamCombiner``) per lowering,
    ``folds.<lowering>`` in a snapshot.  ``wide_jobs`` counts the
    :class:`~repro.core.api.MapReduce` plans built for wide (64-bit value)
    jobs.  ``holder_bytes`` is per plan, not an event: ``{plan key: bytes
    of holder state folded per emitted pair}`` (every holder column's
    itemsize x width, plus the int32 count), left out of snapshots."""

    derives: int = 0
    autotunes: int = 0
    probes: int = 0
    compiles: int = 0
    hits: int = 0
    misses: int = 0
    plan_hits: int = 0
    plan_misses: int = 0
    file_hits: int = 0
    folds: dict = dataclasses.field(
        default_factory=lambda: dict.fromkeys(FOLD_LOWERINGS, 0))
    wide_jobs: int = 0
    holder_bytes: dict = dataclasses.field(default_factory=dict)

    def snapshot(self) -> dict:
        snap = dataclasses.asdict(self)
        del snap["holder_bytes"]
        folds = snap.pop("folds")
        snap.update({f"folds.{m}": n for m, n in folds.items()})
        return snap


STATS = CacheStats()


def stats_snapshot() -> dict:
    """Copy of the counters — diff two snapshots to assert cache behaviour."""
    return STATS.snapshot()


# ---------------------------------------------------------------------------
# Content fingerprints
# ---------------------------------------------------------------------------


#: fallback identity for untraceable map/reduce fns.  A monotonic counter
#: stored on the app — unlike ``id(app)``, never reused after the app is
#: garbage-collected, so a fallback key can never alias another app's
#: cached plan.
_FALLBACK_UIDS = itertools.count()


def _fallback_uid(app) -> int:
    memo = app.__dict__.setdefault("_plan_cache_fp", {})
    if "uid" not in memo:
        memo["uid"] = next(_FALLBACK_UIDS)
    return memo["uid"]


def _digest(*parts: str) -> str:
    h = hashlib.sha256()
    for p in parts:
        h.update(p.encode())
        h.update(b"\x00")
    return h.hexdigest()[:16]


def _aval_sig(aval) -> str:
    import jax.numpy as jnp

    return f"{jnp.dtype(aval.dtype).name}{tuple(aval.shape)}"


def _jaxpr_sig(closed) -> str:
    """Content signature of a ClosedJaxpr: the printed program plus a hash
    of every captured constant's BYTES — ``str(jaxpr)`` alone elides large
    const values, so two closures differing only in a captured lookup
    table would otherwise collide."""
    import numpy as np

    parts = [str(closed)]
    for c in getattr(closed, "consts", ()):
        try:
            a = np.asarray(c)
            parts.append(f"{a.dtype}{a.shape}:"
                         + hashlib.sha256(a.tobytes()).hexdigest()[:12])
        except Exception:
            parts.append(repr(c))
    return "\x00".join(parts)


def _app_attr_sig(app) -> str:
    return "|".join([
        f"K={app.key_space}",
        f"v={_aval_sig(app.value_aval)}",
        f"cap={app.emit_capacity}",
        f"lmax={getattr(app, 'max_values_per_key', 0)}",
        f"pad={app.pad_value!r}",
    ])


def reduce_fingerprint(app) -> str:
    """Content hash of the app's reduce semantics: the jaxpr of
    ``reduce(key, values, count)`` (traced once, memoized on the app
    instance) plus the attributes the planner keys on.  Two app objects
    with identical reduce code and shapes share the fingerprint — that is
    what makes the cache *content*-keyed rather than id-keyed."""
    memo = app.__dict__.setdefault("_plan_cache_fp", {})
    if "reduce" not in memo:
        import jax
        import jax.numpy as jnp

        aval = app.value_aval
        try:
            jaxpr = jax.make_jaxpr(app.reduce)(
                jax.ShapeDtypeStruct((), jnp.int32),
                jax.ShapeDtypeStruct((4,) + tuple(aval.shape), aval.dtype),
                jax.ShapeDtypeStruct((), jnp.int32))
            sig = _jaxpr_sig(jaxpr)
        except Exception:  # untraceable reduce: fall back to identity
            sig = f"uid:{_fallback_uid(app)}:{type(app).__qualname__}"
        memo["reduce"] = _digest(sig, _app_attr_sig(app))
    return memo["reduce"]


def map_fingerprint(app, item_spec) -> str:
    """Content hash of the app's map semantics over one item of
    ``item_spec``: the jaxpr of ``map(item, emit)`` through a recording
    emitter (traced once per item spec, memoized on the app instance)."""
    spec_sig = _spec_sig(item_spec)
    memo = app.__dict__.setdefault("_plan_cache_fp", {})
    key = f"map:{spec_sig}"
    if key not in memo:
        import jax

        from repro.core import engine as eng

        def one(item):
            em = eng.Emitter(app.emit_capacity, app.key_space, app.value_aval)
            app.map(item, em)
            return em.pairs()

        try:
            sig = _jaxpr_sig(jax.make_jaxpr(one)(item_spec))
        except Exception:
            sig = f"uid:{_fallback_uid(app)}:{type(app).__qualname__}"
        memo[key] = _digest(sig, spec_sig)
    return memo[key]


def _spec_sig(spec_tree) -> str:
    import jax

    leaves, treedef = jax.tree.flatten(spec_tree)
    return f"{treedef}:" + ",".join(_aval_sig(x) for x in leaves)


def items_spec_of(items):
    """ShapeDtypeStruct pytree of ``items`` (concrete arrays pass through
    ``jax.eval_shape``-style; specs are returned unchanged)."""
    import jax

    return jax.tree.map(
        lambda a: (a if isinstance(a, jax.ShapeDtypeStruct)
                   else jax.ShapeDtypeStruct(a.shape, a.dtype)), items)


def item_spec_of(items_spec):
    """One-item spec: ``items_spec`` with the leading (batch) axis dropped."""
    import jax

    return jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(tuple(a.shape[1:]), a.dtype),
        items_spec)


def bucket_items(n: int, policy: str = "exact") -> int:
    """The N-bucket of the cache key: ``"exact"`` keeps the true item
    count (one executable per shape — jit's contract); ``"pow2"`` rounds
    up to the next power of two so nearby batch sizes share one padded
    executable (the serving case; ``Compiled`` masks the pad rows)."""
    if policy == "exact":
        return int(n)
    if policy == "pow2":
        b = 1
        while b < n:
            b <<= 1
        return b
    raise ValueError(f"unknown items bucket policy {policy!r}")


def plan_key(app, *, flow: str, trust_semantics: bool,
             n_pairs_hint: int | None, use_kernels: bool,
             combine_impl: str, chunk_pairs, key_block,
             autotune_probe: bool, streaming: bool = False,
             wide: bool = False) -> str:
    """Key of the plan stage (derivation + flow selection + tiling) —
    everything :class:`MapReduce` resolves before it sees item shapes,
    which includes the platform the fold lowering is chosen for and the
    job's width (``api.wide_job``)."""
    from repro.core import collector
    return _digest(
        "plan", reduce_fingerprint(app), _app_attr_sig(app),
        f"flow={flow}", f"trust={trust_semantics}",
        f"hint={n_pairs_hint}", f"kern={use_kernels}",
        f"impl={combine_impl}", f"chunk={chunk_pairs}",
        f"blk={key_block}", f"probe={autotune_probe}",
        f"streaming={streaming}", f"platform={collector.fold_platform()}",
        f"wide={wide}")


def compiled_key(app, items_spec, *, plan_key: str, flow: str,
                 n_bucket: int, mesh=None, data_axis: str = "data",
                 mode: str = "local", extra: tuple = ()) -> str:
    """Key of the compiled stage: the plan key x the map jaxpr over the
    item spec x the (bucketed) batch shape x the mesh topology x the
    execution mode and any residual lowering knobs."""
    mesh_sig = ("none" if mesh is None else
                f"{dict(zip(mesh.axis_names, mesh.devices.shape))}")
    return _digest(
        "compiled", plan_key,
        map_fingerprint(app, item_spec_of(items_spec)),
        _spec_sig(items_spec), f"N={n_bucket}", f"flow={flow}",
        f"mesh={mesh_sig}", f"axis={data_axis}", f"mode={mode}",
        *[str(x) for x in extra])


# ---------------------------------------------------------------------------
# In-memory cache
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class PlanEntry:
    """Cached plan stage: the resolved plan (template), tiling and the
    lowering knobs the API layer derived from them."""

    plan: Any
    tiling: Any
    stream_chunk_pairs: int
    key_block: int | None
    bucket_size: int | None


@dataclasses.dataclass
class CompiledEntry:
    """Cached compile stage: the executable plus everything ``explain()``
    and the result plumbing need."""

    executable: Any
    plan: Any
    tiling: Any
    n_bucket: int
    mode: str  # "local" | "distributed"
    aux: Any = None


_PLANS: dict[str, PlanEntry] = {}
_COMPILED: dict[str, CompiledEntry] = {}


def plan_get(key: str) -> PlanEntry | None:
    hit = _PLANS.get(key)
    if hit is None:
        STATS.plan_misses += 1
    else:
        STATS.plan_hits += 1
    return hit


def plan_put(key: str, entry: PlanEntry) -> None:
    _PLANS[key] = entry


def compiled_get(key: str) -> CompiledEntry | None:
    hit = _COMPILED.get(key)
    if hit is None:
        STATS.misses += 1
    else:
        STATS.hits += 1
    return hit


def compiled_put(key: str, entry: CompiledEntry) -> None:
    _COMPILED[key] = entry


def clear() -> None:
    """Drop both in-memory layers (tests; the file layer is untouched)."""
    _PLANS.clear()
    _COMPILED.clear()


def sizes() -> tuple[int, int]:
    return len(_PLANS), len(_COMPILED)


# ---------------------------------------------------------------------------
# File-backed advisory layer (cross-process plan decisions)
# ---------------------------------------------------------------------------

#: fields a file entry must carry with these exact types to be trusted;
#: anything else — hand-edited files, entries from an older schema, plain
#: corruption — reads as "no entry" (the tune-cache corrupt-safe contract).
_FILE_SCHEMA = {"flow": str, "chunk_pairs": int}
_FILE_OPTIONAL = {"key_block": int, "bucket_size": int}


def plan_cache_path() -> str | None:
    p = os.environ.get(PLAN_CACHE_ENV, "").strip()
    return p or None


def _load_file(path: str) -> dict:
    try:
        with open(path) as f:
            data = json.load(f)
        return data if isinstance(data, dict) else {}
    except (OSError, ValueError):
        return {}


def _entry_valid(entry) -> bool:
    if not isinstance(entry, dict):
        return False
    for field, typ in _FILE_SCHEMA.items():
        if not isinstance(entry.get(field), typ):
            return False
    for field, typ in _FILE_OPTIONAL.items():
        if field in entry and entry[field] is not None \
                and not isinstance(entry[field], typ):
            return False
    if entry["flow"] not in ("stream", "sort", "combine", "reduce"):
        return False
    return True


def file_get(key: str) -> dict | None:
    """Validated file-layer entry for ``key``, or None (missing file,
    corrupt JSON, malformed/stale entry — all read the same: no entry)."""
    path = plan_cache_path()
    if path is None:
        return None
    entry = _load_file(path).get(key)
    if not _entry_valid(entry):
        return None
    STATS.file_hits += 1
    return entry


def file_put(key: str, entry: dict) -> bool:
    """Best-effort merge into the file layer (atomic replace; failures are
    swallowed — the cache must never break a run)."""
    path = plan_cache_path()
    if path is None:
        return False
    try:
        cache = _load_file(path)
        cache[key] = entry
        tmp = f"{path}.tmp.{os.getpid()}"
        with open(tmp, "w") as f:
            json.dump(cache, f, indent=1, sort_keys=True)
        os.replace(tmp, path)
        return True
    except OSError:
        return False


def file_entry_from(plan, tiling) -> dict:
    """Serializable decision record of a resolved plan stage."""
    entry: dict[str, Any] = {"flow": plan.flow}
    if tiling is not None:
        entry["chunk_pairs"] = int(tiling.chunk_pairs)
        entry["key_block"] = int(tiling.key_block)
    else:
        from repro.core.engine import DEFAULT_CHUNK_PAIRS

        entry["chunk_pairs"] = int(DEFAULT_CHUNK_PAIRS)
    return entry
