"""Skew-adaptive shuffle planning: sampled histograms -> balanced ranges.

PR 5 made shuffle overflow under key skew *detected*; this module makes it
*handled*, following the data-statistics-driven replanning line (Jahani et
al.; Casper): the framework samples the emitted key distribution, derives
**balanced range boundaries** for the sort/reduce all-to-all instead of the
fixed-width ``k // ceil(K/S)`` radix ranges, and **splits hot keys** across
several destination shards — exact, because the derived combiner is a
monoid, so per-destination partial aggregates of one key recombine to the
unsplit answer (``engine.merge_tables_collective`` /
``engine._merge_tables_host``).

The user surface is one frozen :class:`ShuffleOptions` record carried as
``ExecutionOptions.shuffle``:

* ``capacity`` / ``strict`` — the former flat ``shuffle_capacity`` /
  ``strict_shuffle`` knobs (which now forward here with a
  ``DeprecationWarning``).
* ``skew="auto"`` — sample a key histogram at ``lower()`` time (concrete
  items in hand), derive boundaries + hot-key splits, and memoize the
  decision in-process and (opt-in) in the ``JAX_PALLAS_TUNE_CACHE`` file
  alongside the autotuner's ``StreamTiling`` entries.
* explicit ``boundaries=`` — bypass sampling entirely (tests, replay).

The resolved record is what the plan-cache key digests (``repr`` of the
frozen dataclass), so warm repeat traffic re-derives nothing.

Derivation policy (host-side numpy, sample-sized — micro-probe cheap):

* fixed-width imbalance ``max(range load) / (total/S)`` at or under
  :data:`SNAP_IMBALANCE` snaps to the identity plan (``boundaries=None``)
  — the engine then runs the bitwise-legacy fixed-width arithmetic, which
  is what makes "skew-planned == fixed-width on uniform keys" trivially
  exact.
* keys holding more than :data:`HOT_KEY_FRACTION` of a uniform shard
  share are *hot*: they are carved out of the range balancing and split
  round-robin over ``min(hot_key_split_max, S, ceil(mass/half-share))``
  consecutive shards starting at the range owner (only when the combiner
  is a commutative dense monoid — see :func:`hot_split_ok`).
* boundaries are prefix cuts of the residual histogram's cumulative mass
  at ``j/S``, forced strictly increasing so every shard owns a non-empty
  key range (the engine's static range width is ``max(span)``).
* the default per-destination capacity envelope derives from the sampled
  p-max destination load plus :data:`CAPACITY_SLACK` headroom instead of
  the uniform ``2N/S`` assumption (:meth:`ShufflePlan.capacity_for`).

``SKEW_STATS`` counts samples, cache hits and resolves process-wide; the
traces and compiles inside one call are on its span's record
(``repro.core.trace``).
"""

from __future__ import annotations

import bisect
import dataclasses
import hashlib
import zlib
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

#: hard cap on the sampled pair count — keeps the probe micro-sized no
#: matter the workload (mirrors the autotuner's probe posture).
SAMPLE_PAIR_CAP = 4096
#: fixed-width imbalance at/below this snaps to the identity plan (the
#: legacy fixed-width path, bitwise) — mild skew is not worth replanning.
SNAP_IMBALANCE = 1.25
#: a key holding more than this fraction of a uniform shard share is hot.
HOT_KEY_FRACTION = 0.5
#: at most this many keys are split (the histogram head; the tail is
#: handled by the range balancing).
MAX_HOT_KEYS = 8
#: headroom multiplier on the sampled p-max destination load when deriving
#: the default capacity envelope (sampling error must not overflow it).
CAPACITY_SLACK = 1.5
#: per-range load cap (x the uniform share) the boundary cuts balance to —
#: within it, the cuts minimize the WIDEST range span instead, because the
#: phase-B table width is static at max-span (a sparse tail range would
#: otherwise inflate every shard's dense table).
BOUNDARY_LOAD_SLACK = 1.25

#: monoids whose dense reduction is order-insensitive in both the
#: collective (psum/pmax/...) and host (``dense_reduce``) merge paths —
#: the exactness envelope of hot-key splitting.
_COMMUTATIVE_MONOIDS = frozenset({"add", "max", "min", "and", "or", "mul"})

#: module-level counters (``plan_cache.stats_snapshot`` style): how many
#: histogram probes ran vs how many resolutions were served from cache.
SKEW_STATS = {"samples": 0, "cache_hits": 0, "resolves": 0}

#: in-process memo of resolved decisions, keyed by content
#: (app signature + shard count + sampled item bytes).
_MEMO: dict[str, dict] = {}


def stats_snapshot() -> dict:
    return dict(SKEW_STATS)


def clear_memo() -> None:
    _MEMO.clear()


# ---------------------------------------------------------------------------
# The options record (ExecutionOptions.shuffle)
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class ShuffleOptions:
    """The unified shuffle option surface (``ExecutionOptions.shuffle``).

    The first block is user intent; the second is the *resolved* planning
    state filled in by :func:`resolve_shuffle_options` (or passed
    explicitly) — keeping it on the frozen record is what makes the
    plan-cache key digest the full decision for free (``repr``)."""

    #: per-destination send capacity; None derives it (from the sampled
    #: p-max load when a skew plan exists, else the legacy 2x uniform).
    capacity: int | None = None
    #: raise on shuffle overflow instead of warning.
    strict: bool = False
    #: "auto" samples a key histogram at lower() time and replans the
    #: sort/reduce all-to-all; "off" keeps the fixed-width ranges.
    skew: str = "off"
    #: fraction of items the histogram probe maps (clamped by
    #: SAMPLE_PAIR_CAP pairs).
    sample_fraction: float = 0.25
    #: max destination shards one hot key may be split over (>=2 enables
    #: splitting; the monoid-merge gate still applies).
    hot_key_split_max: int = 4
    #: shuffle wire codec ("raw" | "delta" | "packed") — see
    #: ``distributed/wire.py``.  "delta" is lossless (bit-packed key
    #: residuals); "packed" additionally narrows values to int8 and is an
    #: explicit opt-in because it can change bits.
    wire: str = "raw"
    # -- resolved planning state -------------------------------------------
    #: S+1 ascending key cuts (boundaries[j] <= k < boundaries[j+1] ->
    #: shard j); None means fixed-width legacy ranges.
    boundaries: tuple[int, ...] | None = None
    hot_keys: tuple[int, ...] = ()
    hot_ways: tuple[int, ...] = ()
    #: fixed-width imbalance factor the sample measured (max range load /
    #: uniform share).
    imbalance: float | None = None
    #: largest destination load fraction under the derived plan — the
    #: default capacity envelope derives from it.
    max_dest_frac: float | None = None
    #: provenance: "sample" | "cache" | "file-cache" | "explicit".
    source: str | None = None

    def __post_init__(self):
        if self.skew not in ("auto", "off"):
            raise ValueError(f"ShuffleOptions.skew must be 'auto' or 'off', "
                             f"got {self.skew!r}")
        from repro.distributed import wire as wirelib

        if self.wire not in wirelib.CODECS:
            raise ValueError(
                f"ShuffleOptions.wire must be one of {wirelib.CODECS}, "
                f"got {self.wire!r}")
        if self.boundaries is not None:
            object.__setattr__(self, "boundaries",
                               tuple(int(b) for b in self.boundaries))
        object.__setattr__(self, "hot_keys",
                           tuple(int(k) for k in self.hot_keys))
        object.__setattr__(self, "hot_ways",
                           tuple(int(w) for w in self.hot_ways))
        if len(self.hot_keys) != len(self.hot_ways):
            raise ValueError("hot_keys and hot_ways must pair up")


@dataclasses.dataclass(frozen=True)
class SkewProfile:
    """What the histogram probe saw — ``explain()`` provenance."""

    n_sampled_pairs: int
    imbalance: float
    #: (key, sampled count) of the heaviest keys, descending.
    top_keys: tuple[tuple[int, int], ...]
    source: str

    def describe(self) -> tuple[str, ...]:
        top = ", ".join(f"{k}:{c}" for k, c in self.top_keys)
        return (
            f"histogram: {self.n_sampled_pairs} sampled pairs "
            f"({self.source}); fixed-width imbalance "
            f"{self.imbalance:.2f}x; heavy hitters [{top}]",
        )


# ---------------------------------------------------------------------------
# The engine-facing resolved plan
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class ShufflePlan:
    """Resolved boundary/hot-split plan the engine routes by.

    Frozen + tuple-valued so it hashes into jit closures and ``repr``s
    into cache keys.  ``width`` is the static per-shard range span (the
    shard_map out-width must be uniform); narrow ranges pad with
    zero-count rows exactly like the legacy ``ceil(K/S)`` padding."""

    key_space: int
    num_shards: int
    boundaries: tuple[int, ...]
    hot_keys: tuple[int, ...] = ()
    hot_ways: tuple[int, ...] = ()
    imbalance: float | None = None
    max_dest_frac: float | None = None

    def __post_init__(self):
        b, S, K = self.boundaries, self.num_shards, self.key_space
        if len(b) != S + 1:
            raise ValueError(f"need {S + 1} boundaries for {S} shards, "
                             f"got {len(b)}")
        if b[0] != 0 or b[-1] != K:
            raise ValueError(f"boundaries must span [0, {K}], got "
                             f"[{b[0]}, {b[-1]}]")
        if any(b[i + 1] <= b[i] for i in range(S)):
            raise ValueError("boundaries must be strictly increasing")
        for k, w in zip(self.hot_keys, self.hot_ways):
            if not 0 <= k < K:
                raise ValueError(f"hot key {k} outside [0, {K})")
            if w < 2:
                raise ValueError(f"hot key {k} split {w} ways (<2)")
        if len(self.hot_keys) != len(set(self.hot_keys)):
            raise ValueError("duplicate hot keys")

    @property
    def width(self) -> int:
        """Static per-shard range width: the widest boundary span."""
        b = self.boundaries
        return max(b[i + 1] - b[i] for i in range(self.num_shards))

    @property
    def epoch(self) -> int:
        """Content fingerprint of the boundary/hot layout — stamped into
        the resilient driver's checkpointable wire format so a partial
        checkpointed under different boundaries is never merged."""
        return zlib.crc32(repr((self.boundaries, self.hot_keys,
                                self.hot_ways)).encode())

    def hot_owner(self, key: int) -> int:
        """Range owner of a hot key (the shard whose boundary span holds
        it) — the split destinations start there, and the merged hot row
        lands back in the owner's output range."""
        return bisect.bisect_right(self.boundaries, key) - 1

    def hot_dests(self, i: int) -> tuple[int, ...]:
        owner = self.hot_owner(self.hot_keys[i])
        return tuple((owner + m) % self.num_shards
                     for m in range(self.hot_ways[i]))

    def capacity_for(self, n_pairs: int) -> int:
        """Default per-destination send capacity: sampled p-max
        destination load + :data:`CAPACITY_SLACK` headroom (the bugfix
        over the uniform ``2N/S`` assumption, which a skewed
        distribution overflows).  The legacy ``2N/S`` envelope stays the
        FLOOR: the sample sees aggregate loads, not per-source-shard
        variance, so the derived envelope must only ever widen."""
        from repro.core import engine as eng

        S = self.num_shards
        legacy = eng.shuffle_bucket_capacity(n_pairs, S)
        if self.max_dest_frac is None:
            # boundaries and hot splits given by hand, nothing sampled: a
            # hot key may carry every pair, and the round-robin hands each
            # of its destinations at most ceil(n / ways) of them on top of
            # the range's own share
            hot = sum(-(-n_pairs // w) for w in self.hot_ways)
            return min(n_pairs, legacy + hot) if hot else legacy
        frac = min(1.0, float(self.max_dest_frac))
        cap = int(np.ceil(n_pairs * frac * CAPACITY_SLACK))
        return max(min(n_pairs, max(cap, 8)), legacy)

    def describe(self) -> tuple[str, ...]:
        b = self.boundaries
        spans = [b[i + 1] - b[i] for i in range(self.num_shards)]
        lines = [
            f"boundaries: {self.num_shards} ranges over K={self.key_space}"
            f" width={self.width} (spans {min(spans)}..{max(spans)})"
            + (f" imbalance={self.imbalance:.2f}x"
               if self.imbalance is not None else "")
            + (f" p-max dest {self.max_dest_frac:.3f}"
               if self.max_dest_frac is not None else "")]
        if self.hot_keys:
            parts = ", ".join(
                f"{k}x{w}@{self.hot_dests(i)}"
                for i, (k, w) in enumerate(zip(self.hot_keys,
                                               self.hot_ways)))
            lines.append(f"hot keys split: {parts} "
                         f"(partial-aggregate recombine in phase B)")
        return tuple(lines)


def hot_split_ok(flow: str, spec, value_aval) -> bool:
    """Hot-key splitting is exact only when every holder leaf merges with
    a commutative dense monoid: the split destinations' partials recombine
    through ``merge_tables_collective``/``_merge_tables_host``, whose
    reductions must be order-insensitive AND defined for every leaf (the
    generic ``spec.merge``/reapply paths see per-key value *lists*, which
    a split would reorder)."""
    if flow != "sort" or spec is None:
        return False
    if spec.merge is None or spec.monoids is None:
        return False
    # memoized on the (frozen) spec: holder_avals is an eval_shape trace,
    # and this gate sits on the staged path's per-lower() hot loop
    sig = str(jax.tree.map(lambda a: (tuple(a.shape), str(a.dtype)),
                           value_aval))
    tag = f"_hot_split_ok_{sig}"
    cached = spec.__dict__.get(tag)
    if cached is None:
        leaves = jax.tree.leaves(spec.holder_avals(value_aval))
        cached = (len(spec.monoids) == len(leaves)
                  and all(m.name in _COMMUTATIVE_MONOIDS
                          for m in spec.monoids))
        object.__setattr__(spec, tag, cached)
    return cached


# ---------------------------------------------------------------------------
# Sampling + derivation
# ---------------------------------------------------------------------------


def _sample_indices(n_items: int, sample_fraction: float,
                    emit_capacity: int) -> np.ndarray:
    """Deterministic strided subsample of the item axis, pair-capped.

    Inputs small enough to fit the pair cap are histogrammed EXACTLY —
    fractional sampling of a tiny input is all noise and no savings, and
    a noisy histogram on genuinely uniform keys would defeat the identity
    snap (and with it the bitwise-legacy parity guarantee)."""
    cap_items = max(1, SAMPLE_PAIR_CAP // max(emit_capacity, 1))
    want = int(np.ceil(n_items * max(min(sample_fraction, 1.0), 0.0)))
    want = max(want, min(n_items, cap_items))
    want = max(1, min(want, cap_items))
    stride = max(1, n_items // want)
    return np.arange(0, n_items, stride)[:want]


def sample_key_histogram(app, items, *,
                         sample_fraction: float = 0.25) -> np.ndarray:
    """Map a strided item subsample eagerly and histogram the valid keys.

    Reuses the engine's ``map_phase`` (the autotune micro-probe posture:
    tiny, eager, host-side) — the histogram is over EMITTED keys, i.e. the
    distribution the all-to-all actually routes."""
    from repro.core import engine as eng

    leaves = jax.tree.leaves(items)
    n = int(leaves[0].shape[0])
    idx = _sample_indices(n, sample_fraction,
                          int(getattr(app, "emit_capacity", 16)))
    # the subsample is cut on the host, as the memo key cuts it: items
    # sharded over a mesh cannot be gathered by index on the device
    sub = jax.tree.map(lambda a: np.asarray(a)[idx], items)
    stream = eng.map_phase(app, sub)
    keys = np.asarray(stream.keys)
    valid = np.asarray(stream.valid)
    SKEW_STATS["samples"] += 1
    return np.bincount(keys[valid], minlength=app.key_space
                       ).astype(np.int64)


@dataclasses.dataclass(frozen=True)
class SkewDecision:
    """Raw derivation output (pre-``ShuffleOptions`` packaging)."""

    boundaries: tuple[int, ...] | None
    hot_keys: tuple[int, ...]
    hot_ways: tuple[int, ...]
    imbalance: float
    max_dest_frac: float | None
    top_keys: tuple[tuple[int, int], ...]
    n_sampled_pairs: int


def _balanced_cuts(residual: np.ndarray, K: int, S: int,
                   rtotal: int, n_pairs: int | None = None) -> list[int]:
    """S contiguous ranges covering [0, K): cap each range's load at
    :data:`BOUNDARY_LOAD_SLACK` x the uniform share, and under that cap
    MINIMIZE the widest span (binary search) — the engine's phase-B dense
    tables are statically sized at max-span on EVERY shard, so one sparse
    wide tail range taxes the whole mesh.

    Tightening the load cap narrows the ranges around the histogram head
    and widens the tail spans; relaxing it does the opposite but inflates
    the p-max capacity envelope every receive buffer is sized to.  Which
    side wins depends on the workload: with ``n_pairs`` known, the slack
    candidates are scored by the estimated phase-B row count (S receive
    buckets of the p-max envelope + one static-width table) and the
    cheapest wins; without it, the cap is traded up just until the widest
    span meets the ~1.25x span budget.
    """
    cum = np.cumsum(residual)
    min_span = -(-K // S)
    span_budget = min_span + min_span // 4

    def cuts_for(load_cap: float, span_cap: int) -> list[int] | None:
        bounds = [0]
        for _ in range(S):
            start = bounds[-1]
            if start >= K:
                break
            base = float(cum[start - 1]) if start else 0.0
            b = int(np.searchsorted(cum, base + load_cap, side="right"))
            b = max(start + 1, min(b, start + span_cap, K))
            bounds.append(b)
        return bounds if bounds[-1] == K else None

    def min_span_cuts(load_cap: float) -> list[int] | None:
        if cuts_for(load_cap, K) is None:
            # infeasible for S CONTIGUOUS ranges (the greedy stops just
            # short of a heavy key S times over)
            return None
        lo, hi = min_span, K
        while lo < hi:
            mid = (lo + hi) // 2
            if cuts_for(load_cap, mid) is not None:
                hi = mid
            else:
                lo = mid + 1
        return cuts_for(load_cap, lo)

    candidates = []
    for slack in (BOUNDARY_LOAD_SLACK, 1.5, 2.0, 3.0, 4.0, 8.0, float(S)):
        # a single key's mass is indivisible across contiguous cuts, so
        # the cap can never sit below the heaviest residual key
        cap = max(slack * rtotal / S, float(residual.max()))
        got = min_span_cuts(cap)
        if got is not None:
            candidates.append(got)
    if not candidates:  # slack >= S is one range holding all: feasible
        candidates = [min_span_cuts(float(rtotal) + 1.0)]

    if n_pairs is not None:
        def phase_b_rows(b) -> float:
            width = int(max(np.diff(b)))
            loads = np.add.reduceat(residual, np.asarray(b[:-1]))
            frac = float(loads.max()) / max(rtotal, 1)
            envelope = (n_pairs / S) * frac * CAPACITY_SLACK
            return S * envelope + width

        bounds = min(candidates, key=phase_b_rows)
    else:
        bounds = candidates[-1]
        for got in candidates:
            if max(np.diff(got)) <= span_budget:
                bounds = got
                break
    # the greedy may cover K in fewer than S ranges: split the widest
    # spans (shrinking the static width further) until there are exactly S
    while len(bounds) - 1 < S:
        spans = np.diff(bounds)
        i = int(spans.argmax())
        bounds.insert(i + 1, bounds[i] + int(spans[i]) // 2)
    return bounds


def derive(hist: np.ndarray, num_shards: int, *,
           hot_key_split_max: int = 4,
           mergeable: bool = False,
           n_pairs: int | None = None) -> SkewDecision:
    """Derive balanced boundaries + hot-key splits from a key histogram.

    Pure host-side numpy over the (sample-sized) histogram; deterministic.
    ``n_pairs`` (the run's total emitted pair count, when known) lets the
    cut selection score the span-vs-load trade by estimated phase-B rows.
    """
    hist = np.asarray(hist, np.int64)
    K = int(hist.shape[0])
    S = int(num_shards)
    total = int(hist.sum())
    order = np.argsort(hist)[::-1]
    top = tuple((int(k), int(hist[k])) for k in order[:5] if hist[k] > 0)

    def identity(imb: float) -> SkewDecision:
        return SkewDecision(None, (), (), imb, None, top, total)

    if total == 0 or S <= 1 or K < S:
        return identity(1.0)

    uniform = total / S
    # fixed-width range loads (the legacy k // ceil(K/S) layout)
    K_local = -(-K // S)
    fixed_loads = np.add.reduceat(hist, np.arange(0, K, K_local))
    imbalance = float(fixed_loads.max() / uniform)
    if imbalance <= SNAP_IMBALANCE:
        return identity(imbalance)

    # hot keys: more than HOT_KEY_FRACTION of a uniform share, head-capped
    hot_keys: list[int] = []
    hot_ways: list[int] = []
    if mergeable and hot_key_split_max >= 2 and S >= 2:
        thresh = HOT_KEY_FRACTION * uniform
        for k in order[:MAX_HOT_KEYS]:
            if hist[k] > thresh:
                hot_keys.append(int(k))
                hot_ways.append(int(min(
                    hot_key_split_max, S,
                    max(2, int(np.ceil(hist[k] / max(thresh, 1.0)))))))
    residual = hist.copy()
    residual[hot_keys] = 0
    rtotal = int(residual.sum())

    bounds = _balanced_cuts(residual, K, S, rtotal, n_pairs=n_pairs)

    # p-max destination load fraction under the derived plan: residual
    # range loads + each hot key's mass spread over its destinations
    starts = np.asarray(bounds[:-1])
    loads = np.add.reduceat(residual, starts).astype(np.float64)
    # np.add.reduceat repeats a slice when consecutive starts collide —
    # cannot happen here (strictly increasing), but an empty final range
    # can't either (bounds end at K)
    for i, (k, w) in enumerate(zip(hot_keys, hot_ways)):
        owner = bisect.bisect_right(bounds, k) - 1
        share = hist[k] / w
        for m in range(w):
            loads[(owner + m) % S] += share
    max_dest_frac = float(loads.max() / total)
    return SkewDecision(tuple(int(b) for b in bounds), tuple(hot_keys),
                        tuple(hot_ways), imbalance, max_dest_frac, top,
                        total)


# ---------------------------------------------------------------------------
# Resolution (lower()-time): options -> resolved options (+ profile)
# ---------------------------------------------------------------------------


def _resolve_memo_key(app, num_shards: int, options: ShuffleOptions,
                      items, *, mergeable: bool) -> str:
    """Content key for the resolution memo: app signature (autotune cache
    key style) + shard count + derivation gates + the BYTES of the strided
    item subsample — hashed before any mapping, so a warm hit skips the
    probe entirely.  ``mergeable`` is part of the key because it changes
    the derivation itself: a hot-split decision's boundaries AND capacity
    envelope assume the split spreads the head key's mass."""
    aval = app.value_aval
    head = "|".join([
        "skew", type(app).__name__, f"K={app.key_space}",
        f"cap={app.emit_capacity}",
        f"v={jnp.dtype(aval.dtype).name}{tuple(aval.shape)}",
        f"S={num_shards}", f"frac={options.sample_fraction}",
        f"split={options.hot_key_split_max}",
        f"merge={int(mergeable)}",
    ])
    h = hashlib.sha256(head.encode())
    leaves = jax.tree.leaves(items)
    n = int(leaves[0].shape[0])
    # n feeds the derivation's phase-B row scoring, not just the sample
    h.update(f"n={n}".encode())
    idx = _sample_indices(n, options.sample_fraction,
                          int(getattr(app, "emit_capacity", 16)))
    h.update(np.asarray(idx).tobytes())
    for leaf in leaves:
        h.update(np.ascontiguousarray(np.asarray(leaf)[idx]).tobytes())
    from repro.core import autotune as at

    return f"{at.SKEW_KEY_PREFIX}{h.hexdigest()[:16]}"


def _decision_entry(d: SkewDecision) -> dict:
    return {
        "boundaries": list(d.boundaries) if d.boundaries is not None
        else None,
        "hot_keys": list(d.hot_keys), "hot_ways": list(d.hot_ways),
        "imbalance": d.imbalance, "max_dest_frac": d.max_dest_frac,
        "top_keys": [list(t) for t in d.top_keys],
        "n_sampled_pairs": d.n_sampled_pairs,
    }


def _entry_decision(e: dict) -> SkewDecision:
    return SkewDecision(
        tuple(e["boundaries"]) if e.get("boundaries") is not None else None,
        tuple(e.get("hot_keys", ())), tuple(e.get("hot_ways", ())),
        float(e.get("imbalance", 1.0)), e.get("max_dest_frac"),
        tuple((int(k), int(c)) for k, c in e.get("top_keys", ())),
        int(e.get("n_sampled_pairs", 0)))


def resolve_shuffle_options(app, plan, items, *, num_shards: int,
                            options: ShuffleOptions | None
                            ) -> tuple[ShuffleOptions,
                                       SkewProfile | None]:
    """Fill a ``ShuffleOptions`` record's planning state from the data.

    Called at ``MapReduce.lower()`` time — the one stage with concrete
    items in hand.  Explicit boundaries pass through untouched; otherwise
    ``skew="auto"`` on a multi-shard sort/reduce plan samples (or recalls)
    the key histogram and bakes the derived decision into the returned
    frozen record, which the plan-cache key then digests."""
    opts = options if options is not None else ShuffleOptions()
    if opts.boundaries is not None:
        src = opts.source or "explicit"
        return (dataclasses.replace(opts, source=src),
                SkewProfile(0, opts.imbalance or 0.0, (), src))
    if (opts.skew != "auto" or num_shards <= 1
            or plan.flow not in ("sort", "reduce")):
        return opts, None

    mergeable = (opts.hot_key_split_max >= 2
                 and hot_split_ok(plan.flow, plan.spec, app.value_aval))
    key = _resolve_memo_key(app, num_shards, opts, items,
                            mergeable=mergeable)
    decision = None
    source = "sample"
    if key in _MEMO:
        decision = _entry_decision(_MEMO[key])
        source = "cache"
        SKEW_STATS["cache_hits"] += 1
    else:
        from repro.core import autotune as at

        path = at.tune_cache_path()
        if path is not None:
            e = at.load_tune_cache(path).get(key)
            if isinstance(e, dict):
                decision = _entry_decision(e)
                source = "file-cache"
                SKEW_STATS["cache_hits"] += 1
        if decision is None:
            hist = sample_key_histogram(
                app, items, sample_fraction=opts.sample_fraction)
            n_items = int(jax.tree.leaves(items)[0].shape[0])
            decision = derive(
                hist, num_shards,
                hot_key_split_max=opts.hot_key_split_max,
                mergeable=mergeable,
                n_pairs=n_items * int(getattr(app, "emit_capacity", 1)))
        _MEMO[key] = _decision_entry(decision)
        if path is not None and source == "sample":
            at.store_tune_entry(path, key, _MEMO[key])
    SKEW_STATS["resolves"] += 1

    profile = SkewProfile(decision.n_sampled_pairs, decision.imbalance,
                          decision.top_keys, source)
    resolved = dataclasses.replace(
        opts, boundaries=decision.boundaries,
        hot_keys=decision.hot_keys if mergeable else (),
        hot_ways=decision.hot_ways if mergeable else (),
        imbalance=decision.imbalance,
        max_dest_frac=decision.max_dest_frac, source=source)
    return resolved, profile


def plan_from_options(key_space: int, num_shards: int,
                      options: ShuffleOptions | None, *,
                      flow: str | None = None, spec=None,
                      value_aval=None) -> ShufflePlan | None:
    """Build the engine-facing :class:`ShufflePlan` from resolved options.

    ``None`` (no boundaries) keeps the engine on the bitwise-legacy
    fixed-width path.  Hot keys on a plan whose flow/combiner cannot
    recombine split partials exactly are a hard error — never a silent
    wrong answer."""
    if options is None or options.boundaries is None:
        return None
    if options.hot_keys and flow is not None:
        if not hot_split_ok(flow, spec, value_aval):
            raise ValueError(
                f"hot-key splitting needs the sort flow with a fully "
                f"commutative-monoid combiner (flow={flow!r}); drop "
                f"hot_keys from ShuffleOptions or let skew='auto' gate it")
    return ShufflePlan(
        key_space=key_space, num_shards=num_shards,
        boundaries=options.boundaries, hot_keys=options.hot_keys,
        hot_ways=options.hot_ways, imbalance=options.imbalance,
        max_dest_frac=options.max_dest_frac)
