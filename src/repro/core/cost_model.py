"""Roofline + compute cost model for execution-flow selection.

The paper's optimizer flips ONE flag from MapReduce semantics alone; the
follow-up literature (Manimal/Jahani et al., Casper) shows the real win is
*selecting among semantically equivalent plans by cost*.  This module gives
the planner that cost function: it extends the analytic HBM-bytes models in
``roofline.analysis`` with the COMPUTE terms that actually separate the
flows —

* stream  — the scatter-free one-hot fold burns ``O(N·K)`` masked
  compare/accumulate work (key-blocking tiles it, the total is unchanged);
* sort    — the radix-bucketed segment reduce pays ``O(N·log N)`` for the
  partition plus ``O(N + K)`` for the segmented fold and table pass;
* combine — the legacy single-shot flow: the fused one-hot contraction
  while the pair count stays in the fused regime, else the exact scatter,
  which XLA:CPU serializes per pair;
* reduce  — the paper's baseline: sort + per-pair grouping + the
  ``O(K·Lmax)`` padded window gather.

Two backend profiles translate the terms into seconds:

* ``cpu`` — per-term throughput coefficients measured on XLA:CPU in this
  container (single core; the serialized scatter and the strength-reduced
  one-hot both get their measured constants, which is what makes the
  stream/sort crossover land where ``bench_flow_sweep`` measures it);
* ``tpu`` — roofline: ``max(bytes / hbm_bw, flops / peak_flops)`` with
  the chip's published peaks (``roofline.peaks``, keyed by
  ``device_kind``) and the one-hot fold priced at MXU rates (the
  crossover moves far right: the MXU makes O(N·K) cheap until K is huge
  — the co-design point of the paper).

``choose_flow`` ranks the candidate flows for a workload; the planner
records the full report on the plan so ``MapReduce.explain()`` can show
*why* a flow was picked (paper §3.2 step 6, made quantitative).
"""

from __future__ import annotations

import dataclasses
import math

from repro.roofline import analysis as roofline

#: XLA:CPU per-term throughput coefficients (seconds per unit), measured in
#: this container (median-of-10, jit-compiled, single core):
#:   dispatch  — per-call fixed cost of a jitted executable
#:   pair      — map emission + per-pair plumbing (mask, reshape, premap)
#:   nk        — one element of the fused one-hot compare/accumulate sweep
#:               (measured 1.4–2.5 ns/elem across K = 256..32768)
#:   sortn     — one pair through one packed-sort comparator level
#:   seg       — one pair through the segmented-aggregate + run-end pass
#:   scatter   — one serialized scatter row update (XLA:CPU scatter loop)
#:   table     — one holder-table row touch (init/merge/finalize)
#:   window    — one padded reduce-flow window element (gather + reduce)
#:   wire      — one byte through the fake-mesh all-to-all (priced at the
#:               v5e ICI link rate, 50 GB/s)
CPU_COEFF = {
    "dispatch": 60e-6,
    "pair": 3.0e-8,
    "nk": 1.8e-9,
    "sortn": 6.0e-9,
    "seg": 6.0e-8,
    "scatter": 1.0e-7,
    "table": 2.5e-9,
    "window": 4.0e-9,
    "wire": 1.0 / 50e9,
}

#: TPU compute rates: the one-hot fold runs on the MXU (priced against the
#: bf16 peak with a conservative 25% utilization for the skinny D), the
#: segment/window work on the VPU (~1e11 elem/s class), and the radix
#: bucket-scatter's per-pair dynamic VMEM stores on the scalar unit
#: (~1e8 pairs/s per partition pass) — the term that keeps the MXU one-hot
#: fold the TPU winner until K reaches the few-hundred-k range (the
#: co-design point: same semantics, different crossover per architecture).
TPU_VPU_ELEMS = 1.0e11
TPU_MXU_UTIL = 0.25
TPU_SCALAR_PAIRS = 1.0e8
RADIX_PASSES = 2


@dataclasses.dataclass(frozen=True)
class FlowCost:
    """One flow's modeled cost for a workload."""

    flow: str
    est_s: float  # modeled wall-clock (backend profile)
    model_bytes: float  # analytic HBM bytes (roofline flow model)
    terms: tuple[tuple[str, float], ...]  # named seconds contributions

    def describe(self) -> str:
        parts = " ".join(f"{k}={v * 1e6:.0f}us" for k, v in self.terms
                         if v * 1e6 >= 0.5)
        return (f"{self.flow}: est={self.est_s * 1e6:.0f}us "
                f"bytes={self.model_bytes / 1e6:.2f}MB ({parts})")


@dataclasses.dataclass(frozen=True)
class CostReport:
    """The planner's decision record: every candidate, ranked."""

    chosen: str
    n_pairs: int
    key_space: int
    backend: str
    costs: tuple[FlowCost, ...]  # sorted, cheapest first

    def cost_of(self, flow: str) -> FlowCost | None:
        for c in self.costs:
            if c.flow == flow:
                return c
        return None

    def describe(self) -> str:
        lines = [f"cost model [{self.backend}] N={self.n_pairs} "
                 f"K={self.key_space} -> {self.chosen}"]
        for c in self.costs:
            mark = "*" if c.flow == self.chosen else " "
            lines.append(f"  {mark} {c.describe()}")
        return "\n".join(lines)


def _cpu_terms(flow: str, *, n, k, d, lmax, chunk_pairs, fused_combine,
               sort_passes=1):
    c = CPU_COEFF
    logn = max(math.log2(max(min(n, chunk_pairs), 2)), 1.0)
    terms = [("dispatch", c["dispatch"]), ("map", c["pair"] * n)]
    if flow == "stream":
        # scatter-free one-hot fold: O(N·K·D) masked sweep (key blocking
        # tiles it; the total element count is invariant)
        terms.append(("onehot", c["nk"] * n * k * d))
        terms.append(("table", c["table"] * k * d))
    elif flow == "sort":
        # one packed digit sort per radix pass: past the 31-bit packed
        # regime the pure-JAX lowering pays ceil(key_bits / digit_bits)
        # passes (collector.sort_radix_passes), each n·log n
        terms.append(("sort", c["sortn"] * n * logn * max(sort_passes, 1)))
        terms.append(("segments", c["seg"] * n * d))
        terms.append(("table", c["table"] * k * d))
    elif flow == "combine":
        if fused_combine:
            terms.append(("onehot", c["nk"] * n * k * d))
        else:
            terms.append(("scatter", c["scatter"] * n * (d + 1)))
        terms.append(("table", c["table"] * k * d))
    elif flow == "reduce":
        terms.append(("sort", c["sortn"] * n * logn))
        terms.append(("group", c["scatter"] * n))  # bincount/offsets
        terms.append(("windows", c["window"] * k * lmax * d))
    else:
        raise ValueError(f"unknown flow {flow!r}")
    return terms


def _tpu_terms(flow: str, *, n, k, d, lmax, model_bytes, fused_combine,
               chip):
    mem_s = model_bytes / chip.hbm_bw
    if flow in ("stream", "combine"):
        flops = 2.0 * n * k * d  # one-hot contraction on the MXU
        comp_s = flops / (chip.flops * TPU_MXU_UTIL)
    elif flow == "sort":
        # hist + bucket-scatter: the per-pair dynamic VMEM stores run on
        # the scalar unit
        comp_s = (n * RADIX_PASSES / TPU_SCALAR_PAIRS
                  + (n * d + k * d) / TPU_VPU_ELEMS)
    else:  # reduce
        logn = max(math.log2(max(n, 2)), 1.0)
        comp_s = (n * logn + k * lmax * d) / TPU_VPU_ELEMS
    return [("memory", mem_s), ("compute", comp_s)]


def estimate_flow_cost(
    flow: str,
    *,
    n_pairs: int,
    key_space: int,
    d: int = 1,
    value_bytes: int = 4,
    holder_bytes: int | None = None,
    chunk_pairs: int | None = None,
    max_values_per_key: int | None = None,
    backend: str = "cpu",
    skew_factor: float = 1.0,
    num_shards: int = 1,
    wire: str = "raw",
    shuffle_capacity: int | None = None,
    value_dtype: str = "int32",
    device_kind: str | None = None,
) -> FlowCost:
    """Model one flow's cost for a workload (see module docstring).

    The ``tpu`` profile prices ``device_kind``'s published peaks (default:
    the running device's); a kind without a row in ``roofline.PEAKS``
    raises.

    ``skew_factor`` is the sampled key-distribution imbalance (max range
    load over the uniform share, >= 1.0, from ``core/skew.py``): the
    shuffled flows (sort/reduce) are paced by their HOTTEST shard, so
    their estimate scales by the imbalance — which is how ``flow="auto"``
    prices a skewed all-to-all against the skew-immune stream flow.  The
    table-merge flows are unaffected (their per-shard work is
    item-partitioned, not key-partitioned).

    ``num_shards > 1`` adds the network term for the shuffled flows: the
    per-shard all-to-all wire bytes under the ``wire`` codec
    (``roofline.shuffle_wire_bytes``, exact against the wire layer's
    encoded-tree accounting) over the link bandwidth — which is how
    ``flow="auto"`` and ``explain()`` price wire compression."""
    n, k = max(int(n_pairs), 1), max(int(key_space), 1)
    lmax = max_values_per_key or max(n // k, 1)
    chunk = chunk_pairs or n
    from repro.core import collector as col

    # the sort flow's passes per lowering: pure-JAX digit-sort passes on
    # the cpu profile, the one-pass partition (kernel path) on tpu
    sort_levels = 1
    if flow == "sort" and backend != "tpu":
        sort_levels = col.sort_radix_passes(max(min(n, chunk), 1), k)
    model_bytes = roofline.mapreduce_flow_bytes(
        flow, n_pairs=n, key_space=k, value_bytes=value_bytes,
        holder_bytes=holder_bytes, chunk_pairs=chunk,
        max_values_per_key=lmax,
        sort_levels=sort_levels if flow == "sort" else 1)
    # the legacy combine flow keeps the fused one-hot contraction only
    # while N is inside the fused regime or K under the legacy cutoff
    fused_combine = (n <= col.ADDITIVE_FOLD_PAIRS_FUSED
                     or k <= col.ONEHOT_MAX_KEYS)
    if backend == "cpu":
        terms = _cpu_terms(flow, n=n, k=k, d=d, lmax=lmax,
                           chunk_pairs=chunk, fused_combine=fused_combine,
                           sort_passes=sort_levels)
        est = sum(v for _, v in terms)
    elif backend == "tpu":
        terms = _tpu_terms(flow, n=n, k=k, d=d, lmax=lmax,
                           model_bytes=model_bytes,
                           chip=roofline.peaks(device_kind),
                           fused_combine=fused_combine)
        est = max(v for _, v in terms)  # overlappable roofline terms
    else:
        raise ValueError(f"unknown backend profile {backend!r}")
    S = max(int(num_shards), 1)
    if S > 1 and flow in ("sort", "reduce"):
        # the all-to-all's link traffic, under the configured wire codec —
        # added before the skew scaling so a hot destination paces the
        # wire the same way it paces the compute
        wire_s = roofline.shuffle_wire_bytes(
            wire, n_pairs=n, key_space=k, num_shards=S,
            value_bytes=value_bytes, value_dtype=value_dtype,
            capacity=shuffle_capacity) * (
                1.0 / roofline.peaks(device_kind).link_bw
                if backend == "tpu" else CPU_COEFF["wire"])
        terms = list(terms) + [("wire", wire_s)]
        est += wire_s
    sf = max(float(skew_factor), 1.0)
    if sf > 1.0 and flow in ("sort", "reduce"):
        # the all-to-all flows finish when their hottest destination
        # shard does: scale the whole estimate by the imbalance factor
        extra = est * (sf - 1.0)
        terms = list(terms) + [("skew", extra)]
        est += extra
    return FlowCost(flow=flow, est_s=est, model_bytes=model_bytes,
                    terms=tuple(terms))


def default_backend() -> str:
    """Profile for the current JAX backend ("tpu" on TPU, else "cpu")."""
    import jax

    return "tpu" if jax.default_backend() == "tpu" else "cpu"


def choose_flow(
    *,
    n_pairs: int,
    key_space: int,
    d: int = 1,
    value_bytes: int = 4,
    holder_bytes: int | None = None,
    chunk_pairs: int | None = None,
    max_values_per_key: int | None = None,
    candidates: tuple[str, ...] = ("stream", "sort"),
    backend: str | None = None,
    skew_factor: float = 1.0,
    num_shards: int = 1,
    wire: str = "raw",
    shuffle_capacity: int | None = None,
    value_dtype: str = "int32",
    device_kind: str | None = None,
) -> CostReport:
    """Rank ``candidates`` by modeled cost and pick the cheapest.

    The planner restricts ``candidates`` to the flows the derived combiner
    can actually run (e.g. no sort flow for coupled-holder scan specs —
    its sequential fallback has no edge over the stream flow's).
    """
    backend = backend or default_backend()
    costs = sorted(
        (estimate_flow_cost(f, n_pairs=n_pairs, key_space=key_space, d=d,
                            value_bytes=value_bytes,
                            holder_bytes=holder_bytes,
                            chunk_pairs=chunk_pairs,
                            max_values_per_key=max_values_per_key,
                            backend=backend, skew_factor=skew_factor,
                            num_shards=num_shards, wire=wire,
                            shuffle_capacity=shuffle_capacity,
                            value_dtype=value_dtype,
                            device_kind=device_kind)
         for f in candidates),
        key=lambda fc: fc.est_s)
    return CostReport(chosen=costs[0].flow, n_pairs=n_pairs,
                      key_space=key_space, backend=backend,
                      costs=tuple(costs))


def pipeline_overhead_s(n_stages: int, *, handoff_bytes: float = 0.0,
                        fused: bool = True,
                        backend: str | None = None) -> float:
    """Model the per-call overhead a pipeline's *structure* adds.

    A fused pipeline is one executable: one dispatch, intermediates live in
    registers/VMEM.  The unfused form pays one dispatch per stage plus the
    materialized intermediate tables crossing HBM (``handoff_bytes``, from
    ``roofline.pipeline_handoff_bytes`` summed over the DAG edges) — the
    co-design point ``Pipeline.compile`` removes.
    """
    backend = backend or default_backend()
    dispatches = 1 if fused else max(1, int(n_stages))
    secs = dispatches * CPU_COEFF["dispatch"]
    if not fused and handoff_bytes:
        bw = roofline.peaks().hbm_bw if backend == "tpu" else 2.0e10
        secs += float(handoff_bytes) / bw
    return secs
