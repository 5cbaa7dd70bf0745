"""Roofline terms from a compiled dry-run artifact.

Per (arch × shape × mesh):
  compute term    = HLO flops / peak_flops            (per chip)
  memory term     = HLO bytes accessed / hbm_bw       (per chip)
  collective term = Σ wire bytes / link_bw            (per chip)

``compiled.as_text()`` is the SPMD-partitioned module of one device, so
tensor shapes in collective ops are already per-chip; wire bytes apply the
standard algorithmic factors (ring all-reduce 2(n−1)/n, all-gather /
reduce-scatter (n−1)/n, all-to-all (n−1)/n, permute 1) with the group size n
parsed from ``replica_groups``.

Hardware model: the published per-chip peaks in :data:`PEAKS`, keyed by
``jax.Device.device_kind``.
"""

from __future__ import annotations

import dataclasses
import json
import re


@dataclasses.dataclass(frozen=True)
class DevicePeaks:
    """Published peaks of one chip."""

    flops: float  # bf16 FLOP/s
    hbm_bw: float  # HBM bytes/s
    hbm_bytes: float  # HBM capacity
    link_bw: float  # interconnect bytes/s per link


#: per-chip peaks by ``device_kind``.  "TPU v5 lite" is the TPU v5e; source:
#: Google Cloud documentation, "TPU v5e" — 197 TFLOP/s bf16, 16 GB of HBM at
#: 819 GB/s, 1,600 Gbit/s of interconnect per chip over 4 links (50 GB/s
#: each).
PEAKS = {
    "TPU v5 lite": DevicePeaks(flops=197e12, hbm_bw=819e9, hbm_bytes=16e9,
                               link_bw=50e9),
}

#: the chip the dry-run rooflines and TPU-profile defaults describe.
DRYRUN_DEVICE_KIND = "TPU v5 lite"


def peaks(device_kind: str | None = None) -> DevicePeaks:
    """Peaks of ``device_kind`` (default: the running device).  A kind not
    in :data:`PEAKS` is an error, never a v5e default."""
    if device_kind is None:
        import jax

        device_kind = jax.devices()[0].device_kind
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(
            f"no published peaks for device kind {device_kind!r}; add its "
            f"row, with its source, to roofline.analysis.PEAKS") from None


_COLL_RE = re.compile(
    r"=\s*([a-z0-9_\[\]\(\),\s]*?)\s*"
    r"(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute)"
    r"(?:-start)?\(", re.I)
_SHAPE_RE = re.compile(r"(f64|f32|bf16|f16|f8\w*|s32|u32|s16|u16|s8|u8|pred)"
                       r"\[([\d,]*)\]")
_GROUPS_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]")
_GROUPS_BRACE_RE = re.compile(r"replica_groups=\{\{([^}]*)\}")

_DTYPE_BYTES = {"f64": 8, "f32": 4, "bf16": 2, "f16": 2, "s32": 4, "u32": 4,
                "s16": 2, "u16": 2, "s8": 1, "u8": 1, "pred": 1}


def _dtype_bytes(dt: str) -> int:
    if dt.startswith("f8"):
        return 1
    return _DTYPE_BYTES.get(dt, 4)


def _line_tensor_bytes(line: str) -> int:
    """Sum of tensor bytes on the lhs of the op (covers tuple shapes)."""
    total = 0
    lhs = line.split("=", 1)[0] + "=" + line.split("=", 1)[1].split("(", 1)[0]
    for dt, dims in _SHAPE_RE.findall(lhs):
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _dtype_bytes(dt)
    return total


def _group_size(line: str, default: int) -> int:
    m = _GROUPS_RE.search(line)
    if m:
        return int(m.group(2))  # [num_groups, group_size]<=[N]
    m = _GROUPS_BRACE_RE.search(line)
    if m:
        return len([x for x in m.group(1).split(",") if x.strip() != ""])
    return default


def _wire_factor(op: str, n: int) -> float:
    if n <= 1:
        return 0.0
    if op == "all-reduce":
        return 2.0 * (n - 1) / n
    if op in ("all-gather", "reduce-scatter", "all-to-all"):
        return float(n - 1) / n
    return 1.0  # collective-permute


@dataclasses.dataclass
class Roofline:
    arch: str
    shape: str
    mesh: str
    chips: int
    flops: float  # per chip
    bytes_accessed: float  # per chip
    collective_bytes: float  # wire bytes per chip
    collective_ops: dict
    model_flops: float  # 6·N·D (global), for the usefulness ratio
    peak_memory_bytes: float
    device_kind: str = DRYRUN_DEVICE_KIND

    @property
    def compute_s(self) -> float:
        return self.flops / peaks(self.device_kind).flops

    @property
    def memory_s(self) -> float:
        return self.bytes_accessed / peaks(self.device_kind).hbm_bw

    @property
    def collective_s(self) -> float:
        return self.collective_bytes / peaks(self.device_kind).link_bw

    @property
    def dominant(self) -> str:
        terms = {"compute": self.compute_s, "memory": self.memory_s,
                 "collective": self.collective_s}
        return max(terms, key=terms.get)

    @property
    def step_s(self) -> float:
        """Roofline step time: max of the three overlappable terms."""
        return max(self.compute_s, self.memory_s, self.collective_s)

    @property
    def useful_ratio(self) -> float:
        """MODEL_FLOPS / (HLO flops × chips) — remat/redundancy waste."""
        total = self.flops * self.chips
        return self.model_flops / total if total else 0.0

    @property
    def mfu(self) -> float:
        """Model-flops utilization at the roofline step time."""
        denom = self.step_s * self.chips * peaks(self.device_kind).flops
        return self.model_flops / denom if denom else 0.0

    def to_dict(self) -> dict:
        return {
            "arch": self.arch, "shape": self.shape, "mesh": self.mesh,
            "chips": self.chips, "flops_per_chip": self.flops,
            "bytes_per_chip": self.bytes_accessed,
            "collective_bytes_per_chip": self.collective_bytes,
            "collective_ops": self.collective_ops,
            "model_flops": self.model_flops,
            "peak_memory_bytes": self.peak_memory_bytes,
            "compute_s": self.compute_s, "memory_s": self.memory_s,
            "collective_s": self.collective_s, "dominant": self.dominant,
            "step_s": self.step_s, "useful_ratio": self.useful_ratio,
            "mfu": self.mfu,
        }


def collective_stats(hlo_text: str, default_group: int) -> tuple[float, dict]:
    """(wire bytes per chip, per-op {count, bytes}) from partitioned HLO."""
    per_op: dict = {}
    total = 0.0
    for line in hlo_text.splitlines():
        m = _COLL_RE.search(line)
        if not m:
            continue
        op = m.group(2).lower()
        b = _line_tensor_bytes(line)
        n = _group_size(line, default_group)
        wire = b * _wire_factor(op, n)
        total += wire
        rec = per_op.setdefault(op, {"count": 0, "bytes": 0.0})
        rec["count"] += 1
        rec["bytes"] += wire
    return total, per_op


def analyze(compiled, *, arch: str, shape: str, mesh_name: str, chips: int,
            model_flops: float) -> Roofline:
    """Roofline terms via the trip-count-aware HLO parser.

    ``compiled.cost_analysis()`` counts while bodies once (useless under
    scan-over-layers); hlo_parser multiplies by known_trip_count.  The raw
    XLA numbers are kept in ``collective_ops['_xla_cost_analysis']`` as a
    cross-check.
    """
    from repro.roofline import hlo_parser

    cost = compiled.cost_analysis()
    mem = compiled.memory_analysis()
    text = compiled.as_text()
    hc = hlo_parser.analyze_text(text, default_group=chips)
    per_op = dict(hc.collective_ops)
    per_op["_xla_cost_analysis"] = {
        "flops_bodies_once": float(cost.get("flops", 0.0)),
        "bytes_bodies_once": float(cost.get("bytes accessed", 0.0)),
    }
    if hc.warnings:
        per_op["_warnings"] = hc.warnings[:5]
    peak = (mem.argument_size_in_bytes + mem.output_size_in_bytes +
            mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    return Roofline(arch=arch, shape=shape, mesh=mesh_name, chips=chips,
                    flops=hc.flops, bytes_accessed=hc.bytes_accessed,
                    collective_bytes=hc.collective_bytes,
                    collective_ops=per_op, model_flops=model_flops,
                    peak_memory_bytes=float(peak))


def mapreduce_flow_bytes(
    flow: str,
    *,
    n_pairs: int,
    key_space: int,
    value_bytes: int = 4,
    holder_bytes: int | None = None,
    chunk_pairs: int | None = None,
    key_block: int | None = None,
    max_values_per_key: int | None = None,
    sort_levels: int = 1,
) -> float:
    """First-order HBM-bytes model of the three collector flows (Figs 8/9).

    Complements the measured ``hlo_parser`` numbers in ``bench_memory`` with
    the analytic story; all terms assume the fused one-hot/masked lowerings
    (the pair→table fold itself stays on-chip), so each flow is charged for
    what it *materializes*:

    * reduce  — writes + re-reads the full pair stream around a sort (~3
      passes of key+value), then gathers O(K·Lmax) padded value windows.
    * combine — writes + re-reads the full pair stream once (map phase
      materializes, fold consumes), plus one table write.
    * stream  — never materializes the full stream: one pair-chunk buffer
      per scan step (written + read), plus the carried O(K) holder tables
      re-touched (read + write) once per chunk — the bytes-level form of
      the paper's "minimize data transfers before the reduce phase".
    * sort    — the radix-bucketed segment-reduce flow: each chunk's pairs
      are written + read once; the radix partition / packed sort works on
      the chunk in fast memory (the Pallas bucket-scatter keeps the
      partitioned copy VMEM-resident, never an extra HBM round-trip), and
      the carried tables are re-touched once per chunk — same O(N + K)
      bytes class as the stream flow, but O(N·log N + K) compute instead
      of the one-hot fold's O(N·K).  ``sort_levels > 1`` charges the
      pure-JAX multi-pass sort's extra per-pass key/permutation traffic
      (one int32 stream re-read + re-write per extra digit sort).
    """
    if chunk_pairs is None:  # keep the model in sync with the engine
        from repro.core.engine import (DEFAULT_CHUNK_PAIRS,
                                       DEFAULT_SORT_CHUNK_PAIRS)
        chunk_pairs = (DEFAULT_SORT_CHUNK_PAIRS if flow == "sort"
                       else DEFAULT_CHUNK_PAIRS)
    K, N = key_space, n_pairs
    pair = 4 + value_bytes  # int32 key + value
    hold = (holder_bytes if holder_bytes is not None else value_bytes) + 4
    table = K * hold  # holder tables + int32 counts
    if flow == "reduce":
        lmax = max_values_per_key or max(N // max(K, 1), 1)
        return 3.0 * N * pair + 2.0 * K * lmax * value_bytes + table
    if flow == "combine":
        return 2.0 * N * pair + table
    if flow == "stream":
        n_chunks = max(1, -(-N // max(chunk_pairs, 1)))
        chunk = min(N, chunk_pairs)
        # key-blocked fold: the [K, D] table is partitioned into
        # ceil(K / key_block) blocks and each block's fold re-reads the
        # chunk's pairs (the table itself is still touched once per chunk:
        # the blocks tile it).  key_block == None / >= K -> single block.
        n_blocks = 1
        if key_block is not None and 0 < key_block < K:
            n_blocks = -(-K // key_block)
        return (2.0 * n_chunks * chunk * pair * n_blocks
                + 2.0 * n_chunks * table)
    if flow == "sort":
        n_chunks = max(1, -(-N // max(chunk_pairs, 1)))
        # pairs in/out once per chunk; the radix partition stays in fast
        # memory (VMEM bucket-scatter / fused packed sort); the carried
        # tables are re-touched (read + write) per chunk, minus the first
        # read (identity init).  Equal to the single-chunk combine-flow
        # bytes — the sort flow's win is the compute term
        # (see core/cost_model.py).  Extra digit-sort passes each re-touch
        # the int32 key/permutation stream once.
        return (2.0 * N * pair + (2.0 * n_chunks - 1.0) * table
                + (max(sort_levels, 1) - 1) * 2.0 * N * 4.0)
    raise ValueError(f"unknown flow {flow!r}")


def mapreduce_flow_peak_bytes(
    flow: str,
    *,
    n_pairs: int,
    key_space: int,
    value_bytes: int = 4,
    holder_bytes: int | None = None,
    chunk_pairs: int | None = None,
    key_block: int | None = None,
    max_values_per_key: int | None = None,
) -> float:
    """First-order peak-residency model — the paper's actual Figs 8/9 axis
    (JVM heap pressure).  The streaming flow's peak is O(K + chunk_pairs)
    and independent of N; the legacy flows grow with the full pair stream.
    """
    if chunk_pairs is None:  # keep the model in sync with the engine
        from repro.core.engine import (DEFAULT_CHUNK_PAIRS,
                                       DEFAULT_SORT_CHUNK_PAIRS)
        chunk_pairs = (DEFAULT_SORT_CHUNK_PAIRS if flow == "sort"
                       else DEFAULT_CHUNK_PAIRS)
    K, N = key_space, n_pairs
    pair = 4 + value_bytes
    hold = (holder_bytes if holder_bytes is not None else value_bytes) + 4
    table = K * hold
    if flow == "reduce":
        lmax = max_values_per_key or max(N // max(K, 1), 1)
        return 2.0 * N * pair + K * lmax * value_bytes  # stream + sorted copy
    if flow == "combine":
        return N * pair + table
    if flow == "stream":
        del key_block  # blocking bounds the VMEM working set, not HBM peak
        return min(N, chunk_pairs) * pair + table
    if flow == "sort":
        del key_block
        # chunk buffer + its partitioned/sorted copy + the carried tables
        return 2.0 * min(N, chunk_pairs) * pair + table
    raise ValueError(f"unknown flow {flow!r}")


def stream_working_set_bytes(
    *,
    chunk_pairs: int,
    key_block: int,
    d: int = 1,
    tile_n: int = 1024,
) -> float:
    """Per-grid-step VMEM residency model of the key-blocked fold kernel
    (``kernels/fold.py``), in bytes.

    Per step the kernel keeps, double-buffered, the ``[D, key_block]``
    accumulator block and output block, the ``[D, tile_n]`` value tile
    and the ``[R, 128]`` key tile (sublanes padded to 8), plus about
    three ``[128, key_block]`` one-hot temporaries (iota, hit mask,
    one-hot).  Lanes pad to 128 and sublanes to 8; ``d`` is the
    flattened holder width (channels + the counts column)."""
    lanes = 128
    tn = min(tile_n, -(-max(chunk_pairs, 1) // lanes) * lanes)
    kb = -(-max(key_block, 1) // lanes) * lanes
    dp = -(-max(d, 1) // 8) * 8
    key_rows = max(tn // lanes, 8)
    return 4.0 * (4 * dp * kb + 2 * dp * tn + 2 * key_rows * lanes
                  + 3 * lanes * kb)


def pipeline_handoff_bytes(key_space: int, *, value_bytes: int = 4,
                           dead_value: bool = False) -> float:
    """HBM bytes of materializing one producer→consumer pipeline edge.

    An unfused pipeline ends the producer program by writing its dense
    ``[K]`` output table — (key int32, value, count int32) rows — and
    starts the consumer program by reading it back: a
    ``2 · K · row_bytes`` round-trip that exists only because the program
    boundary forces materialization.  The fused pipeline
    (``core/pipeline.py``) runs both stages in one program and elides the
    term entirely; with a dead value column
    (``StageSemantics.reads_value == False``) the unfused handoff still
    moves the value bytes — the producer cannot know its consumer — which
    is exactly the co-design gap this model quantifies."""
    row = 4 + 4 + (0 if dead_value else int(value_bytes))
    return 2.0 * float(key_space) * row


def model_flops_estimate(cfg, shape_kind: str, seq: int, batch: int,
                         n_params: int, n_active: int) -> float:
    """6·N·D train; 2·N·D per generated token for decode/prefill."""
    tokens = seq * batch
    n = n_active
    if shape_kind == "train":
        return 6.0 * n * tokens
    if shape_kind == "prefill":
        return 2.0 * n * tokens
    return 2.0 * n * batch  # decode: one token per sequence


def shuffle_wire_bytes(
    codec: str = "raw",
    *,
    n_pairs: int,
    key_space: int,
    num_shards: int,
    value_bytes: int = 4,
    value_dtype: str = "int32",
    capacity: int | None = None,
    plan=None,
) -> float:
    """Per-shard link bytes of one tiled all-to-all shuffle under a wire
    codec (``distributed/wire.py``).

    ``n_pairs`` is the GLOBAL pair count (the model splits it uniformly
    over the shards, matching the engine's data-axis partition);
    ``capacity``/``plan`` follow the engine's envelope-resolution chain.
    The encoded-tree bytes come from the wire layer's own accounting —
    ``wire.encoded_nbytes`` matches ``tree_nbytes(encode(...))`` leaf for
    leaf — times the standard all-to-all ``(S-1)/S`` factor, so the cost
    model's wire term is assertable against measured wire bytes
    (``bench_flow_sweep --wire``)."""
    import jax
    import jax.numpy as jnp

    from repro.distributed import wire as wirelib

    S = max(int(num_shards), 1)
    if S <= 1:
        return 0.0
    per = -(-max(int(n_pairs), 1) // S)
    itemsize = jnp.dtype(value_dtype).itemsize
    elems = max(1, int(value_bytes) // itemsize)
    fmt = wirelib.wire_format(
        key_space=int(key_space), num_shards=S, n_pairs=per,
        value_avals=jax.ShapeDtypeStruct((per, elems), value_dtype),
        codec=codec, capacity=capacity, plan=plan)
    return wirelib.wire_bytes_per_shard(fmt)
