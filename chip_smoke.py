"""Smoke run of the MapReduce main path on a TPU.

One chip (the default): WordCount at the size of the Phoenix word_count
large input — 2^20 windows x 16 tokens (16,777,216 int32 tokens, 64 MB on
the device), vocabulary 131,072, Zipf(1.2) made from ``--seed`` — through
the normal ``MapReduce(app).run(...)`` entry points, in five phases:

1. ``flow="auto"`` (resolves to stream), pure-JAX lowering;
2. the same with ``use_kernels=True``: the Pallas fold kernel, compiled
   by Mosaic;
3. ``flow="sort"``, pure-JAX lowering;
4. ``flow="sort", use_kernels=True``: the radix partition and segment
   reduce kernels;
5. ``MapReduce(wc, streaming=True).serve(...)``: the stream ingested in
   micro-batches, then ``snapshot()``.

The kernel phases count words as float32 (the kernels carry f32 holder
tables; counts stay exact below 2^24 per word), the others as int32.

Four chips (``--chips 4``): only the multi-chip path, on a
``jax.make_mesh((4,), ("data",))`` mesh with the same input sharded over
it: ``run_distributed`` with ``flow="sort"`` (the all-to-all shuffle) and
``flow="stream"`` (the psum merge), and ``run_resilient`` with one killed
host, each host computing its shards on its own chip.

Every result must equal an ``np.bincount`` oracle on the host, values and
counts.  Any failure — no TPU, a LoweringFallbackWarning, a kernel phase
without ``tpu_custom_call`` in its executable, ``JAX_PALLAS_INTERPRET``
asking for interpreted kernels, a wrong count — exits non-zero before the
result line.  The last line of stdout is the JSON result
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
Compile and run seconds are one run's smoke timings, not benchmark
numbers.

``--windows`` and ``--vocab`` exist only to rehearse at small sizes;
``--rehearse`` also accepts the CPU backend (kernels interpreted, no
``tpu_custom_call`` check):

    JAX_PLATFORMS=cpu python chip_smoke.py --rehearse --windows 4096 --vocab 4096
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time
import warnings
from pathlib import Path

ROOT = Path(__file__).resolve().parent
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


class SmokeFailure(Exception):
    pass


def _args():
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--chips", type=int, choices=(1, 4), default=1)
    p.add_argument("--windows", type=int, default=1 << 20,
                   help="16-token windows (rehearsal only)")
    p.add_argument("--vocab", type=int, default=131072,
                   help="key space (rehearsal only)")
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--rehearse", action="store_true",
                   help="accept a non-TPU backend (CPU rehearsal)")
    return p.parse_args()


def _log(msg: str) -> None:
    print(msg, flush=True)


def main() -> dict:
    args = _args()
    interp = os.environ.get("JAX_PALLAS_INTERPRET", "").strip().lower()
    if interp not in ("", "0", "false", "no"):
        raise SmokeFailure(f"JAX_PALLAS_INTERPRET={interp!r} asks for "
                           f"interpreted kernels; the smoke compiles them")

    import jax
    import numpy as np

    from benchmarks.apps import WordCount
    from repro.compile_cache import enable_compile_cache
    from repro.core import LoweringFallbackWarning
    from repro.data import datasets

    warnings.simplefilter("error", LoweringFallbackWarning)
    devices = jax.devices()
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices)}
    _log(f"device: {device}")
    if device["platform"] != "tpu" and not args.rehearse:
        raise SmokeFailure(f"no TPU: JAX found {device['platform']}")
    if len(devices) < args.chips:
        raise SmokeFailure(f"--chips {args.chips} needs {args.chips} "
                           f"devices, JAX found {len(devices)}")
    _log(f"compile cache: {enable_compile_cache()}")

    t0 = time.perf_counter()
    toks, vocab = datasets.wordcount_data(
        np.random.default_rng(args.seed), tokens=args.windows * 16,
        vocab=args.vocab)
    windows = toks.reshape(args.windows, 16)
    oracle = np.bincount(toks, minlength=vocab)
    _log(f"data: {windows.shape[0]} windows x 16 tokens, vocab {vocab}, "
         f"zipf(1.2) seed {args.seed}, top word {oracle.max()} "
         f"({time.perf_counter() - t0:.1f}s set-up)")

    def check(name, values, counts):
        for what, got in (("values", values), ("counts", counts)):
            got = np.asarray(got)[:vocab]
            if got.shape != oracle.shape or not np.array_equal(
                    got.astype(np.int64), oracle):
                bad = int(np.sum(got.astype(np.int64) != oracle))
                raise SmokeFailure(f"{name}: {what} differ from the "
                                   f"np.bincount oracle at {bad} keys")

    def peak_bytes():
        stats = devices[0].memory_stats() or {}
        return stats.get("peak_bytes_in_use", "n/a")

    if args.chips == 4:
        _four_chips(args, windows, vocab, check, peak_bytes, WordCount)
    else:
        _one_chip(args, windows, vocab, check, peak_bytes, WordCount)
    return device


def _summary(mr) -> str:
    lines = mr.explain().splitlines()
    return " | ".join(ln for ln in lines
                      if ln.startswith(("flow:", "tiling:")))


def _one_chip(args, windows, vocab, check, peak_bytes, WordCount):
    import jax
    import jax.numpy as jnp

    from repro.core import ExecutionOptions, MapReduce

    items = jax.device_put(jnp.asarray(windows), jax.devices()[0])
    check_kernels = not args.rehearse

    def batch_phase(n, name, mr, *, kernels):
        t0 = time.perf_counter()
        compiled = mr.lower(items).compile()
        t_compile = time.perf_counter() - t0
        t0 = time.perf_counter()
        res = compiled(items)
        jax.block_until_ready((res.values, res.counts))
        t_run = time.perf_counter() - t0
        check(name, res.values, res.counts)
        line = (f"phase {n} {name}: {_summary(mr)} | smoke timing, not a "
                f"benchmark: compile {t_compile:.2f}s run {t_run:.3f}s | "
                f"peak_bytes_in_use {peak_bytes()}")
        if kernels:
            custom = "tpu_custom_call" in compiled.as_text()
            line += f" | tpu_custom_call {custom}"
            if check_kernels and not custom:
                raise SmokeFailure(f"{name}: no tpu_custom_call in the "
                                   f"executable (kernels not compiled)")
        _log(line)

    wc_i32 = WordCount(vocab)
    wc_f32 = WordCount(vocab, jnp.float32)
    batch_phase(1, "stream/auto pure-JAX", MapReduce(wc_i32, flow="auto"),
                kernels=False)
    batch_phase(2, "stream/auto kernels",
                MapReduce(wc_f32, flow="auto", use_kernels=True),
                kernels=True)
    batch_phase(3, "sort pure-JAX", MapReduce(wc_i32, flow="sort"),
                kernels=False)
    batch_phase(4, "sort kernels",
                MapReduce(wc_f32, flow="sort", use_kernels=True),
                kernels=True)

    # phase 5: the same stream through the streaming service, in 4
    # micro-batches folded with the batch run's chunk size
    mr = MapReduce(wc_i32, streaming=True)
    batch = max(windows.shape[0] // 4, 1)
    t0 = time.perf_counter()
    svc = mr.serve(batch_capacity=batch, options=ExecutionOptions(
        chunk_pairs=mr.stream_chunk_pairs),
        item_spec=jax.ShapeDtypeStruct((16,), jnp.int32))
    t_compile = time.perf_counter() - t0
    t0 = time.perf_counter()
    for lo in range(0, windows.shape[0], batch):
        svc.ingest(items[lo:lo + batch])
    snap = svc.snapshot()
    jax.block_until_ready((snap.values, snap.counts))
    t_run = time.perf_counter() - t0
    check("streaming", snap.values, snap.counts)
    _log(f"phase 5 streaming serve: {_summary(mr)} | {snap.batch_id} "
         f"micro-batches of {batch} windows | smoke timing, not a "
         f"benchmark: compile {t_compile:.2f}s ingest+snapshot "
         f"{t_run:.3f}s | peak_bytes_in_use {peak_bytes()}")


def _four_chips(args, windows, vocab, check, peak_bytes, WordCount):
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding
    from jax.sharding import PartitionSpec as P

    from repro.core import ExecutionOptions, MapReduce, ShuffleOptions
    from repro.distributed.fault import FaultInjection

    mesh = jax.make_mesh((4,), ("data",))
    items = jax.device_put(jnp.asarray(windows),
                           NamedSharding(mesh, P("data")))
    wc = WordCount(vocab)
    # Zipf keys crowd the first key range: give every destination room for
    # all of a source shard's pairs, and fail loudly if any overflow
    per_shard_pairs = windows.shape[0] // 4 * 16
    exact = ExecutionOptions(shuffle=ShuffleOptions(
        capacity=per_shard_pairs, strict=True))

    def run(n, name, fn):
        t0 = time.perf_counter()
        res = fn()
        jax.block_until_ready((res.values, res.counts))
        t = time.perf_counter() - t0
        check(name, res.values, res.counts)
        return res, (f"phase {n} {name}: smoke timing, not a benchmark: "
                     f"compile+run {t:.2f}s | peak_bytes_in_use (device 0) "
                     f"{peak_bytes()}")

    for n, flow, opts in ((1, "sort", exact), (2, "stream", None)):
        mr = MapReduce(wc, flow=flow)
        _, line = run(n, f"run_distributed {flow}",
                      lambda: mr.run_distributed(items, mesh=mesh,
                                                 options=opts))
        _log(f"{line} | {_summary(mr)}")

    mr = MapReduce(wc, flow="stream")
    res, line = run(3, "run_resilient stream, host 1 killed",
                    lambda: mr.run_resilient(items, mesh=mesh, options=(
                        ExecutionOptions(inject=FaultInjection(
                            dead_hosts=(1,))))))
    if res.recovery.recomputed != [(1, 2)]:
        raise SmokeFailure(f"run_resilient: expected shard 1 recomputed on "
                           f"host 2, got {res.recovery.recomputed}")
    # each host computes on its own chip: shard s on device s, the killed
    # host's shard on host 2's device
    devs = mesh.devices.reshape(-1)
    want = {s: devs[2 if s == 1 else s].id for s in range(4)}
    if res.recovery.devices != want:
        raise SmokeFailure(f"run_resilient: shards ran on devices "
                           f"{res.recovery.devices}, expected {want}")
    _log(f"{line} | recomputed {res.recovery.recomputed} | shard -> device "
         f"{res.recovery.devices}")


if __name__ == "__main__":
    try:
        dev = main()
    except SmokeFailure as e:
        print(f"chip_smoke FAILED: {e}", file=sys.stderr, flush=True)
        sys.exit(1)
    print(json.dumps({"ok": True, "device": dev}), flush=True)
