"""Paper Fig 10: speedup of the optimized flow across workload shapes —
extended (PR 3) with the sort flow and the cost-model crossover.

The paper sweeps GC configs and finds the benchmarks with the greatest
(key, value)-pair pressure (HG: 768 keys × 1.4e9 values; WC) improve most,
while SM (4 keys × 910 values) does not.  We sweep the (key_space, pairs)
grid directly with a synthetic sum-reducer workload and report the
combine/reduce speedup surface — the same monotonic trend, parameterized.

PR 2 extended the sweep past the old one-hot VMEM envelope (K = 32768): the
autotuned streaming flow must stay on the scatter-free one-hot fold there
(key-blocked in the Pallas kernel path) with the paper's bytes ordering
``stream ≤ combine < reduce`` intact — both asserted.  The scatter fallback
is also timed A/B (``fold=scatter`` rows): on XLA:CPU the serialized
scatter wins wall-clock at large K (the one-hot path pays O(N·K) vectorized
compute) but loses the bytes/residency axis by orders of magnitude.

PR 3 adds the flow the optimizer was missing in that trade: ``flow="sort"``
(radix-bucketed segment reduce, O(N·log N + K) compute, O(N + K) bytes).
Every sweep row now times the sort flow next to the stream fold, and the
cost model's choice (``core/cost_model.py``) is ASSERTED to match the
measured winner on every row.  The K=32768 crossover rows pin the headline:
the sort flow beats the one-hot fold (and the combine/reduce flows) by
orders of magnitude of wall-clock while holding the model bytes chain
``sort ≤ combine < reduce``.  Against the serialized scatter fold the sort
flow is in the same wall-clock class on XLA:CPU (the comparator sort and
the scatter loop have near-identical per-pair constants — asserted within
a 6× class bound, ratio reported) while winning the counted-bytes axis ~25×; on TPU the
radix kernel keeps the partition VMEM-resident, which is what the cost
model's TPU profile prices (see ``flow_sweep_K32768_sort_bytes`` for the
model-vs-measured split).

PR 4 takes the sort flow past one bucket sweep: ``--big`` adds the
K=1,048,576 crossover rows where the MULTI-PASS radix sort is what keeps the
fast path — the pure-JAX lowering runs the two-pass packed radix sort
(``stable_sort_by_key(impl="radix")``; the forced single-pass two-key
comparator sort is timed A/B and loses), the kernel pipeline runs the
one-pass bucket partition (parity-asserted in interpret mode), the
cost model (extended with per-pass terms) must still pick sort for
``flow="auto"``, and the model bytes chain ``sort ≤ combine < reduce``
must hold.  The nightly CI job runs ``--crossover --big --json
BENCH_nightly.json`` and diffs against the committed nightly baseline.

PR 9 adds the skew rows (``--skew``): a Zipf(1.1) key stream driven through
the mesh-less resilient sort flow on 8 shards with
``ShuffleOptions(skew="auto")`` — the sampled histogram derives balanced
range boundaries + hot-key splits (``core/skew.py``), so the zipf row must
stay within 1.5× of the uniform row's wall-clock and raise ZERO
shuffle-overflow ``LoweringFallbackWarning``s, with bitwise parity against
the single-host oracle asserted on both rows.

``python benchmarks/bench_flow_sweep.py --crossover`` runs only the
crossover rows (the CI smoke step).
"""

from __future__ import annotations

import os
import sys
import time

# self-locating like run.py: `python benchmarks/bench_flow_sweep.py` puts
# benchmarks/ (not the repo root) on sys.path
_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for _p in (_ROOT, os.path.join(_ROOT, "src")):
    if _p not in sys.path:
        sys.path.insert(0, _p)

import warnings

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import bench_scale, row, time_fn
from repro.core import (ExecutionOptions, LoweringFallbackWarning, MapReduce,
                        MapReduceApp, ShuffleOptions)
from repro.core import engine as eng
from repro.core.plan import flow_cost_report
from repro.roofline import analysis as roofline
from repro.roofline import hlo_parser

#: the large-K config (past onehot VMEM residency) whose stream lowering,
#: bytes ordering and sort-flow crossover are asserted.
BIG_K = 32768
#: pair count of the crossover rows (tiny preset).
CROSS_N = 1024


def make_app(key_space, lmax, dtype=jnp.int32):
    class App(MapReduceApp):
        pass

    a = App()
    a.key_space = key_space
    a.value_aval = jax.ShapeDtypeStruct((), dtype)
    a.max_values_per_key = lmax
    a.emit_capacity = 8
    a.map = lambda item, emit: emit(item, jnp.ones_like(
        item, a.value_aval.dtype))
    a.reduce = lambda k, v, c: jnp.sum(v)
    return a


def _flow_bytes(mr, items) -> float:
    c = mr.lower(items).compile()
    return hlo_parser.analyze_text(c.as_text()).bytes_accessed


def _check_large_k(app, items, mr_stream):
    """PR 2 acceptance: at K >= 32768 the stream flow keeps the one-hot
    fold (no scatter fallback) and stream ≤ combine < reduce bytes hold."""
    t = mr_stream.tiling
    assert t is not None and t.mode == "additive", (
        f"large-K stream flow degraded to mode={getattr(t, 'mode', None)}")
    b = {
        "stream": _flow_bytes(mr_stream, items),
        "combine": _flow_bytes(MapReduce(app, flow="combine"), items),
        "reduce": _flow_bytes(MapReduce(app, flow="reduce"), items),
    }
    assert b["stream"] <= b["combine"] < b["reduce"], (
        f"bytes ordering violated at K={app.key_space}: {b}")
    return b


def sweep():
    rng = np.random.default_rng(0)
    print("# paper Fig 10: speedup surface over (keys × pairs) pressure")
    scale = bench_scale()
    pair_grid = sorted({1 << 10, max(1 << 10, int((1 << 14) * scale))})
    for K in (4, 256, 4096, BIG_K):
        for n_pairs in pair_grid:
            toks = rng.integers(0, K, size=(n_pairs // 8, 8)).astype(np.int32)
            lmax = int(np.bincount(toks.reshape(-1), minlength=K).max())
            lmax = max(8, 1 << int(np.ceil(np.log2(lmax + 1))))
            app = make_app(K, lmax)
            items = jnp.asarray(toks)
            mr_s = MapReduce(app)  # auto flow, no hint -> autotuned stream
            t_c = time_fn(lambda x: mr_s.run(x).counts, items, iters=5)
            t_r = time_fn(
                lambda x: MapReduce(app, flow="reduce").run(x).counts,
                items, iters=5)
            tiling = mr_s.tiling
            print(row(f"flow_sweep_K{K}_N{n_pairs}", t_c * 1e6,
                      f"speedup={t_r / t_c:.2f}x {tiling.describe()}"))

            # PR 3: sort flow A/B + cost-model agreement.  The model's
            # chosen flow (given the row's workload hint) must match the
            # measured stream/sort winner on every sweep row where the
            # measured gap is material (≥ 2× — inside that band XLA:CPU's
            # single-shot vs chunked-scan lowerings differ by more than
            # any analytic model resolves, and either choice costs < 2×).
            mr_sort = MapReduce(app, flow="sort", n_pairs_hint=n_pairs)
            t_sort = time_fn(lambda x: mr_sort.run(x).counts, items, iters=5)
            winner = "sort" if t_sort < t_c else "stream"
            # the model's verdict, from the already-derived spec (a fresh
            # MapReduce would re-pay derivation + validation per row)
            chosen = flow_cost_report(app, mr_sort.plan.spec,
                                      n_pairs).chosen
            margin = max(t_sort, t_c) / max(min(t_sort, t_c), 1e-9)
            if margin >= 2.0:
                assert chosen == winner, (
                    f"cost model chose {chosen} but measured winner at "
                    f"K={K}, N={n_pairs} is {winner} by {margin:.1f}x "
                    f"(stream={t_c * 1e6:.0f}us sort={t_sort * 1e6:.0f}us)")
                verdict = "agree=ok"
            else:
                verdict = (f"agree={'ok' if chosen == winner else 'close'}"
                           f" (margin {margin:.2f}x < 2x, not gated)")
            print(row(f"flow_sweep_K{K}_N{n_pairs}_sort", t_sort * 1e6,
                      f"stream={t_c * 1e6:.1f}us winner={winner} "
                      f"model={chosen} {verdict}"))

        # large-K: assert the one-hot path + bytes ordering, and A/B the
        # scatter fallback + key-blocked Pallas kernel on the small config
        if K == BIG_K:
            n_chk = pair_grid[0]
            toks = rng.integers(0, K, size=(n_chk // 8, 8)).astype(np.int32)
            app = make_app(K, 8)
            items = jnp.asarray(toks)
            mr_s = MapReduce(app)
            b = _check_large_k(app, items, mr_s)
            print(row(f"flow_sweep_K{K}_stream_bytes", b["stream"],
                      f"combine={b['combine']:.0f} reduce={b['reduce']:.0f} "
                      "ordering=ok"))

            spec = mr_s.plan.spec
            fold_scatter = jax.jit(lambda x: eng.run_local_stream(
                app, spec, x, chunk_pairs=mr_s.stream_chunk_pairs,
                fold_mode="scatter")[2])
            t_sc = time_fn(fold_scatter, items, iters=5)
            t_oh = time_fn(lambda x: mr_s.run(x).counts, items, iters=5)
            print(row(f"flow_sweep_K{K}_scatterAB", t_sc * 1e6,
                      f"onehot={t_oh * 1e6:.1f}us "
                      f"onehot_pays={t_oh / t_sc:.1f}x_compute_on_cpu "
                      f"bytes_win={b['reduce'] / max(b['stream'], 1):.0f}x"))

            # float holders engage the fused Pallas fold kernel, whose
            # key-block grid axis is sized against the VMEM model
            appf = make_app(K, 8, jnp.float32)
            mr_k = MapReduce(appf, use_kernels=True)
            tk = mr_k.tiling
            assert tk.mode == "additive" and tk.blocked, (
                "kernel path should key-block at K=32768")
            res_k = mr_k.run(items)
            want = np.bincount(toks.reshape(-1), minlength=K)
            np.testing.assert_array_equal(np.asarray(res_k.values), want)
            t_k = time_fn(lambda x: mr_k.run(x).counts, items, iters=3)
            print(row(f"flow_sweep_K{K}_kernel_blocked", t_k * 1e6,
                      tk.describe()))


def crossover():
    """The PR 3 headline rows: the sort flow's measured crossover at BIG_K.

    Asserted: sort beats the one-hot stream fold AND the combine/reduce
    flows wall-clock by a wide margin; the model bytes chain
    ``sort ≤ combine < reduce`` holds; the cost model picks sort; and the
    sort flow stays in the serialized scatter fold's wall-clock class
    (≤ 6× — on XLA:CPU the scatter loop's per-pair constant matches the
    comparator sort's, and the measured ratio swings 0.4×–2.4× run-to-run
    on a shared box with occasional tail spikes, so the class bound needs
    that headroom; the scatter
    meanwhile loses the counted-bytes axis ~25×, and the TPU radix kernel
    path is where the partition goes VMEM-resident).
    """
    rng = np.random.default_rng(1)
    K, N = BIG_K, CROSS_N
    toks = rng.integers(0, K, size=(N // 8, 8)).astype(np.int32)
    items = jnp.asarray(toks)
    app = make_app(K, 8, jnp.float32)

    mr_sort = MapReduce(app, flow="sort", n_pairs_hint=N)
    mr_stream = MapReduce(app, flow="stream")
    mr_reduce = MapReduce(app, flow="reduce")
    want = np.bincount(toks.reshape(-1), minlength=K)
    np.testing.assert_allclose(np.asarray(mr_sort.run(items).values), want)

    t_sort = time_fn(lambda x: mr_sort.run(x).counts, items, iters=7)
    t_oh = time_fn(lambda x: mr_stream.run(x).counts, items, iters=3)
    t_red = time_fn(lambda x: mr_reduce.run(x).counts, items, iters=3)
    spec = mr_stream.plan.spec
    fold_scatter = jax.jit(lambda x: eng.run_local_stream(
        app, spec, x, chunk_pairs=mr_stream.stream_chunk_pairs,
        fold_mode="scatter")[2])
    t_sc = time_fn(fold_scatter, items, iters=7)

    assert t_sort < t_oh, (
        f"sort flow must beat the one-hot fold at K={K}: "
        f"sort={t_sort * 1e6:.0f}us onehot={t_oh * 1e6:.0f}us")
    assert t_sort < t_red, (
        f"sort flow must beat the reduce flow at K={K}")
    # class bound, not a ratio claim: the measured ratio swings 0.4×–2.4×
    # run-to-run on a shared box with occasional tail spikes past 4×, so
    # the gate needs that headroom (median ≈ 2×)
    assert t_sort <= 6.0 * t_sc, (
        f"sort flow left the scatter fold's wall-clock class: "
        f"sort={t_sort * 1e6:.0f}us scatter={t_sc * 1e6:.0f}us")
    chosen = flow_cost_report(app, mr_sort.plan.spec, N).chosen
    assert chosen == "sort", f"cost model chose {chosen} at the crossover"

    print(row(f"flow_sweep_K{K}_crossover", t_sort * 1e6,
              f"onehot={t_oh * 1e6:.1f}us reduce={t_red * 1e6:.1f}us "
              f"scatterAB={t_sc * 1e6:.1f}us "
              f"beats_onehot={t_oh / t_sort:.0f}x "
              f"sort_vs_scatter={t_sc / t_sort:.2f}x model={chosen}"))

    # bytes: the analytic chain is asserted (kernel/fused lowerings, the
    # same assumption every flow model makes); the measured XLA:CPU number
    # is reported next to it — the pure-JAX densify pays the counted
    # scatter loop, exactly like the scatterAB row it replaces.
    value_bytes = 4
    mb = {f: roofline.mapreduce_flow_bytes(
        f, n_pairs=N, key_space=K, value_bytes=value_bytes,
        chunk_pairs=mr_sort.stream_chunk_pairs, max_values_per_key=8)
        for f in ("sort", "combine", "reduce")}
    assert mb["sort"] <= mb["combine"] < mb["reduce"], mb
    measured = _flow_bytes(mr_sort, items)
    print(row(f"flow_sweep_K{K}_sort_bytes", mb["sort"],
              f"model combine={mb['combine']:.0f} reduce={mb['reduce']:.0f} "
              f"ordering=ok measured_cpu={measured:.0f} "
              f"(pure-JAX densify pays the counted scatter loop; the radix "
              f"kernel keeps the partition VMEM-resident)"))


#: the multi-pass regime: one million keys, the ISSUE 4 acceptance point.
HUGE_K = 1 << 20
#: pairs per chunk of the headline huge-K row.
HUGE_N = 4096


def crossover_big():
    """The PR 4 headline rows: K=1M, where the multi-pass sort carries the
    flow.

    Asserted: the multi-pass sort flow beats the one-hot stream fold
    wall-clock (measured ~670× on this container — the one-hot fold pays
    the O(N·K) sweep at K=1M); the model bytes chain ``sort ≤ combine <
    reduce`` holds; ``flow="auto"`` with the workload hint picks sort via
    the extended cost model; the tiling records two packed-sort passes;
    and at the default 16k chunk the multi-pass
    radix sort beats the forced single-pass two-key comparator sort both
    sort-only (~4.5×) and flow-level (~1.3× — the O(K) table merge is
    shared).  The kernel pipeline is parity-checked in interpret mode (timing reported as info, not gated: interpret mode
    executes kernel bodies in Python).
    """
    rng = np.random.default_rng(2)
    K, N = HUGE_K, HUGE_N
    toks = rng.integers(0, K, size=(N // 8, 8)).astype(np.int32)
    items = jnp.asarray(toks)
    app = make_app(K, 8, jnp.float32)
    want = np.bincount(toks.reshape(-1), minlength=K)

    mr_sort = MapReduce(app, flow="sort", n_pairs_hint=N)
    t = mr_sort.tiling
    assert t.sort_passes == 2, (
        f"K=1M must engage the multi-pass sort: {t.describe()}")
    np.testing.assert_allclose(np.asarray(mr_sort.run(items).values), want)
    t_sort = time_fn(lambda x: mr_sort.run(x).counts, items, iters=7)

    mr_stream = MapReduce(app, flow="stream")
    t_oh = time_fn(lambda x: mr_stream.run(x).counts, items,
                   warmup=1, iters=2)
    assert t_sort * 10 < t_oh, (
        f"multi-pass sort flow must beat the one-hot fold at K={K}: "
        f"sort={t_sort * 1e6:.0f}us onehot={t_oh * 1e6:.0f}us")
    assert MapReduce(app, n_pairs_hint=N).plan.flow == "sort", (
        "flow='auto' with the hint must pick sort at K=1M")
    chosen = flow_cost_report(app, mr_sort.plan.spec, N).chosen
    assert chosen == "sort", f"cost model chose {chosen} at K=1M"
    print(row(f"flow_sweep_K{K}_crossover", t_sort * 1e6,
              f"onehot={t_oh * 1e6:.0f}us beats_onehot={t_oh / t_sort:.0f}x "
              f"model={chosen} {t.describe()}"))

    # forced single-level A/B: the two-key comparator sort the multi-pass
    # radix replaces, at the default 16k chunk where the sort term matters
    N2 = eng.DEFAULT_SORT_CHUNK_PAIRS
    toks2 = rng.integers(0, K, size=(N2 // 8, 8)).astype(np.int32)
    items2 = jnp.asarray(toks2)
    mr2 = MapReduce(app, flow="sort", n_pairs_hint=N2)
    spec = mr2.plan.spec
    t_multi = time_fn(lambda x: mr2.run(x).counts, items2, iters=7)
    single = jax.jit(lambda x: eng.run_local_sort(
        app, spec, x, chunk_pairs=mr2.stream_chunk_pairs,
        sort_impl="two_key")[2])
    t_single = time_fn(single, items2, iters=7)
    from repro.core import collector as col
    keys_only = jnp.asarray(rng.integers(0, K, N2).astype(np.int32))
    t_sr = time_fn(jax.jit(lambda x: col.stable_sort_by_key(
        x, K, impl="radix")[0]), keys_only, iters=10)
    t_st = time_fn(jax.jit(lambda x: col.stable_sort_by_key(
        x, K, impl="two_key")[0]), keys_only, iters=10)
    # sort-only is the decisive A/B (measured ~3–4.5× across runs); the
    # flow-level numbers share the dominant O(K) table merge, so that
    # ratio swings with scheduler noise (0.9×–1.4× run-to-run) — gate it
    # as a class bound only
    assert t_sr * 1.5 < t_st, (
        f"multi-pass radix sort must beat the two-key comparator sort: "
        f"radix={t_sr * 1e6:.0f}us two_key={t_st * 1e6:.0f}us")
    assert t_multi < t_single * 1.5, (
        f"multi-pass sort flow left the single-pass class: "
        f"multi={t_multi * 1e6:.0f}us single={t_single * 1e6:.0f}us")
    print(row(f"flow_sweep_K{K}_single_level_AB", t_multi * 1e6,
              f"forced_two_key={t_single * 1e6:.0f}us "
              f"flow_gain={t_single / t_multi:.2f}x "
              f"sort_only: radix={t_sr * 1e6:.0f}us "
              f"two_key={t_st * 1e6:.0f}us ({t_st / t_sr:.2f}x)"))

    # model bytes chain under the kernel-lowering assumption every flow
    # model makes (sort_levels=1: the one-pass partition stays in fast
    # memory, like the fused one-hot); the pure-JAX multi-pass pays
    # (levels-1)·2N int32 extra — reported next to the chain
    mb = {f: roofline.mapreduce_flow_bytes(
        f, n_pairs=N, key_space=K, value_bytes=4,
        chunk_pairs=mr_sort.stream_chunk_pairs, max_values_per_key=8)
        for f in ("sort", "combine", "reduce")}
    assert mb["sort"] <= mb["combine"] < mb["reduce"], mb
    mb_jax = roofline.mapreduce_flow_bytes(
        "sort", n_pairs=N, key_space=K, value_bytes=4,
        chunk_pairs=mr_sort.stream_chunk_pairs, max_values_per_key=8,
        sort_levels=t.sort_passes)
    measured = _flow_bytes(mr_sort, items)
    print(row(f"flow_sweep_K{K}_sort_bytes", mb["sort"],
              f"model combine={mb['combine']:.0f} reduce={mb['reduce']:.0f} "
              f"ordering=ok purejax_multipass={mb_jax:.0f} "
              f"measured_cpu={measured:.0f}"))

    # kernel pipeline: interpret-mode parity (info row)
    mr_k = MapReduce(app, flow="sort", use_kernels=True, n_pairs_hint=N)
    np.testing.assert_allclose(np.asarray(mr_k.run(items).values), want)
    print(row(f"flow_sweep_K{K}_kernel_hierarchy", 0.0,
              f"parity=ok {mr_k.tiling.describe()} (interpret mode, "
              f"not timed)"))


#: key space of the skew rows (big enough that zipf's heavy head and long
#: tail land in different fixed-width ranges).
SKEW_K = 8192
#: shard count the skew rows drive the mesh-less resilient path at.
SKEW_S = 8


def skew_bench():
    """The PR 9 headline rows: skew-adaptive shuffle planning.

    A Zipf(1.1) key stream is driven through the mesh-less resilient sort
    flow on 8 shards with ``ShuffleOptions(skew="auto")``: the sampled key
    histogram (``core/skew.py``) derives balanced range boundaries, splits
    the hot head keys across shards and sizes the capacity envelope to the
    sampled p-max destination load.  Gated: the zipf row stays within 1.5×
    of the uniform row's wall-clock, raises ZERO shuffle-overflow
    ``LoweringFallbackWarning``s, and both rows are bitwise-identical to
    the single-host oracle (the uniform row snaps to the identity plan, so
    it IS the legacy fixed-width arithmetic).
    """
    rng = np.random.default_rng(3)
    K, S = SKEW_K, SKEW_S
    # floor at 8k pairs: below ~1k pairs/shard the rows time host dispatch,
    # not shuffle behaviour, and the ratio gate drowns in scheduler jitter
    N = max(1 << 13, int((1 << 14) * bench_scale()))
    app = make_app(K, max(4096, N))
    opts = ExecutionOptions(num_hosts=S, num_shards=S,
                            shuffle=ShuffleOptions(skew="auto"))

    uni = rng.integers(0, K, size=(N // 8, 8)).astype(np.int32)
    zpf = (rng.zipf(1.1, size=(N // 8, 8)) % K).astype(np.int32)

    results = {}
    for name, toks in (("uniform", uni), ("zipf", zpf)):
        items = jnp.asarray(toks)
        mr = MapReduce(app, flow="sort", cache=False)
        want = np.bincount(toks.reshape(-1), minlength=K)
        with warnings.catch_warnings(record=True) as caught:
            warnings.simplefilter("always")
            res = mr.run_resilient(items, options=opts)
        bad = [w for w in caught
               if issubclass(w.category, LoweringFallbackWarning)]
        assert not bad, (
            f"skew row '{name}' raised overflow/fallback warnings: "
            f"{[str(w.message) for w in bad]}")
        np.testing.assert_array_equal(np.asarray(res.values), want)
        results[name] = (mr, items, res)

    mr_u, it_u, res_u = results["uniform"]
    mr_z, it_z, res_z = results["zipf"]

    # interleave the two rows call-by-call: machine-load drift over the
    # measurement window then hits both rows alike and cancels out of the
    # ratio, which is what the gate scores
    for _ in range(2):
        mr_u.run_resilient(it_u, options=opts)
        mr_z.run_resilient(it_z, options=opts)
    tus, tzs = [], []
    for _ in range(11):
        t0 = time.perf_counter()
        mr_u.run_resilient(it_u, options=opts)
        tus.append(time.perf_counter() - t0)
        t0 = time.perf_counter()
        mr_z.run_resilient(it_z, options=opts)
        tzs.append(time.perf_counter() - t0)
    t_u = float(np.median(tus))
    t_z = float(np.median(tzs))
    plan_lines = tuple(res_z.recovery.skew_plan)
    assert plan_lines, "zipf row must engage the skew planner"
    assert not tuple(res_u.recovery.skew_plan), (
        "uniform row must snap to the identity plan (legacy arithmetic)")
    assert t_z <= 1.5 * t_u, (
        f"zipf row left the uniform row's wall-clock class: "
        f"zipf={t_z * 1e6:.0f}us uniform={t_u * 1e6:.0f}us "
        f"({t_z / t_u:.2f}x > 1.5x)")
    print(row("flow_sweep_skew_sort_uniform", t_u * 1e6,
              f"S={S} K={K} N={N} plan=identity-snap (bitwise-legacy)"))
    print(row("flow_sweep_skew_sort_zipf", t_z * 1e6,
              f"uniform={t_u * 1e6:.1f}us ratio={t_z / t_u:.2f}x "
              f"(gate <=1.5x) overflow_warnings=0 {'; '.join(plan_lines)}"))


#: key space of the wire rows: span = K/S = 512 at 16 hosts, so the delta
#: codec's range residuals pack to 10 bits against 32-bit raw keys.
WIRE_K = 8192
#: default fake-host count of the wire rows (--hosts overrides).
WIRE_S = 16


def wire_bench(hosts: int | None = None):
    """The PR 10 headline rows: the compressed shuffle wire.

    A SORTED Zipf(1.1) key stream (each shard holds a contiguous key
    range — the worst case for per-destination bucket balance, the best
    case for a columnar wire) drives the mesh-less resilient sort flow on
    16 fake hosts with an int16-value app, raw vs delta codec.  Gated:

    * both rows bitwise-equal each other AND the single-host oracle
      (delta is lossless by construction — ``distributed/wire.py``);
    * measured wire bytes/shard under delta <= 0.6x raw (the 10-bit key
      residuals vs 32-bit keys do the work; values ride unchanged);
    * the cost model's wire term equals the MEASURED bytes exactly
      (``roofline.shuffle_wire_bytes`` and the real encoded tree are the
      same arithmetic — asserted, not modeled twice).
    """
    S = hosts or WIRE_S
    K = WIRE_K
    rng = np.random.default_rng(3)
    # same floor rationale as skew_bench: keep >=1k pairs/shard in play
    N = max(1 << 13, int((1 << 14) * bench_scale()))
    N -= N % (8 * S)
    keys = np.sort((rng.zipf(1.1, size=N) % K).astype(np.int32))
    items = jnp.asarray(keys.reshape(-1, 8))
    # sorted keys concentrate each shard's pairs on few destinations:
    # provision the full per-shard pair count so neither codec overflows
    per_pairs = (N // 8 // S) * 8

    app = make_app(K, max(4096, N), dtype=jnp.int16)
    app.map = lambda item, emit: emit(item, (item % 1000).astype(jnp.int16))
    app.reduce = lambda k, v, c: jnp.max(v)

    def opts(codec):
        return ExecutionOptions(
            num_hosts=S, num_shards=S,
            shuffle=ShuffleOptions(wire=codec, capacity=per_pairs))

    want = np.full(K, np.iinfo(np.int16).min, np.int64)
    np.maximum.at(want, keys, keys % 1000)
    cnt = np.bincount(keys, minlength=K)
    results = {}
    for codec in ("raw", "delta"):
        mr = MapReduce(app, flow="sort", cache=False)
        res = mr.run_resilient(items, options=opts(codec))
        got = np.asarray(res.values, np.int64)
        np.testing.assert_array_equal(np.asarray(res.counts), cnt)
        np.testing.assert_array_equal(got[cnt > 0], want[cnt > 0])
        results[codec] = (mr, res)
    np.testing.assert_array_equal(
        np.asarray(results["raw"][1].values),
        np.asarray(results["delta"][1].values))

    # measured wire bytes: encode shard 0's REAL pair stream through the
    # wire layer and count the tree's bytes (== encoded_nbytes, asserted)
    from repro.distributed import wire as wirelib
    stream = eng.map_phase(app, items[: items.shape[0] // S])
    bytes_shard = {}
    for codec in ("raw", "delta"):
        fmt = wirelib.wire_format(
            key_space=K, num_shards=S, n_pairs=stream.keys.shape[0],
            value_avals=stream.values, codec=codec, capacity=per_pairs)
        sk, sv, overflow = wirelib.bucketize(fmt, stream)
        assert int(overflow) == 0, f"wire row '{codec}' overflowed"
        measured = wirelib.tree_nbytes(wirelib.encode(fmt, sk, sv))
        assert measured == wirelib.encoded_nbytes(fmt)
        bytes_shard[codec] = measured * (S - 1) / S
        model = roofline.shuffle_wire_bytes(
            codec, n_pairs=stream.keys.shape[0], key_space=K, num_shards=S,
            value_bytes=2, value_dtype="int16", capacity=per_pairs)
        assert model == bytes_shard[codec], (
            f"cost-model wire bytes diverged from measured for '{codec}': "
            f"model={model} measured={bytes_shard[codec]}")
    ratio = bytes_shard["delta"] / bytes_shard["raw"]
    assert ratio <= 0.6, (
        f"delta wire bytes left the gate: {bytes_shard['delta']:.0f}B "
        f"vs raw {bytes_shard['raw']:.0f}B ({ratio:.3f}x > 0.6x)")

    # interleave raw/delta call-by-call (same drift-cancellation argument
    # as skew_bench: the ratio is what the derived column reports)
    mr_r, _ = results["raw"]
    mr_d, _ = results["delta"]
    for _ in range(2):
        mr_r.run_resilient(items, options=opts("raw"))
        mr_d.run_resilient(items, options=opts("delta"))
    trs, tds = [], []
    for _ in range(11):
        t0 = time.perf_counter()
        mr_r.run_resilient(items, options=opts("raw"))
        trs.append(time.perf_counter() - t0)
        t0 = time.perf_counter()
        mr_d.run_resilient(items, options=opts("delta"))
        tds.append(time.perf_counter() - t0)
    t_r = float(np.median(trs))
    t_d = float(np.median(tds))
    assert t_d <= 3.0 * t_r, (
        f"delta row left the raw row's wall-clock class: "
        f"delta={t_d * 1e6:.0f}us raw={t_r * 1e6:.0f}us "
        f"({t_d / t_r:.2f}x > 3x)")
    print(row(f"flow_sweep_wire_sort_raw_h{S}", t_r * 1e6,
              f"S={S} K={K} N={N} sorted-zipf codec=raw"))
    print(row(f"flow_sweep_wire_sort_delta_h{S}", t_d * 1e6,
              f"raw={t_r * 1e6:.1f}us ratio={t_d / t_r:.2f}x "
              f"(class gate <=3x) bitwise=ok"))
    print(row(f"flow_sweep_wire_bytes_delta_h{S}", bytes_shard["delta"],
              f"raw={bytes_shard['raw']:.0f}B ratio={ratio:.3f}x "
              f"(gate <=0.6x) model=exact int16-values"))


def main():
    sweep()
    crossover()
    skew_bench()
    wire_bench()


if __name__ == "__main__":
    import argparse
    import contextlib
    import io
    import json

    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--crossover", action="store_true",
                    help="run only the K=32768 sort-flow crossover rows "
                         "(the CI smoke step)")
    ap.add_argument("--big", action="store_true",
                    help="add the K=1M multi-pass crossover rows (the "
                         "nightly stress job)")
    ap.add_argument("--skew", action="store_true",
                    help="run only the skew-adaptive shuffle rows (uniform "
                         "vs Zipf(1.1) on the resilient sort flow)")
    ap.add_argument("--wire", action="store_true",
                    help="run only the compressed-wire rows (raw vs delta "
                         "codec on the sorted-Zipf resilient sort flow)")
    ap.add_argument("--hosts", type=int, default=None, metavar="S",
                    help=f"fake-host count for the --wire rows "
                         f"(default {WIRE_S})")
    ap.add_argument("--json", default=None, metavar="PATH",
                    help="write parsed rows as a BENCH_*.json artifact "
                         "(compare.py-compatible)")
    args = ap.parse_args()

    buf = io.StringIO()

    class _Tee(io.TextIOBase):
        def write(self, s):
            buf.write(s)
            return sys.__stdout__.write(s)

    print("name,us_per_call,derived")
    with contextlib.redirect_stdout(_Tee()):
        if args.crossover or args.big or args.skew or args.wire:
            if args.crossover:
                crossover()
            if args.big:
                crossover_big()
            if args.skew:
                skew_bench()
            if args.wire:
                wire_bench(hosts=args.hosts)
        else:
            main()
    if args.json:
        from benchmarks.common import parse_rows

        mode = "+".join([m for m, on in (("crossover", args.crossover),
                                         ("big", args.big),
                                         ("skew", args.skew),
                                         ("wire", args.wire)) if on]) or "full"
        with open(args.json, "w") as f:
            json.dump({"scale": bench_scale(), "preset": mode,
                       "rows": parse_rows(buf.getvalue()), "failures": []},
                      f, indent=2)
        print(f"# wrote {args.json}")
