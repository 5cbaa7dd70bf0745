"""Multi-device behaviour, via subprocesses with fake CPU devices (the main
test process must keep seeing ONE device)."""

from _subproc import run_with_devices


def test_distributed_engine_flows():
    """combine flow (all-reduce of O(K) tables) and reduce flow (all-to-all
    of O(N) pairs) both match ground truth on a 4-device mesh, and lower to
    exactly the expected collectives."""
    out = run_with_devices("""
        import numpy as np, re, jax, jax.numpy as jnp
        from functools import partial
        from jax.sharding import PartitionSpec as P, NamedSharding
        from repro.core import MapReduceApp, plan_execution
        from repro.core import engine as eng

        VOCAB = 48
        class WC(MapReduceApp):
            key_space = VOCAB
            value_aval = jax.ShapeDtypeStruct((), jnp.int32)
            max_values_per_key = 256
            emit_capacity = 8
            def map(self, item, emit): emit(item, jnp.ones_like(item))
            def reduce(self, key, values, count): return jnp.sum(values)

        mesh = jax.make_mesh((4,), ("data",))
        rng = np.random.default_rng(0)
        toks = jax.device_put(
            jnp.asarray(rng.integers(0, VOCAB, (64, 8)).astype(np.int32)),
            NamedSharding(mesh, P("data")))
        want = np.bincount(np.asarray(toks).reshape(-1), minlength=VOCAB)
        app = WC()
        with mesh:
            plan_c = plan_execution(app, flow="auto")
            k, v, c = eng.run_distributed(app, plan_c, toks, mesh=mesh)
            assert np.array_equal(np.asarray(v), want)
            plan_r = plan_execution(app, flow="reduce")
            k2, v2, c2 = eng.run_distributed(app, plan_r, toks, mesh=mesh)
            got = np.zeros(VOCAB, np.int64)
            for kk, vv, cc in zip(np.asarray(k2), np.asarray(v2), np.asarray(c2)):
                if kk < VOCAB and cc > 0: got[kk] = vv
            assert np.array_equal(got, want)
            t_c = jax.jit(partial(eng.run_distributed, app, plan_c, mesh=mesh)).lower(toks).compile().as_text()
            t_r = jax.jit(partial(eng.run_distributed, app, plan_r, mesh=mesh)).lower(toks).compile().as_text()
        assert "all-reduce" in t_c and "all-to-all" not in t_c
        assert "all-to-all" in t_r
        print("DIST_OK")
    """)
    assert "DIST_OK" in out


def test_distributed_sort_flow():
    """Sort flow on a 4-device mesh: the reduce-flow key-partitioned
    all-to-all (shard ranges == top-level radix buckets) feeding the local
    sort collector — same answer, key-sharded output, O(N) wire traffic."""
    out = run_with_devices("""
        import numpy as np, jax, jax.numpy as jnp
        from functools import partial
        from jax.sharding import PartitionSpec as P, NamedSharding
        from repro.core import MapReduceApp, plan_execution
        from repro.core import engine as eng

        VOCAB = 48
        class WC(MapReduceApp):
            key_space = VOCAB
            value_aval = jax.ShapeDtypeStruct((), jnp.int32)
            max_values_per_key = 256
            emit_capacity = 8
            def map(self, item, emit): emit(item, jnp.ones_like(item))
            def reduce(self, key, values, count): return jnp.sum(values)

        mesh = jax.make_mesh((4,), ("data",))
        rng = np.random.default_rng(0)
        toks = jax.device_put(
            jnp.asarray(rng.integers(0, VOCAB, (64, 8)).astype(np.int32)),
            NamedSharding(mesh, P("data")))
        want = np.bincount(np.asarray(toks).reshape(-1), minlength=VOCAB)
        app = WC()
        with mesh:
            plan_s = plan_execution(app, flow="sort")
            k, v, c = eng.run_distributed(app, plan_s, toks, mesh=mesh)
            got = np.zeros(VOCAB, np.int64)
            for kk, vv, cc in zip(np.asarray(k), np.asarray(v), np.asarray(c)):
                if kk < VOCAB and cc > 0: got[kk] = vv
            assert np.array_equal(got, want)
            txt = jax.jit(partial(eng.run_distributed, app, plan_s,
                                  mesh=mesh)).lower(toks).compile().as_text()
        assert "all-to-all" in txt and "all-reduce" not in txt
        print("DIST_SORT_OK")
    """)
    assert "DIST_SORT_OK" in out


def test_distributed_sort_flow_hierarchical_kernels():
    """Sort flow on a 4-device mesh with the kernel pipeline: the shard
    key ranges are the top-level radix digits (the all-to-all wire format
    is unchanged), and each shard sizes the buckets of its own K/S range —
    a shrunk bucket cap gives every shard 16 buckets."""
    out = run_with_devices("""
        import numpy as np, jax, jax.numpy as jnp
        from jax.sharding import PartitionSpec as P, NamedSharding
        from repro.core import MapReduceApp, plan_execution
        from repro.core import engine as eng
        from repro.kernels import ops

        ops.MAX_BUCKET_SIZE = 64   # per-shard K/S = 1024 -> 16 buckets
        VOCAB = 4096
        assert ops.auto_bucket_size(VOCAB // 4, d=2) == 64

        class WC(MapReduceApp):
            key_space = VOCAB
            value_aval = jax.ShapeDtypeStruct((), jnp.float32)
            max_values_per_key = 256
            emit_capacity = 8
            def map(self, item, emit):
                emit(item, jnp.ones_like(item, jnp.float32))
            def reduce(self, key, values, count): return jnp.sum(values)

        mesh = jax.make_mesh((4,), ("data",))
        rng = np.random.default_rng(1)
        toks = jax.device_put(
            jnp.asarray(rng.integers(0, VOCAB, (64, 8)).astype(np.int32)),
            NamedSharding(mesh, P("data")))
        want = np.bincount(np.asarray(toks).reshape(-1), minlength=VOCAB)
        app = WC()
        with mesh:
            plan_s = plan_execution(app, flow="sort")
            k, v, c = eng.run_distributed(app, plan_s, toks, mesh=mesh,
                                          use_kernels=True)
        got = np.zeros(VOCAB, np.int64)
        for kk, vv, cc in zip(np.asarray(k), np.asarray(v), np.asarray(c)):
            if kk < VOCAB and cc > 0: got[kk] = vv
        assert np.array_equal(got, want)
        print("DIST_SORT_MULTI_OK")
    """)
    assert "DIST_SORT_MULTI_OK" in out


def test_distributed_stream_per_shard_autotune():
    """run_distributed re-derives the streaming tiling from the per-shard
    item count (ROADMAP open item) instead of reusing a global tiling."""
    out = run_with_devices("""
        import numpy as np, jax, jax.numpy as jnp
        from jax.sharding import PartitionSpec as P, NamedSharding
        from repro.core import MapReduceApp, plan_execution
        from repro.core import autotune as at
        from repro.core import engine as eng

        VOCAB = 4096
        class WC(MapReduceApp):
            key_space = VOCAB
            value_aval = jax.ShapeDtypeStruct((), jnp.int32)
            max_values_per_key = 256
            emit_capacity = 8
            def map(self, item, emit): emit(item, jnp.ones_like(item))
            def reduce(self, key, values, count): return jnp.sum(values)

        app = WC()
        mesh = jax.make_mesh((4,), ("data",))
        rng = np.random.default_rng(0)
        toks = jax.device_put(
            jnp.asarray(rng.integers(0, VOCAB, (256, 8)).astype(np.int32)),
            NamedSharding(mesh, P("data")))
        want = np.bincount(np.asarray(toks).reshape(-1), minlength=VOCAB)
        with mesh:
            plan = plan_execution(app, flow="auto")
            # default (chunk_pairs=None): per-shard autotune, answer exact
            k, v, c = eng.run_distributed(app, plan, toks, mesh=mesh)
            assert np.array_equal(np.asarray(v), want)
        # the per-shard hint changes the derived tiling vs the global one
        t_global = at.autotune_stream(app, plan.spec,
                                      n_pairs_hint=256 * 8)
        t_shard = at.autotune_stream(app, plan.spec,
                                     n_pairs_hint=(256 // 4) * 8)
        assert t_shard.chunk_pairs <= t_global.chunk_pairs
        print("SHARD_TUNE_OK")
    """)
    assert "SHARD_TUNE_OK" in out


def test_shuffle_overflow_skew_regression():
    """Seed regression: ``_shuffle_pairs`` silently dropped pairs past the
    per-destination capacity ``B`` — a skewed key distribution (every pair
    on one key) returned WRONG distributed reduce/sort results with no
    signal.  The shuffle now counts the overflow, fires a
    LoweringFallbackWarning with the per-shard counts in
    ``plan.diagnostics``, and raises under ``strict_shuffle=True``; the
    resilient driver's ledger records the same counters."""
    out = run_with_devices("""
        import warnings, numpy as np, jax, jax.numpy as jnp
        from jax.sharding import PartitionSpec as P, NamedSharding
        from repro.core import MapReduceApp, plan_execution
        from repro.core import LoweringFallbackWarning
        from repro.core import engine as eng

        VOCAB = 32
        class Skew(MapReduceApp):
            key_space = VOCAB
            value_aval = jax.ShapeDtypeStruct((), jnp.int32)
            max_values_per_key = 1024
            emit_capacity = 8
            def map(self, item, emit):
                emit(jnp.zeros_like(item), jnp.ones_like(item))  # all key 0
            def reduce(self, key, values, count): return jnp.sum(values)

        mesh = jax.make_mesh((4,), ("data",))
        toks = jax.device_put(jnp.zeros((64, 8), jnp.int32),
                              NamedSharding(mesh, P("data")))
        app = Skew()
        for flow in ("reduce", "sort"):
            with mesh:
                plan = plan_execution(app, flow=flow)
                with warnings.catch_warnings(record=True) as w:
                    warnings.simplefilter("always")
                    eng.run_distributed(app, plan, toks, mesh=mesh)
                msgs = [str(x.message) for x in w
                        if issubclass(x.category, LoweringFallbackWarning)]
                # seed behavior: no warning, silently wrong counts
                assert any("overflow" in m for m in msgs), (flow, msgs)
                assert any("overflow" in d for d in plan.diagnostics)

                plan2 = plan_execution(app, flow=flow)
                try:
                    eng.run_distributed(app, plan2, toks, mesh=mesh,
                                        strict_shuffle=True)
                    raise SystemExit(f"strict did not raise for {flow}")
                except ValueError as e:
                    assert "overflow" in str(e)

                # a capacity that fits the skew keeps the answer exact and
                # quiet (the overflow counter reads zero)
                plan3 = plan_execution(app, flow=flow)
                with warnings.catch_warnings(record=True) as w3:
                    warnings.simplefilter("always")
                    k, v, c = eng.run_distributed(
                        app, plan3, toks, mesh=mesh,
                        shuffle_capacity=64 * 8,
                        strict_shuffle=True)
                assert not [x for x in w3
                            if issubclass(x.category,
                                          LoweringFallbackWarning)]
                got = {int(kk): int(vv) for kk, vv, cc in
                       zip(np.asarray(k), np.asarray(v), np.asarray(c))
                       if kk < VOCAB and cc > 0}
                assert got == {0: 64 * 8}, got

                # overflow must stay loud even when an earlier lowering
                # fallback already spent the plan's once-per-plan warning
                # latch — it signals WRONG OUTPUT, not a lowering downgrade
                plan3b = plan_execution(app, flow=flow)
                plan3b._fallback_warned = True
                with warnings.catch_warnings(record=True) as w3b:
                    warnings.simplefilter("always")
                    eng.run_distributed(app, plan3b, toks, mesh=mesh)
                assert any("overflow" in str(x.message) for x in w3b
                           if issubclass(x.category,
                                         LoweringFallbackWarning)), flow

            # the resilient driver surfaces the same counters
            plan4 = plan_execution(app, flow=flow)
            with warnings.catch_warnings(record=True) as w4:
                warnings.simplefilter("always")
                _, _, _, log = eng.run_resilient(
                    app, plan4, toks, mesh=mesh)
            assert sum(log.shuffle_overflow) > 0
            assert any("overflow" in str(x.message) for x in w4
                       if issubclass(x.category, LoweringFallbackWarning))
            print("SKEW_OK", flow)
    """)
    assert out.count("SKEW_OK") == 2


def test_elastic_reshard_8_to_4():
    """Checkpoint on an (4,2) mesh, restore resharded onto (2,2)."""
    out = run_with_devices("""
        import numpy as np, jax, jax.numpy as jnp, tempfile, os
        from repro.checkpoint import ckpt
        from repro.distributed import elastic, sharding as shd
        from repro.configs import get_config
        from repro.models.registry import get_model

        cfg = get_config("llama3-8b").reduced()
        model = get_model(cfg)
        params = model.init_params(jax.random.PRNGKey(0))

        mesh8 = jax.make_mesh((4, 2), ("data", "model"))
        sh8 = shd.param_shardings(params, mesh8)
        p8 = jax.tree.map(jax.device_put, params, sh8)
        d = tempfile.mkdtemp()
        ckpt.save(d, 5, p8)

        # "lose half the fleet": remesh over 4 devices
        mesh4 = jax.make_mesh((2, 2), ("data", "model"))
        import numpy as _np
        from jax.sharding import Mesh
        mesh4 = Mesh(_np.asarray(jax.devices()[:4]).reshape(2, 2),
                     ("data", "model"))
        restored, step = elastic.elastic_restore(d, params, mesh4)
        assert step == 5
        for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(restored)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        print("ELASTIC_OK")
    """, n=8)
    assert "ELASTIC_OK" in out


def test_compressed_psum_wire_dtype():
    """int8 compressed all-reduce moves int8 on the wire and approximates
    the exact sum."""
    out = run_with_devices("""
        import numpy as np, jax, jax.numpy as jnp
        from functools import partial
        from jax.sharding import PartitionSpec as P
        from repro.distributed.compression import compressed_psum

        mesh = jax.make_mesh((4,), ("d",))
        x = jnp.asarray(np.random.default_rng(0).standard_normal((4, 64)),
                        jnp.float32)
        f = jax.shard_map(lambda a: compressed_psum(a[0], "d"), mesh=mesh,
                          in_specs=(P("d"),), out_specs=P(),
                          check_vma=False)
        with mesh:
            got = jax.jit(f)(x)
            txt = jax.jit(f).lower(x).compile().as_text()
        want = np.asarray(x).sum(0)
        err = np.abs(np.asarray(got) - want).max()
        scale = np.abs(np.asarray(x)).max(axis=-1).sum() / 127
        assert err <= scale + 1e-5, (err, scale)
        assert "s8[" in txt and "all-gather" in txt
        print("COMPRESS_OK")
    """)
    assert "COMPRESS_OK" in out


def test_dryrun_smallmesh_train_and_decode():
    """The dry-run builder lowers + compiles on a small fake mesh (fast
    proxy for the 512-chip run, exercised fully by launch/dryrun.py)."""
    out = run_with_devices("""
        import jax
        from repro.launch.dryrun import build_cell
        from repro.launch.mesh import make_test_mesh
        mesh = make_test_mesh(2, 2)
        with mesh:
            for arch, shape in [("llama3-8b", "train_4k"),
                                ("qwen3-moe-30b-a3b", "decode_32k")]:
                fn, avals = build_cell(arch, shape, mesh, microbatches=4)
                c = fn.lower(*avals).compile()
                assert c.memory_analysis().temp_size_in_bytes > 0
                print("CELL_OK", arch, shape)
    """, n=4, timeout=560)
    assert out.count("CELL_OK") == 2
