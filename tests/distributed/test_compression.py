"""Quantized-collective building blocks (distributed/compression.py).

These are the primitives the ``packed`` wire codec borrows for float
value leaves (``wire.encode``'s per-destination int8 quantization), so
their error bounds are load-bearing for the shuffle layer too:

* ``quant_int8``/``dequant_int8``: elementwise error <= scale/2 with
  scale = max|x|/127 (hypothesis property), zero error at 0, exact on
  the +/-max elements up to rounding;
* ``fake_quant_int8`` is idempotent: re-quantizing a dequantized tensor
  is exact (the lattice points are fixed points);
* ``compressed_psum`` tracks the exact psum within the summed per-shard
  quantization bounds;
* ``ErrorFeedback`` telescopes: over T steps the TRANSMITTED total
  equals the true gradient total up to one step's quantization error,
  not T of them (the unbiased-in-the-limit argument).
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.distributed import compression as comp

jax.config.update("jax_platform_name", "cpu")


def _bound(x):
    """The per-tensor int8 quantization half-step."""
    return max(float(np.max(np.abs(x))), 1e-12) / 127.0 / 2.0


def test_quant_roundtrip_bound_simple():
    x = jnp.asarray(np.linspace(-3.0, 5.0, 101), jnp.float32)
    q, s = comp.quant_int8(x)
    assert q.dtype == jnp.int8 and s.dtype == jnp.float32
    err = np.abs(np.asarray(comp.dequant_int8(q, s)) - np.asarray(x))
    assert err.max() <= _bound(x) + 1e-7


def test_quant_zero_is_exact():
    x = jnp.zeros((8,), jnp.float32)
    q, s = comp.quant_int8(x)
    assert np.all(np.asarray(q) == 0)
    assert np.all(np.asarray(comp.dequant_int8(q, s)) == 0.0)


def test_fake_quant_idempotent():
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal(256), jnp.float32)
    once = comp.fake_quant_int8(x)
    twice = comp.fake_quant_int8(once)
    assert np.array_equal(np.asarray(once), np.asarray(twice))


try:
    from hypothesis import given, settings, strategies as st
    HAVE_HYP = True
except ImportError:  # pragma: no cover
    HAVE_HYP = False


if HAVE_HYP:
    @settings(max_examples=50, deadline=None)
    @given(
        n=st.integers(1, 64),
        scale=st.floats(1e-6, 1e6),
        seed=st.integers(0, 2 ** 16),
    )
    def test_quant_roundtrip_bound_property(n, scale, seed):
        rng = np.random.default_rng(seed)
        x = jnp.asarray(rng.standard_normal(n) * scale, jnp.float32)
        q, s = comp.quant_int8(x)
        err = np.abs(np.asarray(comp.dequant_int8(q, s)) - np.asarray(x))
        assert err.max() <= _bound(x) * (1 + 1e-5) + 1e-12

    @settings(max_examples=25, deadline=None)
    @given(
        n=st.integers(1, 32),
        steps=st.integers(1, 8),
        seed=st.integers(0, 2 ** 16),
    )
    def test_error_feedback_telescopes(n, steps, seed):
        """sum_t c_t = sum_t g_t - e_T: the residual chain cancels, so
        the transmitted total is off by ONE quantization error, however
        many steps ran."""
        rng = np.random.default_rng(seed)
        grads = {"w": jnp.zeros((n,), jnp.float32)}
        res = comp.ErrorFeedback.init(grads)
        sent = np.zeros(n, np.float64)
        true = np.zeros(n, np.float64)
        last_x = np.zeros(n, np.float64)
        for _ in range(steps):
            g = {"w": jnp.asarray(rng.standard_normal(n), jnp.float32)}
            last_x = np.asarray(g["w"], np.float64) + np.asarray(
                res["w"], np.float64)
            c, res = comp.ErrorFeedback.apply(g, res)
            sent += np.asarray(c["w"], np.float64)
            true += np.asarray(g["w"], np.float64)
        # sent == true - final residual  (float32 chain, so allow eps)
        gap = np.abs(sent + np.asarray(res["w"], np.float64) - true)
        assert gap.max() <= 1e-4 * max(1.0, np.abs(true).max())
        # and the final residual is ONE step's quantization error (of the
        # last compressed input), not an accumulation over T steps
        assert np.abs(np.asarray(res["w"])).max() \
            <= _bound(last_x) * (1 + 1e-5) + 1e-12


def test_error_feedback_single_step_residual_is_quant_error():
    rng = np.random.default_rng(3)
    g = {"w": jnp.asarray(rng.standard_normal(64), jnp.float32)}
    res = comp.ErrorFeedback.init(g)
    c, res2 = comp.ErrorFeedback.apply(g, res)
    want = np.asarray(g["w"]) - np.asarray(comp.fake_quant_int8(g["w"]))
    assert np.allclose(np.asarray(res2["w"]), want, atol=1e-7)
    assert np.abs(np.asarray(res2["w"])).max() <= _bound(g["w"]) + 1e-7


def test_compressed_psum_tracks_exact_psum():
    """shard_map all-gather path: the int8-on-the-wire sum equals the
    exact psum within the sum of per-shard quantization bounds."""
    from jax.sharding import Mesh, PartitionSpec as P

    n_dev = jax.local_device_count()
    if n_dev < 2:
        pytest.skip("needs >=2 devices (run under "
                    "XLA_FLAGS=--xla_force_host_platform_device_count)")
    mesh = Mesh(np.array(jax.devices()[:n_dev]), ("d",))
    rng = np.random.default_rng(1)
    x = jnp.asarray(rng.standard_normal((n_dev, 32)), jnp.float32)

    exact = jax.shard_map(
        lambda v: jax.lax.psum(v, "d"), mesh=mesh,
        in_specs=P("d"), out_specs=P())(x)
    # the replication check can't see through the all_gather+sum, but the
    # result IS replicated (every shard gathers the same int8+scale rows)
    approx = jax.shard_map(
        lambda v: comp.compressed_psum(v[0], "d"), mesh=mesh,
        in_specs=P("d"), out_specs=P(), check_vma=False)(x)
    bound = sum(_bound(x[i]) for i in range(n_dev))
    assert np.abs(np.asarray(approx) - np.asarray(exact)).max() \
        <= bound + 1e-6
