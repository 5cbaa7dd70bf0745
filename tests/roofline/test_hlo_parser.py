"""The trip-count-aware HLO cost parser vs closed-form ground truth."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.roofline import analysis, hlo_parser
from repro.roofline.analysis import collective_stats


def test_scan_trip_count_multiplication():
    """flops of scan(matmul, length=L) must be ~L x the single matmul."""
    n, L = 256, 12

    def one(x):
        return jnp.tanh(x @ x)

    def scanned(x):
        y, _ = jax.lax.scan(lambda c, _: (one(c), None), x, None, length=L)
        return y

    x = jax.ShapeDtypeStruct((n, n), jnp.float32)
    c1 = hlo_parser.analyze_text(
        jax.jit(scanned).lower(x).compile().as_text())
    c0 = hlo_parser.analyze_text(
        jax.jit(one).lower(x).compile().as_text())
    dot_flops = 2 * n * n * n
    assert abs(c0.flops - dot_flops) / dot_flops < 0.01
    assert abs(c1.flops - L * c0.flops) / (L * c0.flops) < 0.02


def test_bytes_dus_not_full_buffer():
    """dynamic-update-slice charges the slice, not the whole buffer."""
    buf = jax.ShapeDtypeStruct((1 << 20,), jnp.float32)  # 4 MiB
    upd = jax.ShapeDtypeStruct((8,), jnp.float32)

    def f(b, u):
        return jax.lax.dynamic_update_slice(b, u, (5,))

    # donate the buffer (as the decode steps do) so the update is in place;
    # without donation XLA copies the whole buffer defensively
    c = hlo_parser.analyze_text(
        jax.jit(f, donate_argnums=(0,)).lower(buf, upd).compile().as_text())
    assert c.bytes_accessed < 1 << 16  # slice-sized, not 8 MiB


def test_peaks_keyed_by_device_kind():
    v5e = analysis.peaks("TPU v5 lite")
    assert (v5e.flops, v5e.hbm_bw, v5e.hbm_bytes) == (197e12, 819e9, 16e9)
    with pytest.raises(ValueError, match="no published peaks"):
        analysis.peaks("TPU v4")
    with pytest.raises(ValueError, match="no published peaks"):
        analysis.peaks()  # the test host's device: a CPU has no row


def test_wire_factors():
    assert hlo_parser._wire_factor("all-reduce", 4) == pytest.approx(1.5)
    assert hlo_parser._wire_factor("all-gather", 4) == pytest.approx(0.75)
    assert hlo_parser._wire_factor("all-reduce", 1) == 0.0
    assert hlo_parser._wire_factor("collective-permute", 8) == 1.0


def test_parse_module_roundtrip():
    def f(x):
        return jnp.sum(jnp.exp(x) @ x)

    x = jax.ShapeDtypeStruct((64, 64), jnp.float32)
    txt = jax.jit(f).lower(x).compile().as_text()
    comps = hlo_parser.parse_module(txt)
    assert any(c.is_entry for c in comps.values())
    ops = [op.opcode for c in comps.values() for op in c.ops]
    assert "dot" in ops
