"""Flash-decode attention: the paper's combiner applied to KV tiles.

Decode attention for one new token is a reduction over the KV cache — and
softmax attention admits an *associative combiner* over KV tiles with holder
``(m, l, acc)`` (running max, rescaled normalizer, rescaled value-sum): the
exact shape of ``CombinerSpec`` (core/combiner.py:logsumexp_spec extended
with an accumulator).  The baseline "reduce flow" would materialize all
``[S]`` logits, softmax, then contract; the combine flow folds each KV tile
into the holder as it streams through VMEM — O(tile) live memory instead of
O(S), no second pass.  This kernel is that combine flow on TPU:

  grid = (batch, kv_heads, S_tiles)    (S innermost; holder VMEM-resident)
  per tile: logits = q·Kᵀ  (MXU) -> masked -> holder update (VPU) ->
            acc += softmax-weights · V (MXU); final tile writes acc / l.

GQA: the G = H/Hkv query heads of a KV group are processed together, so K/V
tiles are read once per group, not once per head.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30  # avoid -inf NaN propagation in f32 exp on all-masked tiles


def _kernel(kv_len_ref, q_ref, k_ref, v_ref, out_ref,
            m_ref, l_ref, acc_ref, *, tile_s: int, scale: float):
    b = pl.program_id(0)
    s = pl.program_id(2)
    n_s = pl.num_programs(2)

    @pl.when(s == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0, 0].astype(jnp.float32) * scale  # [G, D]
    k = k_ref[0, :, 0].astype(jnp.float32)  # [Ts, D]
    v = v_ref[0, :, 0].astype(jnp.float32)  # [Ts, D]

    logits = jax.lax.dot_general(  # [G, Ts] on the MXU
        q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)

    pos = s * tile_s + jax.lax.broadcasted_iota(jnp.int32, logits.shape, 1)
    logits = jnp.where(pos < kv_len_ref[b], logits, NEG_INF)

    m_prev = m_ref[...]  # [G, 1]
    m_new = jnp.maximum(m_prev, logits.max(axis=1, keepdims=True))
    alpha = jnp.exp(m_prev - m_new)  # [G, 1]
    p = jnp.exp(logits - m_new)  # [G, Ts]
    p = jnp.where(pos < kv_len_ref[b], p, 0.0)

    m_ref[...] = m_new
    l_ref[...] = l_ref[...] * alpha + p.sum(axis=1, keepdims=True)
    acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)

    @pl.when(s == n_s - 1)
    def _emit():
        out_ref[0, 0] = (acc_ref[...] /
                         jnp.maximum(l_ref[...], 1e-30)).astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("tile_s", "interpret"))
def flash_decode(
    q: jax.Array,  # [B, H, D]
    k: jax.Array,  # [B, S, Hkv, D]
    v: jax.Array,  # [B, S, Hkv, D]
    kv_len: jax.Array,  # [B] int32 valid lengths
    *,
    tile_s: int = 512,
    interpret: bool,
) -> jax.Array:
    """Single-token GQA decode attention -> [B, H, D] f32."""
    B, H, D = q.shape
    _, S, Hkv, _ = k.shape
    G = H // Hkv
    scale = 1.0 / (D ** 0.5)
    tile_s = min(tile_s, S)

    pad_s = (-S) % tile_s
    if pad_s:
        k = jnp.pad(k, ((0, 0), (0, pad_s), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad_s), (0, 0), (0, 0)))
    Sp = S + pad_s

    qg = q.reshape(B, Hkv, G, D)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(B, Hkv, Sp // tile_s),
        in_specs=[
            pl.BlockSpec((1, 1, G, D), lambda b, h, s, kvl: (b, h, 0, 0)),
            pl.BlockSpec((1, tile_s, 1, D), lambda b, h, s, kvl: (b, s, h, 0)),
            pl.BlockSpec((1, tile_s, 1, D), lambda b, h, s, kvl: (b, s, h, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, G, D), lambda b, h, s, kvl: (b, h, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((G, 1), jnp.float32),
            pltpu.VMEM((G, 1), jnp.float32),
            pltpu.VMEM((G, D), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        functools.partial(_kernel, tile_s=tile_s, scale=scale),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, Hkv, G, D), jnp.float32),
        interpret=interpret,
    )(kv_len.astype(jnp.int32), qg, k, v)
    return out.reshape(B, H, D)
