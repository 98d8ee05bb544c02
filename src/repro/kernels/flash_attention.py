"""Flash attention (online softmax) Pallas TPU kernel.

Grid = (batch·heads, Sq/Bq, T/Bk), kv innermost. Running max/sum and the
output accumulator live in VMEM scratch and persist across the kv axis —
the score matrix never touches HBM (this is the traffic the HLO analyzer
books as `vmem_resident_bytes` on the reference path).

Supports causal masking and a local attention window (RecurrentGemma's
block pattern) via position arithmetic on block indices.

INT8 KV (``QuantConfig(kv="int8")`` serving) passes per-token f32 scales
as ``k_scale``/``v_scale`` ``[BH, T, 1]``; dequantisation fuses into the
kernel — each int8 kv block rehydrates in VMEM right before the dot, so
the fp extent never round-trips HBM.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _flash_body(q_ref, k, v, o_ref, m_ref, l_ref, acc_ref, *,
                bq: int, bk: int, n_kv: int, causal: bool, window: int):
    """Online-softmax update for one kv block; ``k``/``v`` arrive already
    rehydrated to f32 ``[bk, d]``."""
    iq, ik = pl.program_id(1), pl.program_id(2)

    @pl.when(ik == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0].astype(jnp.float32)  # [bq, d]
    s = q @ k.T / math.sqrt(q.shape[-1])  # [bq, bk]

    q_pos = iq * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
    k_pos = ik * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    mask = jnp.ones((bq, bk), dtype=bool)
    if causal:
        mask &= k_pos <= q_pos
    if window:
        mask &= (q_pos - k_pos) < window
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1))
    p = jnp.exp(s - m_new[:, None])
    alpha = jnp.exp(m_prev - m_new)
    l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=-1)
    acc_ref[...] = acc_ref[...] * alpha[:, None] + p @ v
    m_ref[...] = m_new

    @pl.when(ik == n_kv - 1)
    def _flush():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0] = (acc_ref[...] / l[:, None]).astype(o_ref.dtype)


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, **kw):
    _flash_body(q_ref, k_ref[0].astype(jnp.float32),
                v_ref[0].astype(jnp.float32), o_ref, m_ref, l_ref, acc_ref,
                **kw)


def _flash_kernel_q8(q_ref, k_ref, v_ref, ks_ref, vs_ref, o_ref,
                     m_ref, l_ref, acc_ref, **kw):
    # fused dequant: [bk, d] int8 * [bk, 1] f32, in VMEM
    _flash_body(q_ref, k_ref[0].astype(jnp.float32) * ks_ref[0],
                v_ref[0].astype(jnp.float32) * vs_ref[0], o_ref,
                m_ref, l_ref, acc_ref, **kw)


@functools.partial(jax.jit,
                   static_argnames=("bq", "bk", "causal", "window", "interpret"))
def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    k_scale: jax.Array = None, v_scale: jax.Array = None,
                    bq: int = 512, bk: int = 512, causal: bool = True,
                    window: int = 0, interpret: bool) -> jax.Array:
    """q: [BH, S, D]; k, v: [BH, T, D] (KV already broadcast across groups).
    ``k_scale``/``v_scale``: optional [BH, T, 1] f32 per-token scales for
    int8 ``k``/``v`` (dequant fused in-kernel)."""
    if (k_scale is None) != (v_scale is None):
        raise ValueError("pass both k_scale and v_scale, or neither")
    bh, sq, d = q.shape
    t = k.shape[1]
    bq, bk = min(bq, sq), min(bk, t)
    assert sq % bq == 0 and t % bk == 0
    grid = (bh, sq // bq, t // bk)
    quant = k_scale is not None

    in_specs = [
        pl.BlockSpec((1, bq, d), lambda b, i, j: (b, i, 0)),
        pl.BlockSpec((1, bk, d), lambda b, i, j: (b, j, 0)),
        pl.BlockSpec((1, bk, d), lambda b, i, j: (b, j, 0)),
    ]
    args = [q, k, v]
    kernel = _flash_kernel
    if quant:
        scale_spec = pl.BlockSpec((1, bk, 1), lambda b, i, j: (b, j, 0))
        in_specs += [scale_spec, scale_spec]
        args += [k_scale, v_scale]
        kernel = _flash_kernel_q8

    return pl.pallas_call(
        functools.partial(kernel, bq=bq, bk=bk, n_kv=grid[2],
                          causal=causal, window=window),
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, bq, d), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq,), jnp.float32),      # running max
            pltpu.VMEM((bq,), jnp.float32),      # running sum
            pltpu.VMEM((bq, d), jnp.float32),    # output accumulator
        ],
        interpret=interpret,
    )(*args)
