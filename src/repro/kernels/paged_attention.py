"""Paged decode attention (single query token) Pallas TPU kernel.

The serving page pool (`repro.serving.pages`) stores KV in fixed-size
pages ``[P, ps, G, D]``; each decode row owns an int32 page-table row
mapping logical position blocks to physical pages. The gather fallback in
``models.blocks._paged_decode_attention`` materialises the full
``[B, M·ps, G, D]`` kv extent through the table in HBM before attending;
this kernel instead walks the table with **scalar prefetch**
(`pltpu.PrefetchScalarGridSpec`): the page id for grid step ``(b, j)`` is
read from the prefetched table to index the kv pool's BlockSpec, so each
page is DMA'd HBM→VMEM exactly once and the gathered extent never exists
in HBM. Online softmax state (running max / sum / accumulator) lives in
VMEM scratch across the page axis, like kernels/flash_attention.py.

INT8 KV pools (``serving.pages`` under ``QuantConfig(kv="int8")``) pass
the per-token f32 scale pools as ``k_scale``/``v_scale`` ``[P, ps, G, 1]``;
the dequantisation is fused into the kernel — each int8 page and its
scale page are DMA'd together and rehydrated in VMEM right before the
dot, so the fp extent never exists in HBM (the whole point of the int8
cache: HBM traffic per page drops ~4x for bf16→int8-and-scale).

Callers go through ``kernels/ops.py``, which picks interpret mode by
platform; `kernels/ref.py:paged_attention_ref` is the jnp oracle.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _paged_body(lens_ref, q_ref, k, v, o_ref, m_ref, l_ref, acc_ref, *,
                ps: int, rep: int, n_pages: int):
    """Online-softmax update for one (row, page) grid step; ``k``/``v``
    are the current page already rehydrated to f32 ``[ps, G, D]``."""
    b, j = pl.program_id(0), pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0].astype(jnp.float32)    # [H, D]
    h, d = q.shape
    g = k.shape[1]
    qg = q.reshape(g, rep, d) / math.sqrt(d)
    s = jnp.einsum("grd,pgd->grp", qg, k).reshape(h, ps)  # head h → group h//rep

    pos = j * ps + jax.lax.broadcasted_iota(jnp.int32, (h, ps), 1)
    s = jnp.where(pos < lens_ref[b], s, NEG_INF)

    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1))
    p = jnp.exp(s - m_new[:, None])
    alpha = jnp.exp(m_prev - m_new)
    l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=-1)
    pv = jnp.einsum("grp,pgd->grd", p.reshape(g, rep, ps), v).reshape(h, d)
    acc_ref[...] = acc_ref[...] * alpha[:, None] + pv
    m_ref[...] = m_new

    @pl.when(j == n_pages - 1)
    def _flush():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0] = (acc_ref[...] / l[:, None]).astype(o_ref.dtype)


def _paged_kernel(lens_ref, table_ref, q_ref, k_ref, v_ref, o_ref,
                  m_ref, l_ref, acc_ref, *, ps: int, rep: int, n_pages: int):
    _paged_body(lens_ref, q_ref,
                k_ref[0].astype(jnp.float32), v_ref[0].astype(jnp.float32),
                o_ref, m_ref, l_ref, acc_ref, ps=ps, rep=rep, n_pages=n_pages)


def _paged_kernel_q8(lens_ref, table_ref, q_ref, k_ref, v_ref,
                     ks_ref, vs_ref, o_ref, m_ref, l_ref, acc_ref, *,
                     ps: int, rep: int, n_pages: int):
    # fused dequant: [ps, G, D] int8 * [ps, G, 1] f32, in VMEM
    _paged_body(lens_ref, q_ref,
                k_ref[0].astype(jnp.float32) * ks_ref[0],
                v_ref[0].astype(jnp.float32) * vs_ref[0],
                o_ref, m_ref, l_ref, acc_ref, ps=ps, rep=rep, n_pages=n_pages)


@functools.partial(jax.jit, static_argnames=("interpret",))
def paged_attention(q: jax.Array, kp: jax.Array, vp: jax.Array,
                    page_table: jax.Array, lengths: jax.Array, *,
                    k_scale: jax.Array = None, v_scale: jax.Array = None,
                    interpret: bool) -> jax.Array:
    """q: [B, H, D]; kp, vp: [P, ps, G, D] page pools;
    page_table: [B, M] int32 physical page per logical block;
    lengths: [B] int32 valid kv count per row (positions >= length are
    masked — unwritten page tails and null-page garbage never attend).
    ``k_scale``/``v_scale``: optional [P, ps, G, 1] f32 per-token scale
    pools for int8 ``kp``/``vp`` (dequant fused in-kernel).
    Returns [B, H, D]."""
    if (k_scale is None) != (v_scale is None):
        raise ValueError("pass both k_scale and v_scale, or neither")
    b, h, d = q.shape
    ps, g = kp.shape[1], kp.shape[2]
    m = page_table.shape[1]
    rep = h // g
    quant = k_scale is not None

    kv_spec = pl.BlockSpec((1, ps, g, d),
                           lambda bi, j, lens, table: (table[bi, j], 0, 0, 0))
    in_specs = [
        pl.BlockSpec((1, h, d), lambda bi, j, lens, table: (bi, 0, 0)),
        kv_spec, kv_spec,
    ]
    args = [q, kp, vp]
    kernel = _paged_kernel
    if quant:
        scale_spec = pl.BlockSpec(
            (1, ps, g, 1), lambda bi, j, lens, table: (table[bi, j], 0, 0, 0))
        in_specs += [scale_spec, scale_spec]
        args += [k_scale, v_scale]
        kernel = _paged_kernel_q8

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,  # lengths, page_table
        grid=(b, m),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, h, d), lambda bi, j, lens, table: (bi, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((h,), jnp.float32),     # running max
            pltpu.VMEM((h,), jnp.float32),     # running sum
            pltpu.VMEM((h, d), jnp.float32),   # output accumulator
        ],
    )
    return pl.pallas_call(
        functools.partial(kernel, ps=ps, rep=rep, n_pages=m),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        interpret=interpret,
    )(lengths.astype(jnp.int32), page_table.astype(jnp.int32), *args)
