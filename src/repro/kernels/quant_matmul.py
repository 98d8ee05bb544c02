"""Dequant-fused INT8-weight matmul Pallas TPU kernel.

The INT8 serving path (``QuantConfig(weights="int8")``) keeps weights
HBM-resident as per-channel int8 with an f32 scale per output column
(``repro.quant.quantize_params``). This kernel streams the *int8* tiles
HBM→VMEM — the bandwidth win the quantisation buys — and fuses the
rehydration into the matmul epilogue: per-channel symmetric scaling
commutes with the contraction (``(x @ q) * scale == x @ (q * scale)``),
so the int8 tile feeds the MXU via ``preferred_element_type=f32`` and the
scale multiplies the accumulated ``[tr, tm]`` tile exactly once at flush,
not per contraction step. Same ⟨Tm,Tn,Tr⟩ tiling and double-buffered
pipeline structure as kernels/xfer_matmul.py.

Callers go through ``kernels/ops.py``, which picks interpret mode by
platform; ``kernels/ref.py:quant_matmul_ref`` is the jnp oracle.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _quant_matmul_kernel(x_ref, w_ref, s_ref, o_ref, acc_ref, *, n_steps: int):
    """Grid = (R/Tr, M/Tm, N/Tn); acc persists across the inner N axis;
    the per-column scale applies once at flush."""
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(x_ref[...].astype(jnp.float32), w_ref[...],
                            preferred_element_type=jnp.float32)

    @pl.when(k == n_steps - 1)
    def _flush():
        o_ref[...] = (acc_ref[...] * s_ref[...]).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("tr", "tm", "tn", "interpret"))
def quant_matmul(x: jax.Array, w_q: jax.Array, scale: jax.Array, *,
                 tr: int = 256, tm: int = 256, tn: int = 256,
                 interpret: bool) -> jax.Array:
    """x: [R, N] fp @ w_q: [N, M] int8 with scale: [1, M] f32 -> [R, M].

    ``w_q``/``scale`` are a per-channel :class:`repro.quant.QTensor`'s
    leaves (scale keeps rank with the reduced axis at extent 1).
    """
    r, n = x.shape
    n2, m = w_q.shape
    assert n == n2, (x.shape, w_q.shape)
    scale = scale.reshape(1, m).astype(jnp.float32)
    tr, tm, tn = min(tr, r), min(tm, m), min(tn, n)
    assert r % tr == 0 and m % tm == 0 and n % tn == 0, (
        f"dims {(r, n, m)} not divisible by tiles {(tr, tn, tm)}")
    grid = (r // tr, m // tm, n // tn)

    return pl.pallas_call(
        functools.partial(_quant_matmul_kernel, n_steps=grid[2]),
        grid=grid,
        in_specs=[
            pl.BlockSpec((tr, tn), lambda i, j, k: (i, k)),  # IFM tile (fp)
            pl.BlockSpec((tn, tm), lambda i, j, k: (k, j)),  # WEI tile (int8)
            pl.BlockSpec((1, tm), lambda i, j, k: (0, j)),   # per-col scale
        ],
        out_specs=pl.BlockSpec((tr, tm), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((r, m), x.dtype),
        scratch_shapes=[pltpu.VMEM((tr, tm), jnp.float32)],
        interpret=interpret,
    )(x, w_q, scale)
