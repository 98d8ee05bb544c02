"""RG-LRU blocked linear-scan Pallas TPU kernel.

Recurrence h_t = a_t ⊙ h_{t-1} + b_t over [B, S, W]. Grid = (B, S/Bs) with
the sequence axis iterated innermost *sequentially* (TPU grid order), so
the carry h lives in VMEM scratch across blocks; within a block the scan
runs over rows of a VMEM tile. HBM traffic = read a,b once + write h once
(the paper's memory-bound streaming layer at machine balance).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _rglru_kernel(a_ref, b_ref, h0_ref, o_ref, carry_ref, *, bs: int):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        carry_ref[...] = h0_ref[0].astype(jnp.float32)

    def step(i, h):  # h: [1, W] f32; rows read/written straight from VMEM
        row = pl.ds(i, 1)
        h = a_ref[0, row, :].astype(jnp.float32) * h + b_ref[0, row, :]
        o_ref[0, row, :] = h.astype(o_ref.dtype)
        return h

    carry_ref[...] = jax.lax.fori_loop(0, bs, step, carry_ref[...])


@functools.partial(jax.jit, static_argnames=("bs", "interpret"))
def rglru_scan(a: jax.Array, b: jax.Array, h0: jax.Array, *,
               bs: int = 256, interpret: bool) -> jax.Array:
    """a, b: [B, S, W] (f32); h0: [B, W]. Returns h sequence [B, S, W].

    ``h0`` enters the kernel as ``[B, 1, W]``: a ``(1, 1, W)`` block's
    last two dims equal the array's, which the TPU lowering requires of
    any block that is not (8, 128)-aligned."""
    bsz, s, w = a.shape
    bs = min(bs, s)
    assert s % bs == 0
    grid = (bsz, s // bs)
    return pl.pallas_call(
        functools.partial(_rglru_kernel, bs=bs),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bs, w), lambda i, j: (i, j, 0)),
            pl.BlockSpec((1, bs, w), lambda i, j: (i, j, 0)),
            pl.BlockSpec((1, 1, w), lambda i, j: (i, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, bs, w), lambda i, j: (i, j, 0)),
        out_shape=jax.ShapeDtypeStruct((bsz, s, w), a.dtype),
        scratch_shapes=[pltpu.VMEM((1, w), jnp.float32)],
        interpret=interpret,
    )(a, b, h0.reshape(bsz, 1, w))
