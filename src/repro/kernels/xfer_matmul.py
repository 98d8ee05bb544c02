"""⟨Tm, Tn, Tr, Tc⟩-tiled matmul — the paper's accelerator core (§3 ②) on TPU.

The paper's on-chip design streams IFM/WEI tiles into double-buffered BRAM
while a Tm×Tn MAC array consumes them (Fig. 5b). The TPU analogue: a
Pallas grid over (rows/Tr, cols/Tm, contraction/Tn) with BlockSpec-tiled
VMEM windows; the Pallas TPU pipeline double-buffers the HBM→VMEM streams
exactly like the paper's "×2" in Eqs. 3–5, and the MXU plays the MAC
array. The contraction dimension is the innermost grid axis, accumulating
into a VMEM scratch accumulator (f32), written back once per (Tr, Tm)
tile — the paper's ``tO_mem`` overlap (Eq. 13).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _matmul_kernel(x_ref, w_ref, o_ref, acc_ref, *, n_steps: int):
    """Grid = (R/Tr, M/Tm, N/Tn); acc persists across the inner N axis."""
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(x_ref[...], w_ref[...],
                            preferred_element_type=jnp.float32)

    @pl.when(k == n_steps - 1)
    def _flush():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("tr", "tm", "tn", "interpret"))
def xfer_matmul(x: jax.Array, w: jax.Array, *, tr: int = 256, tm: int = 256,
                tn: int = 256, interpret: bool) -> jax.Array:
    """x: [R, N] @ w: [N, M] -> [R, M] with explicit ⟨Tm,Tn,Tr⟩ tiling.

    (Tc is folded into Tr: an LM matmul's spatial extent is 1-D, DESIGN §4.)
    """
    r, n = x.shape
    n2, m = w.shape
    assert n == n2, (x.shape, w.shape)
    tr, tm, tn = min(tr, r), min(tm, m), min(tn, n)
    assert r % tr == 0 and m % tm == 0 and n % tn == 0, (
        f"dims {(r, n, m)} not divisible by tiles {(tr, tn, tm)}")
    grid = (r // tr, m // tm, n // tn)

    return pl.pallas_call(
        functools.partial(_matmul_kernel, n_steps=grid[2]),
        grid=grid,
        in_specs=[
            pl.BlockSpec((tr, tn), lambda i, j, k: (i, k)),  # IFM tile
            pl.BlockSpec((tn, tm), lambda i, j, k: (k, j)),  # WEI tile
        ],
        out_specs=pl.BlockSpec((tr, tm), lambda i, j, k: (i, j)),  # OFM tile
        out_shape=jax.ShapeDtypeStruct((r, m), x.dtype),
        scratch_shapes=[pltpu.VMEM((tr, tm), jnp.float32)],
        interpret=interpret,
    )(x, w)
