"""Chunkwise mLSTM Pallas TPU kernel (xLSTM matrix memory).

Grid = (B·H, S/Bq) over time chunks, sequential on the chunk axis. The
recurrent state (C [d,d], n [d], m [1]) persists in VMEM scratch across
chunks; within a chunk the decay-biased attention form runs on the MXU
(two [bq,d]×[d,d]-class matmuls + one [bq,bq] intra-chunk product).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _mlstm_kernel(q_ref, k_ref, v_ref, ic_ref, fc_ref, ir_ref, fr_ref, o_ref,
                  c_ref, n_ref, m_ref, *, bq: int):
    """Gates arrive twice, as a column ``[bq, 1]`` and as a row
    ``[1, bq]`` block, so every step stays 2-D with no transpose: the
    cumulative forget-gate sums come out of masked reductions over the
    causal mask in whichever orientation the use needs."""
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        c_ref[...] = jnp.zeros_like(c_ref)
        n_ref[...] = jnp.zeros_like(n_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)

    q = q_ref[0].astype(jnp.float32)  # [bq, d]
    k = k_ref[0].astype(jnp.float32)
    v = v_ref[0].astype(jnp.float32)
    it_c = ic_ref[0].astype(jnp.float32)  # [bq, 1]
    it_r = ir_ref[0].astype(jnp.float32)  # [1, bq]
    logf_c = jax.nn.log_sigmoid(fc_ref[0].astype(jnp.float32))
    logf_r = jax.nn.log_sigmoid(fr_ref[0].astype(jnp.float32))

    rows = jax.lax.broadcasted_iota(jnp.int32, (bq, bq), 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, (bq, bq), 1)
    causal = cols <= rows
    # inclusive prefix sums of log f, F[i] = Σ_{j<=i}, as column and row
    F_c = jnp.sum(jnp.where(causal, logf_r, 0.0), axis=1, keepdims=True)
    F_r = jnp.sum(jnp.where(rows <= cols, logf_c, 0.0), axis=0, keepdims=True)
    # chunk-level quantities are scalars: a [1, 1] vector would need a
    # broadcast in sublanes and lanes at once, which Mosaic rejects
    Fe = jnp.sum(logf_r, axis=1, keepdims=True)[0, 0]  # chunk total
    m_carry = m_ref[...][0, 0]
    # intra-chunk decay bias D_ij = F_i - F_j + i_j  (j <= i)
    bias = jnp.where(causal, F_c - F_r + it_r, NEG_INF)
    w_state = F_c + m_carry  # [bq, 1] log-coefficient of the carried state
    m_i = jnp.maximum(jnp.maximum(jnp.max(bias, axis=1, keepdims=True),
                                  w_state), NEG_INF)

    qk = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)
    scores = qk * jnp.exp(bias - m_i)  # [bq, bq]
    s_coef = jnp.exp(w_state - m_i)  # [bq, 1]
    num = (jnp.dot(scores, v, preferred_element_type=jnp.float32)
           + s_coef * jnp.dot(q, c_ref[...], preferred_element_type=jnp.float32))
    den = (jnp.sum(scores, axis=1, keepdims=True)
           + s_coef * jnp.sum(q * n_ref[...], axis=1, keepdims=True))
    den = jnp.maximum(jnp.abs(den), jnp.exp(-m_i))
    o_ref[0] = (num / den).astype(o_ref.dtype)

    # fold chunk into state
    w_log = Fe - F_c + it_c  # [bq, 1]
    m_new = jnp.maximum(jnp.max(w_log, axis=0, keepdims=True)[0, 0],
                        Fe + m_carry)
    kw = k * jnp.exp(w_log - m_new)  # [bq, d]
    carry = jnp.exp(Fe + m_carry - m_new)
    c_ref[...] = carry * c_ref[...] + jax.lax.dot_general(
        kw, v, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    n_ref[...] = carry * n_ref[...] + jnp.sum(kw, axis=0, keepdims=True)
    m_ref[...] = jnp.full_like(m_ref, m_new)


@functools.partial(jax.jit, static_argnames=("bq", "interpret"))
def mlstm_chunkwise(q: jax.Array, k: jax.Array, v: jax.Array,
                    it: jax.Array, ft: jax.Array, *,
                    bq: int = 256, interpret: bool) -> jax.Array:
    """q,k,v: [BH, S, D]; it, ft: [BH, S] gate pre-activations. -> [BH, S, D].

    k is expected pre-scaled by 1/sqrt(D) (as in models/recurrent.py).
    The gates enter the kernel as ``[BH, S, 1]`` and ``[BH, 1, S]``
    views, whose ``(1, bq, 1)`` / ``(1, 1, bq)`` blocks meet the TPU
    lowering's rule (last two block dims (8, 128)-aligned or equal to the
    array's).
    """
    bh, s, d = q.shape
    bq = min(bq, s)
    assert s % bq == 0
    grid = (bh, s // bq)
    col = pl.BlockSpec((1, bq, 1), lambda i, j: (i, j, 0))
    row = pl.BlockSpec((1, 1, bq), lambda i, j: (i, 0, j))
    return pl.pallas_call(
        functools.partial(_mlstm_kernel, bq=bq),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bq, d), lambda i, j: (i, j, 0)),
            pl.BlockSpec((1, bq, d), lambda i, j: (i, j, 0)),
            pl.BlockSpec((1, bq, d), lambda i, j: (i, j, 0)),
            col, col, row, row,
        ],
        out_specs=pl.BlockSpec((1, bq, d), lambda i, j: (i, j, 0)),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=[
            pltpu.VMEM((d, d), jnp.float32),
            pltpu.VMEM((1, d), jnp.float32),
            pltpu.VMEM((1, 1), jnp.float32),
        ],
        interpret=interpret,
    )(q, k, v, it[..., None], ft[..., None], it[:, None, :], ft[:, None, :])
