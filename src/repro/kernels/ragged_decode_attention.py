"""Ragged single-token decode attention over the stacked head-major K/V
grid (Pallas TPU kernel).

The dense serving grid is ``[L, B, G, T, D]`` (``models.blocks``), and the
layer scan of ``models.lm.forward`` carries it whole. XLA's attention dots
read all ``T`` positions of a layer's slab for every row, whatever the
row's live context. This kernel leaves the grid in HBM
(``memory_space=pl.ANY``) and, for each row ``b``, copies in only the
position blocks ``0 … nb[b] − 1`` of layer ``i`` that can hold a key the
query may attend, ``nb[b] = ceil(min(q_pos[b] + 1, T) / tb)``
(:func:`live_blocks`). A ring that has wrapped (``q_pos ≥ T``) is read
whole, as it must be.

One grid step per row; inside it a loop over the row's live blocks with
double-buffered ``make_async_copy`` of the ``[G, tb, D]`` K and V tiles.
The last block of a row starts the copy of the next row's first block,
so the copy engine never waits on a grid step boundary. The block's
``pos`` comes in beside its K and V tiles, as the ``[8, tb]`` tile of
the 8 rows that hold the row (a copy's tiling takes no less): 1.6% more
bytes than the row's K and V alone.

Inside a block the mask is ``layers._mask``'s: ``pos ≥ 0``, causal
``kpos ≤ qpos`` and, with ``window``, ``qpos − kpos < window``, so stale
entries beyond a row's extent, wrapped rings and windowed rings stay
exact. The arithmetic is ``layers._attend_block``'s: q scaled in its own
dtype, scores against K with f32 accumulation, probabilities cast to
q's dtype for the PV dot (f32 accumulation), softmax state in f32, the
output cast to q's dtype. Only the order of the softmax differs: it is
online over blocks, so a probability is rounded against the running
max instead of the row's max. A row with no valid key (``nb = 0``)
returns zeros.

Callers go through ``kernels/ops.py``, which picks interpret mode by
platform; ``kernels/ref.py:ragged_decode_attention_ref`` is the jnp
oracle.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def block_size(t: int):
    """Positions per K/V block: the largest of 512, 256 and 128 that
    divides the grid length ``t``; None where none does."""
    return next((tb for tb in (512, 256, 128) if t % tb == 0), None)


def live_blocks(q_pos: jax.Array, t: int) -> jax.Array:
    """``[B]`` int32 count of the blocks a row's query may attend:
    ``ceil(min(q_pos + 1, t) / tb)``, 0 for a negative position."""
    tb = block_size(t)
    extent = jnp.clip(q_pos.astype(jnp.int32) + 1, 0, t)
    return (extent + tb - 1) // tb


def _kernel(layer_ref, qpos_ref, nb_ref, first_ref, q_ref, pos_ref, k_hbm,
            v_hbm, o_ref, pbuf, kbuf, vbuf, sem, *, tb: int, pr: int,
            window: int):
    rows = pl.num_programs(0)
    bi = pl.program_id(0)
    li = layer_ref[0]
    n = nb_ref[bi]
    qp = qpos_ref[bi]

    def copies(row, j):
        """The pos, K and V copies of block ``j`` of ``row``; its buffer
        slot alternates over the whole call, across rows."""
        slot = (first_ref[row] + j) % 2
        start = pl.multiple_of(j * tb, tb)
        rows0 = pl.multiple_of((row // pr) * pr, pr)
        return (pltpu.make_async_copy(
                    pos_ref.at[pl.ds(rows0, pr), pl.ds(start, tb)],
                    pbuf.at[slot], sem.at[2, slot]),
                pltpu.make_async_copy(
                    k_hbm.at[li, row, :, pl.ds(start, tb), :],
                    kbuf.at[slot], sem.at[0, slot]),
                pltpu.make_async_copy(
                    v_hbm.at[li, row, :, pl.ds(start, tb), :],
                    vbuf.at[slot], sem.at[1, slot]))

    # block 0 of this row, unless the row before started it
    @pl.when((n > 0) & ((bi == 0) | (nb_ref[jnp.maximum(bi - 1, 0)] == 0)))
    def _():
        for c in copies(bi, 0):
            c.start()

    q = q_ref[0]  # [G, rep, D], already scaled
    g, rep, d = q.shape

    def body(j, carry):
        m, l, acc = carry

        @pl.when(j + 1 < n)
        def _():
            for c in copies(bi, j + 1):
                c.start()

        nxt = jnp.minimum(bi + 1, rows - 1)

        @pl.when((j + 1 == n) & (bi + 1 < rows) & (nb_ref[nxt] > 0))
        def _():
            for c in copies(nxt, 0):
                c.start()

        slot = (first_ref[bi] + j) % 2
        pc, kc, vc = copies(bi, j)
        pc.wait()
        kc.wait()
        kpos = pbuf[slot, pl.ds(bi % pr, 1), :]  # [1, tb]
        valid = (kpos >= 0) & (kpos <= qp)
        if window:
            valid = valid & (qp - kpos < window)
        valid = valid[None]  # [1, 1, tb]
        s = jnp.einsum("grd,gtd->grt", q, kbuf[slot],
                       preferred_element_type=jnp.float32)
        s = jnp.where(valid, s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        alpha = jnp.exp(m - m_new)
        p = jnp.where(valid, jnp.exp(s - m_new), 0.0)
        l = l * alpha + jnp.sum(p, axis=-1, keepdims=True)
        vc.wait()
        pv = jnp.einsum("grt,gtd->grd", p.astype(q.dtype), vbuf[slot],
                        preferred_element_type=jnp.float32)
        return m_new, l, acc * alpha + pv

    init = (jnp.full((g, rep, 1), NEG_INF, jnp.float32),
            jnp.zeros((g, rep, 1), jnp.float32),
            jnp.zeros((g, rep, d), jnp.float32))
    _, l, acc = jax.lax.fori_loop(0, n, body, init)
    o_ref[0] = (acc / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("window", "interpret"))
def ragged_decode_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                            pos: jax.Array, layer: jax.Array,
                            q_pos: jax.Array, *, window: int = 0,
                            interpret: bool) -> jax.Array:
    """q: [B, H, D] one query per row; k, v: [L, B, G, T, D] the stacked
    grid; pos: [L, B, T] int32 the position each entry holds (−1: none);
    layer: int32 scalar, the layer attended; q_pos: [B] int32 each row's
    query position. ``T`` must have a :func:`block_size`, and Mosaic
    takes block copies only of a ``D`` that is a multiple of 128. Returns
    [B, H, D] in q's dtype."""
    b, h, d = q.shape
    _, _, g, t, _ = k.shape
    rep = h // g
    tb = block_size(t)
    if tb is None:
        raise ValueError(f"grid length {t} has no block size of 512, 256 "
                         f"or 128")
    scale = jnp.asarray(1.0 / math.sqrt(d), q.dtype)
    qg = (q * scale).reshape(b, g, rep, d)
    q_pos = q_pos.astype(jnp.int32)
    nb = live_blocks(q_pos, t)
    first = jnp.cumsum(nb) - nb  # blocks of the call before each row
    pr = 8 if b % 8 == 0 else b  # pos rows per copy: the copy's tiling
    # the layer's [B, T] positions (4 bytes a position; given the whole
    # [L, B, T], the compiler stages all of it in VMEM for every layer)
    pos = jax.lax.dynamic_index_in_dim(pos, layer, keepdims=False)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,  # layer, q_pos, nb, first
        grid=(b,),
        in_specs=[
            pl.BlockSpec((1, g, rep, d), lambda r, *_: (r, 0, 0, 0)),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec((1, g, rep, d), lambda r, *_: (r, 0, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((2, pr, tb), jnp.int32),
            pltpu.VMEM((2, g, tb, d), k.dtype),
            pltpu.VMEM((2, g, tb, d), v.dtype),
            pltpu.SemaphoreType.DMA((3, 2)),
        ],
    )
    out = pl.pallas_call(
        functools.partial(_kernel, tb=tb, pr=pr, window=window),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, g, rep, d), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="ragged_decode_attention",  # the op's name in a device trace
    )(jnp.reshape(layer, (1,)).astype(jnp.int32), q_pos, nb, first, qg, pos,
      k, v)
    return out.reshape(b, h, d)
