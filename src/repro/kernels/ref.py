"""Pure-jnp oracles for every Pallas kernel (allclose targets)."""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

NEG_INF = -1e30


def matmul_ref(x: jax.Array, w: jax.Array) -> jax.Array:
    return jnp.dot(x.astype(jnp.float32), w.astype(jnp.float32)).astype(x.dtype)


def quant_matmul_ref(x: jax.Array, w_q: jax.Array,
                     scale: jax.Array) -> jax.Array:
    """Dequantize-then-matmul oracle for kernels/quant_matmul.py:
    x [R, N] fp @ (w_q [N, M] int8 * scale [1, M] f32)."""
    w = w_q.astype(jnp.float32) * scale.reshape(1, w_q.shape[1])
    return jnp.dot(x.astype(jnp.float32), w).astype(x.dtype)


def flash_attention_ref(q: jax.Array, k: jax.Array, v: jax.Array, *,
                        k_scale: jax.Array = None, v_scale: jax.Array = None,
                        causal: bool = True, window: int = 0) -> jax.Array:
    """q: [BH, S, D]; k, v: [BH, T, D]; optional [BH, T, 1] per-token
    scales dequantize int8 k/v before attending."""
    if k_scale is not None:
        k = k.astype(jnp.float32) * k_scale
        v = v.astype(jnp.float32) * v_scale
    bh, sq, d = q.shape
    t = k.shape[1]
    s = jnp.einsum("bsd,btd->bst", q.astype(jnp.float32),
                   k.astype(jnp.float32)) / math.sqrt(d)
    qp = jnp.arange(sq)[:, None]
    kp = jnp.arange(t)[None, :]
    mask = jnp.ones((sq, t), bool)
    if causal:
        mask &= kp <= qp
    if window:
        mask &= (qp - kp) < window
    s = jnp.where(mask[None], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bst,btd->bsd", p, v.astype(jnp.float32)).astype(q.dtype)


def paged_attention_ref(q: jax.Array, kp: jax.Array, vp: jax.Array,
                        page_table: jax.Array, lengths: jax.Array,
                        k_scale: jax.Array = None,
                        v_scale: jax.Array = None) -> jax.Array:
    """Gather-then-attend oracle for kernels/paged_attention.py.

    q: [B, H, D]; kp, vp: [P, ps, G, D]; page_table: [B, M] int32;
    lengths: [B] valid kv count; optional [P, ps, G, 1] scale pools
    dequantize int8 kp/vp after the gather. Returns [B, H, D]."""
    b, h, d = q.shape
    ps, g = kp.shape[1], kp.shape[2]
    t = page_table.shape[1] * ps
    rep = h // g
    k = kp[page_table].reshape(b, t, g, d).astype(jnp.float32)
    v = vp[page_table].reshape(b, t, g, d).astype(jnp.float32)
    if k_scale is not None:
        k = k * k_scale[page_table].reshape(b, t, g, 1)
        v = v * v_scale[page_table].reshape(b, t, g, 1)
    qg = q.astype(jnp.float32).reshape(b, g, rep, d) / math.sqrt(d)
    s = jnp.einsum("bgrd,btgd->bgrt", qg, k)
    valid = jnp.arange(t)[None] < lengths[:, None]  # [B, t]
    s = jnp.where(valid[:, None, None, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bgrt,btgd->bgrd", p, v)
    return o.reshape(b, h, d).astype(q.dtype)


def ragged_decode_attention_ref(q: jax.Array, k: jax.Array, v: jax.Array,
                                pos: jax.Array, layer, q_pos: jax.Array, *,
                                window: int = 0) -> jax.Array:
    """Whole-slab oracle for kernels/ragged_decode_attention.py.

    q: [B, H, D]; k, v: [L, B, G, T, D]; pos: [L, B, T] (−1: empty);
    layer: the layer attended; q_pos: [B]. Every position of the layer's
    slab is read and masked as ``models.layers._mask`` masks it; a row
    with no valid key returns zeros. Returns [B, H, D]."""
    b, h, d = q.shape
    g = k.shape[2]
    rep = h // g
    kl = k[layer].astype(jnp.float32)  # [B, G, T, D]
    vl = v[layer].astype(jnp.float32)
    kp = pos[layer]  # [B, T]
    qp = q_pos[:, None]
    valid = (kp >= 0) & (kp <= qp)
    if window:
        valid &= (qp - kp) < window
    qg = q.astype(jnp.float32).reshape(b, g, rep, d) / math.sqrt(d)
    s = jnp.einsum("bgrd,bgtd->bgrt", qg, kl)
    s = jnp.where(valid[:, None, None, :], s, NEG_INF)
    p = jnp.where(valid[:, None, None, :], jax.nn.softmax(s, axis=-1), 0.0)
    o = jnp.einsum("bgrt,bgtd->bgrd", p, vl)
    return o.reshape(b, h, d).astype(q.dtype)


def rglru_scan_ref(a: jax.Array, b: jax.Array, h0: jax.Array) -> jax.Array:
    """h_t = a_t * h_{t-1} + b_t; returns the h sequence [B, S, W]."""
    def step(h, ab):
        at, bt = ab
        h = at * h + bt
        return h, h

    _, hs = jax.lax.scan(step, h0.astype(jnp.float32),
                         (a.astype(jnp.float32).transpose(1, 0, 2),
                          b.astype(jnp.float32).transpose(1, 0, 2)))
    return hs.transpose(1, 0, 2).astype(a.dtype)


def mlstm_ref(q: jax.Array, k: jax.Array, v: jax.Array,
              it: jax.Array, ft: jax.Array) -> jax.Array:
    """Strict per-step recurrent reference. q,k,v: [BH,S,D]; it,ft: [BH,S]."""
    bh, s, d = q.shape

    def step(carry, xs):
        C, n, m = carry
        qt, kt, vt, i_t, f_t = xs
        logf = jax.nn.log_sigmoid(f_t)
        m_new = jnp.maximum(logf + m, i_t)
        fs = jnp.exp(logf + m - m_new)[:, None]
        is_ = jnp.exp(i_t - m_new)[:, None]
        C = fs[..., None] * C + is_[..., None] * (kt[:, :, None] * vt[:, None, :])
        n = fs * n + is_ * kt
        num = jnp.einsum("bkv,bk->bv", C, qt)
        den = jnp.maximum(jnp.abs(jnp.einsum("bk,bk->b", n, qt)),
                          jnp.exp(-m_new))[:, None]
        return (C, n, m_new), num / den

    C0 = jnp.zeros((bh, d, d), jnp.float32)
    n0 = jnp.zeros((bh, d), jnp.float32)
    m0 = jnp.full((bh,), NEG_INF, jnp.float32)
    xs = (q.astype(jnp.float32).transpose(1, 0, 2),
          k.astype(jnp.float32).transpose(1, 0, 2),
          v.astype(jnp.float32).transpose(1, 0, 2),
          it.astype(jnp.float32).T, ft.astype(jnp.float32).T)
    _, hs = jax.lax.scan(step, (C0, n0, m0), xs)
    return hs.transpose(1, 0, 2).astype(q.dtype)
