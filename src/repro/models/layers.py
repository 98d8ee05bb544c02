"""Shared model primitives (pure JAX): norms, RoPE, GQA attention, MLPs.

Attention uses a query-block online-softmax formulation for long sequences
(the same algorithm the Pallas kernel in ``kernels/flash_attention.py``
implements for TPU), so a 32k-token prefill never materialises an S×S
score matrix — essential for both CPU smoke tests and compile-time memory
analysis on the dry-run.
"""
from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp

# ---------------------------------------------------------------------------
# init helpers
# ---------------------------------------------------------------------------

def dense_init(key, shape, in_axis: int = 0, dtype=jnp.float32):
    fan_in = shape[in_axis] if isinstance(in_axis, int) else 1
    std = 1.0 / math.sqrt(max(fan_in, 1))
    return (jax.random.truncated_normal(key, -2.0, 2.0, shape) * std).astype(dtype)


# ---------------------------------------------------------------------------
# norms
# ---------------------------------------------------------------------------

def rms_norm(x: jax.Array, scale: jax.Array, eps: float = 1e-6) -> jax.Array:
    dt = x.dtype
    x = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    y = x * jax.lax.rsqrt(var + eps)
    return (y * (1.0 + scale.astype(jnp.float32))).astype(dt)


def layer_norm(x: jax.Array, scale: jax.Array, bias: jax.Array,
               eps: float = 1e-6) -> jax.Array:
    dt = x.dtype
    x = x.astype(jnp.float32)
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    y = (x - mu) * jax.lax.rsqrt(var + eps)
    return (y * scale.astype(jnp.float32) + bias.astype(jnp.float32)).astype(dt)


# ---------------------------------------------------------------------------
# RoPE
# ---------------------------------------------------------------------------

def rope(x: jax.Array, positions: jax.Array, theta: float = 10_000.0) -> jax.Array:
    """x: [B, S, H, D]; positions: [B, S] (int). Rotates pairs (d, d+D/2)."""
    b, s, h, d = x.shape
    half = d // 2
    freq = jnp.exp(-math.log(theta) * jnp.arange(half, dtype=jnp.float32) / half)
    ang = positions.astype(jnp.float32)[:, :, None] * freq[None, None, :]  # [B,S,half]
    cos = jnp.cos(ang)[:, :, None, :]
    sin = jnp.sin(ang)[:, :, None, :]
    x1, x2 = x[..., :half].astype(jnp.float32), x[..., half:].astype(jnp.float32)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# attention (GQA, causal / local-window / prefix-bidirectional / cross)
# ---------------------------------------------------------------------------

NEG_INF = -1e30


def _mask(q_pos, kv_pos, kv_valid, causal: bool, window: int, prefix_len):
    """[B, Sq, Skv] boolean allow-mask from position metadata."""
    qp = q_pos[:, :, None]
    kp = kv_pos[:, None, :]
    m = kv_valid[:, None, :]
    if causal:
        c = kp <= qp
        if prefix_len is not None:
            c = c | (kp < prefix_len[:, None, None])  # prefix-LM: bidirectional prefix
        m = m & c
    if window:
        m = m & (qp - kp < window)
    return m


def _attend_block(q, k, v, mask):
    """One (q-block × full-kv) online-softmax pass. q:[B,Sq,H,D] k,v:[B,G,T,D].

    Pure-jnp oracle of kernels/flash_attention.py. Everything inside the
    "flashattn" scope stays in VMEM on the TPU kernel path; the HLO
    analyzer (launch/hlo_analysis.py) accounts its traffic separately.

    Comm-friendly conventions (§Perf iteration 1): inputs stay in their
    storage dtype with f32 MXU accumulation (`preferred_element_type`), so
    any GSPMD resharding of the score/probability tensors moves bf16, and
    the softmax normalisation happens in the grouped [B,G,rep,…] layout so
    no reshape crosses the head-sharded dim boundary.

    K/V are head-major, the order the dense K/V grid stores them in and
    the order both dots contract in: decode reads a layer's slab of the
    grid where it lies, with no relayout in between.
    """
    b, sq, h, d = q.shape
    g = k.shape[1]
    rep = h // g
    scale = jnp.asarray(1.0 / math.sqrt(d), q.dtype)
    qg = (q * scale).reshape(b, sq, g, rep, d)
    scores = jnp.einsum("bsgrd,bgtd->bgrst", qg, k,
                        preferred_element_type=jnp.float32)
    scores = jnp.where(mask[:, None, None, :, :], scores, NEG_INF)
    m = jnp.max(scores, axis=-1, keepdims=True)
    m = jnp.maximum(m, NEG_INF)  # rows with no valid kv stay finite
    p = jnp.exp(scores - m)
    l = jnp.sum(p, axis=-1, keepdims=True)
    # the output stays grouped, [B,G,rep,Sq,D]: with it query-major the
    # CPU backend runs this bf16 dot on a routine that has no bf16
    o = jnp.einsum("bgrst,bgtd->bgrsd", p.astype(q.dtype), v,
                   preferred_element_type=jnp.float32)
    # normalise in grouped layout (no cross-shard reshape), then flatten
    o = o / jnp.maximum(l, 1e-30)
    return (o.astype(q.dtype).transpose(0, 3, 1, 2, 4).reshape(b, sq, h, d),
            m[..., 0], l[..., 0])  # m,l: [B,G,rep,Sq]


def attention(q: jax.Array, k: jax.Array, v: jax.Array,
              q_pos: jax.Array, kv_pos: jax.Array,
              kv_valid: Optional[jax.Array] = None,
              causal: bool = True, window: int = 0,
              prefix_len: Optional[jax.Array] = None,
              q_block: int = 1024) -> jax.Array:
    """GQA attention. q:[B,Sq,H,D]; k,v:[B,G,T,D]; positions int32.

    For Sq > q_block, scans over query blocks (the kv axis is processed in
    one shot per block — the flash kernel tiles it further on TPU).
    """
    b, sq, h, d = q.shape
    t = k.shape[2]
    if kv_valid is None:
        kv_valid = jnp.ones((b, t), dtype=bool)

    if sq <= q_block:
        with jax.named_scope("flashattn"):
            mask = _mask(q_pos, kv_pos, kv_valid, causal, window, prefix_len)
            o, _, _ = _attend_block(q, k, v, mask)
            return o

    nb = sq // q_block
    assert sq % q_block == 0, f"seq {sq} not divisible by q_block {q_block}"

    def body(_, inputs):
        qb, qpb = inputs
        with jax.named_scope("flashattn"):
            mask = _mask(qpb, kv_pos, kv_valid, causal, window, prefix_len)
            o, _, _ = _attend_block(qb, k, v, mask)
            return None, o

    qs = q.reshape(b, nb, q_block, h, d).transpose(1, 0, 2, 3, 4)
    qps = q_pos.reshape(b, nb, q_block).transpose(1, 0, 2)
    _, out = jax.lax.scan(body, None, (qs, qps))
    return out.transpose(1, 0, 2, 3, 4).reshape(b, sq, h, d)


def attention_sharded(ctx, q, k, v, q_pos, kv_pos, kv_valid=None, *,
                      causal=True, window=0, prefix_len=None, q_block=1024):
    """Attention with explicitly local per-device compute (§Perf iter. 2).

    GSPMD left alone reshards the score/probability tensors inside the
    attention body (observed: GB-scale all-gathers per layer in the
    backward). On TPU the flash kernel runs entirely on-device, so we make
    that structure explicit: ``shard_map`` over (batch, heads); inside, the
    plain jnp attention runs on local shards with **zero** collectives.
    GQA KV heads are broadcast to the full head count first when the KV
    head count does not divide the TP degree (the Pallas kernel indexes
    instead of broadcasting — DESIGN.md §7).

    Falls back to the GSPMD path for decode (s==1) and for head counts not
    divisible by the TP degree (e.g. phi3's 40 heads on 16-way TP).
    """
    if ctx is None or ctx.mesh is None or q.shape[1] == 1:
        return attention(q, k, v, q_pos, kv_pos, kv_valid, causal=causal,
                         window=window, prefix_len=prefix_len, q_block=q_block)
    from jax import shard_map

    b, s, h, d = q.shape
    g, t = k.shape[1], k.shape[2]
    tp = ctx.plan.degree(ctx.plan.tp_axes)
    if tp > 1 and h % tp != 0:
        return attention(q, k, v, q_pos, kv_pos, kv_valid, causal=causal,
                         window=window, prefix_len=prefix_len, q_block=q_block)
    if tp > 1 and g % tp != 0:
        k = jnp.repeat(k, h // g, axis=1)  # broadcast KV to full heads
        v = jnp.repeat(v, h // g, axis=1)
        g = h
    if kv_valid is None:
        kv_valid = jnp.ones((b, t), dtype=bool)
    if prefix_len is None:
        prefix_len = jnp.full((b,), -1, jnp.int32)  # <0: no prefix override

    qs = ctx.spec(q.shape, ("batch", "seq", "tp", None))
    ks = ctx.spec(k.shape, ("batch", "tp", None, None))
    ps = ctx.spec(q_pos.shape, ("batch", "seq"))
    kp = ctx.spec(kv_pos.shape, ("batch", None))
    kvd = ctx.spec(kv_valid.shape, ("batch", None))
    pls = ctx.spec(prefix_len.shape, ("batch",))

    def local(q_, k_, v_, qp_, kp_, kvv_, pl_):
        # prefix_len < 0 encodes "no prefix override"; clamping to 0 makes
        # the prefix clause vacuous (kp < 0 never holds), matching None.
        return attention(q_, k_, v_, qp_, kp_, kvv_, causal=causal,
                         window=window, prefix_len=jnp.maximum(pl_, 0),
                         q_block=min(q_block, q_.shape[1]))

    kwargs = dict(mesh=ctx.mesh, in_specs=(qs, ks, ks, ps, kp, kvd, pls),
                  out_specs=qs)
    fn = shard_map(local, check_vma=False, **kwargs)
    return fn(q, k, v, q_pos, kv_pos, kv_valid, prefix_len)


def decode_attention_sharded(ctx, q, k, v, q_pos, kv_pos, kv_valid, *,
                             causal=True, window=0, prefix_len=None):
    """Flash-decoding (§Perf iteration: decode cell).

    The KV cache's head dim rarely divides the TP degree (GQA kv=8 on
    16-way TP; MQA kv=1), so head-sharding the cache is impossible and
    GSPMD falls back to replicating + all-gathering the entire cache every
    step (observed: 68 GB of cache movement per decoded token). Instead the
    cache is sharded over its *sequence* dim; each device computes partial
    attention (o, m, l) over its chunk and the partials merge with a
    log-sum-exp weighted psum over the TP axis — two tiny collectives of
    [B,H,D] instead of the cache.
    """
    if ctx is None or ctx.mesh is None:
        return attention(q, k, v, q_pos, kv_pos, kv_valid, causal=causal,
                         window=window, prefix_len=prefix_len)
    from jax import shard_map

    b, s, h, d = q.shape
    t = k.shape[2]
    tp_axes = ctx.plan.tp_axes
    tp = ctx.plan.degree(tp_axes)
    if tp <= 1 or t % tp != 0 or s != 1:
        return attention(q, k, v, q_pos, kv_pos, kv_valid, causal=causal,
                         window=window, prefix_len=prefix_len)
    if prefix_len is None:
        prefix_len = jnp.full((b,), -1, jnp.int32)

    qs = ctx.spec(q.shape, ("batch", None, None, None))
    ks = ctx.spec(k.shape, ("batch", None, "tp", None))
    pqs = ctx.spec(q_pos.shape, ("batch", None))
    pks = ctx.spec(kv_pos.shape, ("batch", "tp"))
    kvs = ctx.spec(kv_valid.shape, ("batch", "tp"))
    pls = ctx.spec(prefix_len.shape, ("batch",))
    used = ks[2]  # axes actually sharding the cache seq dim
    axis_names = tuple(used) if isinstance(used, tuple) else (used,) if used else ()
    if not axis_names:
        return attention(q, k, v, q_pos, kv_pos, kv_valid, causal=causal,
                         window=window, prefix_len=prefix_len)

    def local(q_, k_, v_, qp_, kp_, kvv_, pl_):
        bl, _, hl, _ = q_.shape
        with jax.named_scope("flashattn"):
            mask = _mask(qp_, kp_, kvv_, causal, window, jnp.maximum(pl_, 0))
            o, m, l = _attend_block(q_, k_, v_, mask)  # o normalised by local l
            # undo local normalisation -> weighted partials, merge over axis
            lq = l.reshape(bl, hl, 1).transpose(0, 2, 1)[..., None]  # [B,1,H,1]
            mq = m.reshape(bl, hl, 1).transpose(0, 2, 1)[..., None]
            m_star = jax.lax.pmax(mq, axis_names)
            w = jnp.exp(mq - m_star) * lq
            num = jax.lax.psum((o.astype(jnp.float32) * w), axis_names)
            den = jax.lax.psum(w, axis_names)
            return (num / jnp.maximum(den, 1e-30)).astype(q_.dtype)

    kwargs = dict(mesh=ctx.mesh,
                  in_specs=(qs, ks, ks, pqs, pks, kvs, pls), out_specs=qs)
    fn = shard_map(local, check_vma=False, **kwargs)
    return fn(q, k, v, q_pos, kv_pos, kv_valid, prefix_len)


# ---------------------------------------------------------------------------
# MLPs
# ---------------------------------------------------------------------------

def mlp_apply(p: dict, x: jax.Array, kind: str, ctx=None) -> jax.Array:
    if kind in ("swiglu", "geglu"):
        act = jax.nn.silu if kind == "swiglu" else (lambda u: jax.nn.gelu(u, approximate=True))
        g = x @ p["w_gate"]
        u = x @ p["w_up"]
        if ctx is not None:
            g = ctx.constrain(g, "batch", "seq", "tp")
            u = ctx.constrain(u, "batch", "seq", "tp")
        h = act(g) * u
    elif kind == "relu2":
        h = x @ p["w_up"]
        if ctx is not None:
            h = ctx.constrain(h, "batch", "seq", "tp")
        h = jnp.square(jax.nn.relu(h))
    else:
        raise ValueError(kind)
    return h @ p["w_down"]


def mlp_init(key, d_model: int, d_ff: int, kind: str, dtype=jnp.float32) -> dict:
    ks = jax.random.split(key, 3)
    if kind in ("swiglu", "geglu"):
        return {
            "w_gate": dense_init(ks[0], (d_model, d_ff), 0, dtype),
            "w_up": dense_init(ks[1], (d_model, d_ff), 0, dtype),
            "w_down": dense_init(ks[2], (d_ff, d_model), 0, dtype),
        }
    return {
        "w_up": dense_init(ks[0], (d_model, d_ff), 0, dtype),
        "w_down": dense_init(ks[1], (d_ff, d_model), 0, dtype),
    }


def mlp_dims(kind: str) -> dict:
    """Logical sharding roles per param (leading layer-stack dim added by stack)."""
    if kind in ("swiglu", "geglu"):
        return {"w_gate": ("xfer", "tp"), "w_up": ("xfer", "tp"), "w_down": ("tp", "xfer")}
    return {"w_up": ("xfer", "tp"), "w_down": ("tp", "xfer")}


# ---------------------------------------------------------------------------
# embeddings / logits
# ---------------------------------------------------------------------------

def embed_tokens(embed: jax.Array, tokens: jax.Array, ctx=None) -> jax.Array:
    x = jnp.take(embed, tokens, axis=0)
    if ctx is not None:
        x = ctx.constrain(x, "batch", "seq", None)
    return x


def unembed_logits(w: jax.Array, x: jax.Array, ctx=None) -> jax.Array:
    logits = x @ w  # [B,S,V]
    if ctx is not None:
        logits = ctx.constrain(logits, "batch", "seq", "tp")
    return logits


def cross_entropy_chunked(unembed_w: jax.Array, x: jax.Array, labels: jax.Array,
                          mask: Optional[jax.Array] = None, ctx=None,
                          chunk: int = 512) -> jax.Array:
    """Mean CE over tokens, computing logits in sequence chunks so the
    [B, S, V] tensor never materialises (vocab up to 257k)."""
    b, s, d = x.shape
    if mask is None:
        mask = jnp.ones((b, s), dtype=jnp.float32)
    chunk = min(chunk, s)
    while s % chunk:
        chunk -= 1
    nb = s // chunk

    def body(carry, inp):
        xc, yc, mc = inp
        logits = (xc @ unembed_w).astype(jnp.float32)
        if ctx is not None:
            logits = ctx.constrain(logits, "batch", "seq", "tp")
        lse = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, yc[..., None], axis=-1)[..., 0]
        loss = jnp.sum((lse - gold) * mc)
        return carry + loss, None

    xs = x.reshape(b, nb, chunk, d).transpose(1, 0, 2, 3)
    ys = labels.reshape(b, nb, chunk).transpose(1, 0, 2)
    ms = mask.reshape(b, nb, chunk).transpose(1, 0, 2)
    total, _ = jax.lax.scan(body, jnp.float32(0.0), (xs, ys, ms))
    return total / jnp.maximum(jnp.sum(mask), 1.0)
