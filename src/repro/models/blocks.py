"""Transformer blocks: GQA attention (+dense MLP or MoE), with KV caches.

Every block exposes three functions:
  ``*_init(key, arch, ...) -> params``          (pytree of arrays)
  ``*_dims(arch, ...) -> roles``                 (matching pytree of logical
                                                  sharding roles, see
                                                  core/xfer.ShardingCtx)
  ``*_apply(arch, params, x, ctx, ...) -> (x, cache')``
"""
from __future__ import annotations

import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.models import layers as L
from repro.quant import quantize_kv


# ---------------------------------------------------------------------------
# KV cache
# ---------------------------------------------------------------------------

def make_kv_cache(arch: ArchConfig, batch: int, length: int, dtype=jnp.bfloat16,
                  window: int = 0, kv_quant: bool = False) -> dict:
    """K/V are stored head-major, ``[B, G, t, D]``: the order the
    attention dots contract in (``layers._attend_block``), so decode reads
    a layer's slab where it lies. ``pos`` is ``[B, t]``.

    ``kv_quant=True`` stores K/V as int8 with per-token f32 scale
    leaves (``k_scale``/``v_scale`` ``[B, G, t, 1]``, one scale per token
    per KV group). The scales are ordinary cache leaves: they splice,
    page and shard structurally alongside the payload they describe."""
    t = min(length, window) if window else length
    g, d = arch.num_kv_heads, arch.head_dim
    cache = {
        "k": jnp.zeros((batch, g, t, d), jnp.int8 if kv_quant else dtype),
        "v": jnp.zeros((batch, g, t, d), jnp.int8 if kv_quant else dtype),
        "pos": jnp.full((batch, t), -1, jnp.int32),  # -1 = invalid slot
        "count": jnp.zeros((), jnp.int32),
    }
    if kv_quant:
        cache["k_scale"] = jnp.zeros((batch, g, t, 1), jnp.float32)
        cache["v_scale"] = jnp.zeros((batch, g, t, 1), jnp.float32)
    return cache


def kv_quantized(cache: dict) -> bool:
    return "k_scale" in cache or "kps" in cache


def _kv_leaves(cache: dict, k: jax.Array, v: jax.Array):
    """Fresh fp K/V → the cache's storage leaves: ``[(name, value)]``
    pairs matching the dict layout (int8 payload + per-token scales for
    quantised caches), in the order ``k``/``v`` come in. Per-token
    quantisation commutes with any gather/slice/pad/transpose of the
    token and head axes, so fill paths can quantise first and reuse their
    fp indexing untouched."""
    if "k_scale" not in cache:
        return [("k", k.astype(cache["k"].dtype)),
                ("v", v.astype(cache["v"].dtype))]
    kq, vq = quantize_kv(k), quantize_kv(v)
    return [("k", kq.q), ("k_scale", kq.scale),
            ("v", vq.q), ("v_scale", vq.scale)]


def _kv_read(cache: dict, name: str, dtype) -> jax.Array:
    """Cache leaf → attention operand (dequantised for int8 caches)."""
    x = cache[name]
    scale = cache.get(f"{name}_scale")
    if scale is None:
        return x
    return (x.astype(jnp.float32) * scale).astype(dtype)


# Paged decode read-path implementation (see serving/pages.py):
# "gather" reads pages with a jnp gather and runs the same attention the
# dense grid runs (bit-exact with it when page_size divides max_len);
# "kernel" dispatches the Pallas paged-attention kernel through
# kernels/ops.py (compiled on TPU, interpreted elsewhere). Overridable for
# experiments, like lm.set_remat_policy.
_PAGED_ATTN_IMPL = "gather"


def set_paged_attention_impl(impl: str) -> None:
    global _PAGED_ATTN_IMPL
    if impl not in ("gather", "kernel"):
        raise ValueError(f"paged attention impl must be 'gather' or "
                         f"'kernel', got {impl!r}")
    _PAGED_ATTN_IMPL = impl


def _paged_decode_attention(ctx, q, k, v, cache: dict,
                            page_table: jax.Array, positions: jax.Array,
                            causal: bool):
    """Decode (S≥1) against a paged pool: write the new KV into the
    slot's frontier page(s), then attend over the slot's page list.

    The gather path materialises ``[B, M·ps, G, D]`` keys through the
    page table, turns them head-major and runs the *same* attention the
    dense grid runs — positions beyond the frontier map to the null page
    or to a not-yet-written tail and are masked exactly like the dense
    grid's stale ``pos=-1`` entries, so the two layouts are bit-identical when
    ``page_size`` divides ``max_len`` (equal kv extent per shard).

    S>1 is the speculative verify: positions are the contiguous range
    ``p..p+k`` per row, every slot of which is (over)written before the
    gathered read, so stale entries from a previous partially-accepted
    verify can never be read. Positions at or beyond the table extent
    (speculative overshoot past a slot's budget) are redirected to the
    null page and masked from the read."""
    b, s = q.shape[0], q.shape[1]
    ps = cache["kp"].shape[-3]
    m = page_table.shape[1]
    t = m * ps
    pos = positions  # [B, S]
    page = jnp.take_along_axis(page_table, jnp.clip(pos // ps, 0, m - 1),
                               axis=1)
    page = jnp.where(pos < t, page, 0)  # overshoot → null page
    slot = pos % ps

    def write(pool, new):
        # inactive slots carry a zeroed (null-page) table row, so their
        # writes collide harmlessly on page 0's garbage
        return pool.at[page, slot].set(new.astype(pool.dtype))

    quant = "kps" in cache
    if quant:
        kq, vq = quantize_kv(k), quantize_kv(v)
        new_cache = {"kp": write(cache["kp"], kq.q),
                     "kps": write(cache["kps"], kq.scale),
                     "vp": write(cache["vp"], vq.q),
                     "vps": write(cache["vps"], vq.scale)}
    else:
        new_cache = {"kp": write(cache["kp"], k), "vp": write(cache["vp"], v)}
    if _PAGED_ATTN_IMPL == "kernel" and s == 1:
        from repro.kernels import ops
        o = ops.paged_attn(q[:, 0], new_cache["kp"], new_cache["vp"],
                           page_table, pos[:, 0] + 1,
                           k_scale=new_cache.get("kps"),
                           v_scale=new_cache.get("vps"))[:, None]
        return o, new_cache

    def flat(name):
        x = new_cache[name][page_table]  # [B, M, ps, G, ·]
        x = x.reshape(b, t, *x.shape[3:])
        if quant:
            s_ = new_cache[f"{name}s"][page_table].reshape(b, t, *x.shape[2:-1] + (1,))
            x = (x.astype(jnp.float32) * s_).astype(q.dtype)
        return x.swapaxes(1, 2)  # [B, G, t, D], the dense grid's order

    # materialised head-major like a layer of the dense grid: a transpose
    # left for the compiler to fold into the attention dots reorders
    # their accumulation, and the two layouts would no longer agree
    # bit for bit
    kf, vf = jax.lax.optimization_barrier((flat("kp"), flat("vp")))
    kv_pos = jnp.broadcast_to(jnp.arange(t, dtype=jnp.int32)[None], (b, t))
    kv_valid = kv_pos <= pos[:, -1][:, None]
    o = L.decode_attention_sharded(ctx, q, kf, vf, positions, kv_pos,
                                   kv_valid, causal=causal)
    return o, new_cache


def _shared_prefix_attention(ctx, q, k, v, cache: dict, positions, seq_lens):
    """Compute-skip suffix prefill: queries at positions ``m..`` attend
    the gathered shared-prefix KV (``pre_k/pre_v``, valid below
    ``pre_len``) concatenated ahead of the fresh suffix KV. The valid
    kv set per query is identical to a full-prompt prefill — padding
    (the gathered region's tail and the suffix bucket's tail) is masked
    to exact zeros, so the suffix hidden states match the full prefill
    bit-for-bit. ``k``/``v`` are the suffix's, head-major; the gathered
    prefix is token-major, as the pool stores it."""
    b, s = q.shape[0], q.shape[1]
    pre_k, pre_v, pre_len = cache["pre_k"], cache["pre_v"], cache["pre_len"]
    lp = pre_k.shape[1]
    k_cat = jnp.concatenate([pre_k.astype(k.dtype).swapaxes(1, 2), k], axis=2)
    v_cat = jnp.concatenate([pre_v.astype(v.dtype).swapaxes(1, 2), v], axis=2)
    pre_pos = jnp.broadcast_to(jnp.arange(lp, dtype=jnp.int32)[None], (b, lp))
    kv_pos = jnp.concatenate([pre_pos, positions], axis=1)
    pre_valid = pre_pos < pre_len[:, None]
    suf_valid = (jnp.arange(s, dtype=jnp.int32)[None]
                 < (seq_lens - pre_len)[:, None])
    kv_valid = jnp.concatenate([pre_valid, suf_valid], axis=1)
    return L.attention_sharded(ctx, q, k_cat, v_cat, positions, kv_pos,
                               kv_valid, causal=True)


def _cache_write(cache: dict, k_new, v_new, pos_new, layer=None):
    """Ring-buffer write of one token (decode step). ``k_new``/``v_new``
    are the fresh ``[B, 1, G, D]`` rows.

    Slot = position mod cache length, **per batch row**, so continuous
    batching can hold requests at different positions in one grid. Every
    row is written, inert ones included.

    With ``layer`` (a traced int32) the leaves are the whole stacked grid
    ``[L, B, G, t, ...]`` that the layer scan carries, and only this
    layer's token row is scattered in: XLA updates the carried buffer in
    place instead of rewriting the layer's slab.

    The K/V head is a scatter index, so each update window is one head's
    ``D`` values: a ``[G, D]`` window across the head-major grid's
    ``(G, t)`` axes would make the compiler lay the grid out token-major
    again, and relayout every layer's slab for the attention dots.
    """
    b, _, g, _ = k_new.shape
    t = cache["pos"].shape[-1]
    rows = jnp.arange(b, dtype=jnp.int32)
    slot = (pos_new[:, 0] % t).astype(jnp.int32)  # [B]
    at = (rows[:, None], jnp.arange(g, dtype=jnp.int32)[None, :],
          slot[:, None])  # [B, G]
    at_pos = (rows, slot)
    if layer is not None:
        at, at_pos = (layer,) + at, (layer,) + at_pos
    out = dict(cache)
    for name, u in _kv_leaves(cache, k_new, v_new):
        out[name] = cache[name].at[at].set(u[:, 0].astype(cache[name].dtype))
    out["pos"] = cache["pos"].at[at_pos].set(pos_new[:, 0])
    out["count"] = (cache["count"] + 1 if layer is None
                    else cache["count"].at[layer].add(1))
    return out


def _grid_rows(ctx, k, v):
    """Fresh K/V rows ``[B, S, G, D]`` in the sharding of the grid they
    are written to (``lm.cache_dims``): whole on every chip, since the
    grid is split by sequence. Left in the projection's split by head,
    the rows make the partitioner gather the scatter's indices instead:
    two more exchanges per layer."""
    if ctx is None:
        return k, v
    return (ctx.constrain(k, "batch", None, None, None),
            ctx.constrain(v, "batch", None, None, None))


def ragged_decode_applies(ctx, grid: dict) -> bool:
    """Whether single-token decode over the stacked dense ``grid`` runs
    the ragged Pallas kernel (``kernels/ragged_decode_attention.py``),
    which reads only each row's live K/V blocks: the program is built
    for a TPU, on one device (sequence- or batch-sharded grids keep the
    flash-decoding path), and the grid is unquantised with a length the
    kernel can block and a head size its block copies can tile (a
    multiple of 128). Causal attention with no prefix-LM override is the
    caller's part of the test."""
    from repro.kernels import ops
    from repro.kernels.ragged_decode_attention import block_size
    one_device = ctx is None or ctx.mesh is None or ctx.mesh.size == 1
    return (ops._on_tpu() and one_device and "k_scale" not in grid
            and block_size(grid["pos"].shape[-1]) is not None
            and grid["k"].shape[-1] % 128 == 0)


def _layer_view(cache: dict, layer) -> dict:
    """Layer ``layer`` of a stacked grid: the ``[B, G, t, ·]`` and
    ``[B, t]`` leaves that attention reads."""
    return {name: jax.lax.dynamic_index_in_dim(x, layer, keepdims=False)
            for name, x in cache.items() if name != "count"}


def _cache_write_many(cache: dict, k_new, v_new, pos_new):
    """Append-mode write of S tokens per row (speculative draft/verify).

    Non-windowed caches only: the slot is the position itself (no ring
    wrap — a wrap inside one multi-token write would clobber live
    context). Writes at positions beyond the cache extent are dropped
    (OOB scatter with ``mode="drop"``); a slot's stale entries above its
    accept frontier always store a position greater than any future
    query position below them, and every verify rewrites the full
    ``p..p+k`` range before the in-step read, so stale data is never
    attended. ``k_new``/``v_new`` are ``[B, S, G, D]``; the K/V head is
    a scatter index, as in :func:`_cache_write`.
    """
    b, s = pos_new.shape
    g = k_new.shape[2]
    t = cache["pos"].shape[-1]
    rows = jnp.arange(b, dtype=jnp.int32)[:, None]
    slot = jnp.where(pos_new >= 0, pos_new, t)  # negative → dropped too
    heads = jnp.arange(g, dtype=jnp.int32)[None, None, :]
    out = dict(cache)
    for name, u in _kv_leaves(cache, k_new, v_new):
        out[name] = cache[name].at[rows[..., None], heads, slot[..., None]].set(
            u.astype(cache[name].dtype), mode="drop")
    out["pos"] = cache["pos"].at[rows, slot].set(pos_new, mode="drop")
    out["count"] = cache["count"] + s
    return out


# ---------------------------------------------------------------------------
# attention block (pre-norm attn + pre-norm MLP/MoE)
# ---------------------------------------------------------------------------

def attn_init(key, arch: ArchConfig, dtype=jnp.float32, moe: bool = False,
              d_ff: Optional[int] = None, cross: bool = False) -> dict:
    ks = jax.random.split(key, 12)
    d, qd, kvd = arch.d_model, arch.q_dim, arch.kv_dim
    p = {
        "ln1": jnp.zeros((d,), dtype),
        "wq": L.dense_init(ks[0], (d, qd), 0, dtype),
        "wk": L.dense_init(ks[1], (d, kvd), 0, dtype),
        "wv": L.dense_init(ks[2], (d, kvd), 0, dtype),
        "wo": L.dense_init(ks[3], (qd, d), 0, dtype),
    }
    if arch.qkv_bias:
        p["bq"] = jnp.zeros((qd,), dtype)
        p["bk"] = jnp.zeros((kvd,), dtype)
        p["bv"] = jnp.zeros((kvd,), dtype)
    if cross:
        p["ln_x"] = jnp.zeros((d,), dtype)
        p["xwq"] = L.dense_init(ks[8], (d, qd), 0, dtype)
        p["xwk"] = L.dense_init(ks[9], (d, kvd), 0, dtype)
        p["xwv"] = L.dense_init(ks[10], (d, kvd), 0, dtype)
        p["xwo"] = L.dense_init(ks[11], (qd, d), 0, dtype)
    ff = d_ff if d_ff is not None else arch.d_ff
    if ff and arch.mlp != "none":
        p["ln2"] = jnp.zeros((d,), dtype)
        if moe:
            p["router"] = L.dense_init(ks[4], (d, arch.num_experts), 0, dtype)
            ks2 = jax.random.split(ks[5], 3)
            eff = arch.moe_d_ff or arch.d_ff
            gates = arch.mlp in ("swiglu", "geglu")
            p["moe"] = {
                "w_gate": L.dense_init(ks2[0], (arch.num_experts, d, eff), 1, dtype),
                "w_up": L.dense_init(ks2[1], (arch.num_experts, d, eff), 1, dtype),
                "w_down": L.dense_init(ks2[2], (arch.num_experts, eff, d), 1, dtype),
            } if gates else {
                "w_up": L.dense_init(ks2[1], (arch.num_experts, d, eff), 1, dtype),
                "w_down": L.dense_init(ks2[2], (arch.num_experts, eff, d), 1, dtype),
            }
            if arch.num_shared_experts:
                p["shared"] = L.mlp_init(ks[6], d, (arch.moe_d_ff or arch.d_ff) * arch.num_shared_experts,
                                         arch.mlp, dtype)
        else:
            p["mlp"] = L.mlp_init(ks[7], d, ff, arch.mlp, dtype)
    return p


def attn_dims(arch: ArchConfig, moe: bool = False, d_ff: Optional[int] = None,
              cross: bool = False) -> dict:
    d = {
        "ln1": (None,),
        "wq": ("xfer", "tp"), "wk": ("xfer", "tp"), "wv": ("xfer", "tp"),
        "wo": ("tp", "xfer"),
    }
    if arch.qkv_bias:
        d["bq"] = ("tp",)
        d["bk"] = ("tp",)
        d["bv"] = ("tp",)
    if cross:
        d.update({"ln_x": (None,), "xwq": ("xfer", "tp"), "xwk": ("xfer", "tp"),
                  "xwv": ("xfer", "tp"), "xwo": ("tp", "xfer")})
    ff = d_ff if d_ff is not None else arch.d_ff
    if ff and arch.mlp != "none":
        d["ln2"] = (None,)
        if moe:
            d["router"] = ("xfer", None)
            gates = arch.mlp in ("swiglu", "geglu")
            d["moe"] = ({"w_gate": ("ep", "xfer", None), "w_up": ("ep", "xfer", None),
                         "w_down": ("ep", None, "xfer")} if gates else
                        {"w_up": ("ep", "xfer", None), "w_down": ("ep", None, "xfer")})
            if arch.num_shared_experts:
                d["shared"] = L.mlp_dims(arch.mlp)
        else:
            d["mlp"] = L.mlp_dims(arch.mlp)
    return d


def _project_qkv(arch: ArchConfig, p: dict, h: jax.Array, ctx, prefix: str = "w"):
    b, s, _ = h.shape
    q = h @ p[f"{prefix}q"]
    k = h @ p[f"{prefix}k"]
    v = h @ p[f"{prefix}v"]
    if arch.qkv_bias and prefix == "w":
        q, k, v = q + p["bq"], k + p["bk"], v + p["bv"]
    q = q.reshape(b, s, arch.num_heads, arch.head_dim)
    k = k.reshape(b, s, arch.num_kv_heads, arch.head_dim)
    v = v.reshape(b, s, arch.num_kv_heads, arch.head_dim)
    if ctx is not None:
        q = ctx.constrain(q, "batch", "seq", "tp", None)
        k = ctx.constrain(k, "batch", "seq", "tp", None)
        v = ctx.constrain(v, "batch", "seq", "tp", None)
    return q, k, v


def _ring_exact_fill(cache: dict, k, v, seq_lens: jax.Array, s: int) -> dict:
    """Length-exact prefill fill of a (possibly windowed) ring cache.

    Index ``i`` of a ring of size ``t`` must hold the newest position
    ``p ≡ i (mod t)`` below the true length — i.e. the last
    ``min(len, t)`` positions of the *unpadded* prompt, not of the padded
    bucket. The plain suffix fill keeps the last ``t`` positions of the
    padded sequence instead, which evicts real context whenever the
    prompt is shorter than the bucket; per-row gather by true length
    makes the fill identical for every padded length ≥ the prompt.
    ``k``/``v`` are head-major ``[B, G, s, D]``.
    """
    t = cache["pos"].shape[-1]
    ring = jnp.arange(t)[None, :]  # [1, t]
    last = seq_lens[:, None] - 1
    pos = last - jnp.mod(last - ring, t)  # [B, t], pos ≡ ring (mod t)
    valid = pos >= 0
    idx = jnp.clip(pos, 0, s - 1)
    out = dict(cache)
    for name, u in _kv_leaves(cache, k, v):
        out[name] = jnp.take_along_axis(
            u, idx[:, None, :, None], axis=2).astype(cache[name].dtype)
    out["pos"] = jnp.where(valid, pos, -1)
    out["count"] = jnp.asarray(s, jnp.int32)
    return out


def attn_apply(arch: ArchConfig, p: dict, x: jax.Array, ctx=None, *,
               positions: jax.Array, cache: Optional[dict] = None,
               window: int = 0, prefix_len: Optional[jax.Array] = None,
               causal: bool = True, moe: bool = False,
               enc: Optional[jax.Array] = None,
               enc_lens: Optional[jax.Array] = None,
               seq_lens: Optional[jax.Array] = None,
               page_table: Optional[jax.Array] = None,
               deterministic_router: bool = True,
               append: bool = False,
               layer: Optional[jax.Array] = None
               ) -> Tuple[jax.Array, Optional[dict]]:
    """Self-attention + MLP/MoE block.

    full mode (cache is None or being filled): x is [B,S,D];
    decode mode (cache with count>0 and S==1): ring-buffer cache update.
    With ``layer`` (single-token decode on the dense grid, from the layer
    scan in ``models.lm.forward``) ``cache`` is the whole stacked grid the
    scan carries: the token's K/V row is written in place at
    ``[layer, row, :, pos % t]`` and attention reads layer ``layer`` of
    the updated grid — the same values the per-layer write gives. Where
    :func:`ragged_decode_applies` (one TPU device, unquantised grid) and
    attention is causal with no prefix, that read is the ragged Pallas
    kernel, which copies in only each row's live K/V blocks.

    The grid is head-major (:func:`make_kv_cache`), and so is every K/V
    operand of the attention core; freshly projected K/V (prefill, the
    shared-prefix suffix) is transposed once before it is attended or
    stored.

    ``append=True`` (speculative decoding) treats a filled cache as an
    append target for S≥1 fresh positions per row instead of a prefill
    fill: the new KV is scattered at its positions (non-windowed caches
    only — see :func:`_cache_write_many`) and attention runs over the
    whole cache exactly like the decode path. The paged pool handles
    append natively (frontier writes are position-addressed already).

    ``seq_lens`` ([B] int32) marks the true per-row length of a
    right-padded batch: keys at-or-beyond it are masked out of attention
    (only observable for non-causal use — causal masking already hides a
    padded tail from valid queries) and, for windowed caches, the prefill
    fill gathers the last ``window`` positions *before* the true length
    instead of the padded bucket's suffix (see :func:`_ring_exact_fill`).

    Paged modes (``serving.pages``), keyed by the cache dict's shape:
    a pool pair ``{"kp", "vp"}`` plus ``page_table`` ([B, M] int32)
    selects the paged decode path; a gathered shared-prefix block
    ``{"pre_k", "pre_v", "pre_len"}`` selects the compute-skip suffix
    prefill, whose returned cache is the dense suffix row the scheduler
    splices into pages.
    """
    b, s, d = x.shape
    h = L.rms_norm(x, p["ln1"])
    q, k, v = _project_qkv(arch, p, h, ctx)
    q = L.rope(q, positions, arch.rope_theta)
    k = L.rope(k, positions, arch.rope_theta)
    kv_valid_in = (jnp.arange(s)[None, :] < seq_lens[:, None]
                   if seq_lens is not None and s > 1 else None)

    new_cache = None
    if cache is not None and "kp" in cache:
        if page_table is None:
            raise ValueError("paged KV pool given without a page_table")
        o, new_cache = _paged_decode_attention(ctx, q, k, v, cache,
                                               page_table, positions, causal)
    elif cache is not None and "pre_k" in cache:
        kh, vh = k.swapaxes(1, 2), v.swapaxes(1, 2)
        o = _shared_prefix_attention(ctx, q, kh, vh, cache, positions,
                                     seq_lens)
        new_cache = {"k": kh, "v": vh, "pos": positions,
                     "count": jnp.asarray(s, jnp.int32)}
    elif cache is not None and append:
        new_cache = _cache_write_many(cache, *_grid_rows(ctx, k, v),
                                      positions)
        kv_valid = new_cache["pos"] >= 0
        o = L.decode_attention_sharded(ctx, q,
                                       _kv_read(new_cache, "k", q.dtype),
                                       _kv_read(new_cache, "v", q.dtype),
                                       positions, new_cache["pos"], kv_valid,
                                       causal=causal, window=window,
                                       prefix_len=prefix_len)
    elif cache is not None and s == 1:
        new_cache = _cache_write(cache, *_grid_rows(ctx, k, v), positions,
                                 layer)
        if (layer is not None and causal and prefix_len is None
                and ragged_decode_applies(ctx, new_cache)):
            # reads layer `layer` of the written grid where it lies, only
            # each row's live blocks
            from repro.kernels import ops
            o = ops.ragged_decode_attn(q[:, 0], new_cache["k"],
                                       new_cache["v"], new_cache["pos"],
                                       layer, positions[:, 0],
                                       window=window)[:, None]
        else:
            view = (new_cache if layer is None
                    else _layer_view(new_cache, layer))
            kv_valid = view["pos"] >= 0
            o = L.decode_attention_sharded(ctx, q,
                                           _kv_read(view, "k", q.dtype),
                                           _kv_read(view, "v", q.dtype),
                                           positions, view["pos"], kv_valid,
                                           causal=causal, window=window,
                                           prefix_len=prefix_len)
    else:
        kh, vh = k.swapaxes(1, 2), v.swapaxes(1, 2)  # [B, G, S, D]
        o = L.attention_sharded(ctx, q, kh, vh, positions, positions,
                                kv_valid_in, causal=causal, window=window,
                                prefix_len=prefix_len)
        if cache is not None:  # prefill: fill the cache with the suffix
            t = cache["pos"].shape[-1]
            if seq_lens is not None and window:
                new_cache = _ring_exact_fill(cache, kh, vh, seq_lens, s)
            elif s >= t:
                new_cache = dict(cache)
                for name, u in _kv_leaves(cache, kh, vh):
                    new_cache[name] = u[:, :, -t:]
                new_cache["pos"] = positions[:, -t:]
                new_cache["count"] = jnp.asarray(s, jnp.int32)
            else:
                pad = t - s
                new_cache = dict(cache)
                for name, u in _kv_leaves(cache, kh, vh):
                    new_cache[name] = jnp.pad(
                        u, ((0, 0), (0, 0), (0, pad), (0, 0)))
                new_cache["pos"] = jnp.pad(positions, ((0, 0), (0, pad)),
                                           constant_values=-1)
                new_cache["count"] = jnp.asarray(s, jnp.int32)
    o = o.reshape(b, s, arch.q_dim)
    x = x + o @ p["wo"]
    if ctx is not None:
        x = ctx.constrain(x, "batch", "sp", None)

    if enc is not None:
        x = cross_attn_apply(arch, p, x, enc, ctx, enc_lens=enc_lens)
        if ctx is not None:
            x = ctx.constrain(x, "batch", "sp", None)

    if "ln2" in p:
        h = L.rms_norm(x, p["ln2"])
        if moe:
            y = moe_apply(arch, p, h, ctx)
        else:
            y = L.mlp_apply(p["mlp"], h, arch.mlp, ctx)
        x = x + y
        if ctx is not None:
            x = ctx.constrain(x, "batch", "sp", None)
    return x, new_cache


# ---------------------------------------------------------------------------
# cross-attention (enc-dec decoder)
# ---------------------------------------------------------------------------

def cross_attn_apply(arch: ArchConfig, p: dict, x: jax.Array, enc: jax.Array,
                     ctx=None, enc_lens: Optional[jax.Array] = None) -> jax.Array:
    """Decoder cross-attention over encoder output. ``enc_lens`` ([B]
    int32) masks right-padded encoder positions out of the keys — the
    per-slot encoder-length mask the serving runtime threads through
    ``DecodeState`` (padded ``enc_out`` rows contribute exactly zero)."""
    b, s, d = x.shape
    t = enc.shape[1]
    h = L.rms_norm(x, p["ln_x"])
    q = (h @ p["xwq"]).reshape(b, s, arch.num_heads, arch.head_dim)
    k = (enc @ p["xwk"]).reshape(b, t, arch.num_kv_heads, arch.head_dim)
    v = (enc @ p["xwv"]).reshape(b, t, arch.num_kv_heads, arch.head_dim)
    if ctx is not None:
        q = ctx.constrain(q, "batch", "seq", "tp", None)
        k = ctx.constrain(k, "batch", "seq", "tp", None)
        v = ctx.constrain(v, "batch", "seq", "tp", None)
    qp = jnp.zeros((b, s), jnp.int32)
    kp = jnp.zeros((b, t), jnp.int32)
    kv_valid = (jnp.arange(t)[None, :] < enc_lens[:, None]
                if enc_lens is not None else None)
    o = L.attention(q, k.swapaxes(1, 2), v.swapaxes(1, 2), qp, kp, kv_valid,
                    causal=False)
    return x + o.reshape(b, s, arch.q_dim) @ p["xwo"]


# ---------------------------------------------------------------------------
# MoE (capacity-based, sort + scatter dispatch — GShard/Switch style)
# ---------------------------------------------------------------------------

def moe_apply(arch: ArchConfig, p: dict, h: jax.Array, ctx=None,
              capacity_factor: float = 0.0) -> jax.Array:
    """Dispatch wrapper: explicit shard_map all-to-all when the mesh allows
    (§Perf iteration: GSPMD's handling of the scatter/gather dispatch
    degenerates into full-buffer all-gathers — observed 185 s of collective
    time on deepseek train_4k; the explicit EP path moves only the routed
    tokens, twice, over the model axis)."""
    if ctx is not None and ctx.mesh is not None and h.shape[1] > 1:
        ep_axes = ctx.plan.ep_axes or ctx.plan.tp_axes
        ep = ctx.plan.degree(ep_axes)
        if (len(ep_axes) == 1 and ep > 1 and arch.num_experts % ep == 0):
            return _moe_apply_sharded(arch, p, h, ctx, ep_axes[0],
                                      capacity_factor or arch.moe_capacity_factor)
    return _moe_apply_dense(arch, p, h, ctx, capacity_factor)


def _local_dispatch(arch: ArchConfig, hf: jax.Array, router: jax.Array,
                    cap: int):
    """Per-device top-k routing into an [E, cap, D] buffer. Returns
    (buffer, combine metadata)."""
    t, d = hf.shape
    e, k = arch.num_experts, arch.top_k
    logits = (hf @ router).astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    gate_vals, idx = jax.lax.top_k(probs, k)
    gate_vals = gate_vals / jnp.maximum(gate_vals.sum(-1, keepdims=True), 1e-9)
    eid = idx.reshape(-1)
    order = jnp.argsort(eid, stable=True)
    eid_s = eid[order]
    rank = jnp.arange(t * k) - jnp.searchsorted(eid_s, eid_s, side="left")
    keep = rank < cap
    dest = jnp.where(keep, eid_s * cap + rank, e * cap)
    src_tok = order // k
    buf = jnp.zeros((e * cap, d), hf.dtype).at[dest].set(hf[src_tok], mode="drop")
    meta = (dest, keep, src_tok, gate_vals.reshape(-1)[order])
    return buf.reshape(e, cap, d), meta


def _local_combine(meta, out: jax.Array, t: int) -> jax.Array:
    dest, keep, src_tok, gv_sorted = meta
    e_cap, d = out.reshape(-1, out.shape[-1]).shape[0], out.shape[-1]
    out_flat = out.reshape(-1, d)
    contrib = jnp.where(keep[:, None], out_flat[jnp.minimum(dest, e_cap - 1)], 0.0)
    return jnp.zeros((t, d), out.dtype).at[src_tok].add(
        (contrib * gv_sorted[:, None]).astype(out.dtype))


def _expert_ffn(arch: ArchConfig, moe_p: dict, buf: jax.Array) -> jax.Array:
    if "w_gate" in moe_p:
        act = jax.nn.silu if arch.mlp == "swiglu" else (
            lambda u: jax.nn.gelu(u, approximate=True))
        inner = act(jnp.einsum("ecd,edf->ecf", buf, moe_p["w_gate"])) * \
            jnp.einsum("ecd,edf->ecf", buf, moe_p["w_up"])
    else:
        inner = jnp.square(jax.nn.relu(jnp.einsum("ecd,edf->ecf", buf, moe_p["w_up"])))
    return jnp.einsum("ecf,efd->ecd", inner, moe_p["w_down"])


def _moe_apply_sharded(arch: ArchConfig, p: dict, h: jax.Array, ctx,
                       axis: str, capacity_factor: float) -> jax.Array:
    """GShard-style EP: local top-k dispatch → all-to-all over the expert
    axis → local expert FFNs → reverse all-to-all → local combine."""
    from jax.sharding import PartitionSpec as P
    from jax import shard_map

    b, s, d = h.shape
    e, k = arch.num_experts, arch.top_k
    wsd = max(ctx.plan.degree(ctx.plan.batch_axes + ctx.plan.seq_axes), 1)
    t_loc = max(b * s // wsd, 1)
    cap = max(int(math.ceil(t_loc * k / e * capacity_factor)), 1)

    moe_p = p["moe"]
    has_gate = "w_gate" in moe_p

    def local(h_loc, router, *weights):
        bl, sl, _ = h_loc.shape
        hf = h_loc.reshape(bl * sl, d)
        buf, meta = _local_dispatch(arch, hf, router, cap)  # [E, cap, D]
        # route: every device sends each expert-owner its slice of tokens
        buf = jax.lax.all_to_all(buf, axis, split_axis=0, concat_axis=1,
                                 tiled=True)  # [E/ep, cap*ep, D]
        names = ("w_gate", "w_up", "w_down") if has_gate else ("w_up", "w_down")
        out = _expert_ffn(arch, dict(zip(names, weights)), buf)
        out = jax.lax.all_to_all(out, axis, split_axis=1, concat_axis=0,
                                 tiled=True)  # [E, cap, D]
        y = _local_combine(meta, out, bl * sl)
        return y.reshape(bl, sl, d)

    hs = ctx.spec(h.shape, ("batch", "seq", None))
    rs = P(*([None] * p["router"].ndim))
    # expert weights: E sharded over the EP axis, other dims gathered at entry
    ws = P(axis, None, None)
    wnames = ("w_gate", "w_up", "w_down") if has_gate else ("w_up", "w_down")
    kwargs = dict(mesh=ctx.mesh, in_specs=(hs, rs) + (ws,) * len(wnames),
                  out_specs=hs)
    fn = shard_map(local, check_vma=False, **kwargs)
    y = fn(h, p["router"], *(moe_p[n] for n in wnames))
    if "shared" in p:
        y = y + L.mlp_apply(p["shared"], h, arch.mlp, ctx)
    return y


def _moe_apply_dense(arch: ArchConfig, p: dict, h: jax.Array, ctx=None,
                     capacity_factor: float = 0.0) -> jax.Array:
    capacity_factor = capacity_factor or arch.moe_capacity_factor
    b, s, d = h.shape
    t = b * s
    e, k = arch.num_experts, arch.top_k
    hf = h.reshape(t, d)

    logits = (hf @ p["router"]).astype(jnp.float32)  # [T,E]
    probs = jax.nn.softmax(logits, axis=-1)
    gate_vals, idx = jax.lax.top_k(probs, k)  # [T,k]
    gate_vals = gate_vals / jnp.maximum(gate_vals.sum(-1, keepdims=True), 1e-9)

    cap = max(int(math.ceil(t * k / e * capacity_factor)), 1)
    eid = idx.reshape(-1)  # [T*k]
    order = jnp.argsort(eid, stable=True)
    eid_s = eid[order]
    # rank within expert group
    first = jnp.searchsorted(eid_s, eid_s, side="left")
    rank = jnp.arange(t * k) - first
    keep = rank < cap
    dest = jnp.where(keep, eid_s * cap + rank, e * cap)  # overflow -> dropped
    src_tok = order // k

    buf = jnp.zeros((e * cap, d), h.dtype).at[dest].set(hf[src_tok], mode="drop")
    buf = buf.reshape(e, cap, d)
    if ctx is not None:
        buf = ctx.constrain(buf, "ep", None, None)

    if "w_gate" in p["moe"]:
        act = jax.nn.silu if arch.mlp == "swiglu" else (lambda u: jax.nn.gelu(u, approximate=True))
        inner = act(jnp.einsum("ecd,edf->ecf", buf, p["moe"]["w_gate"])) * \
            jnp.einsum("ecd,edf->ecf", buf, p["moe"]["w_up"])
    else:
        inner = jnp.square(jax.nn.relu(jnp.einsum("ecd,edf->ecf", buf, p["moe"]["w_up"])))
    out = jnp.einsum("ecf,efd->ecd", inner, p["moe"]["w_down"])
    if ctx is not None:
        out = ctx.constrain(out, "ep", None, None)

    out_flat = out.reshape(e * cap, d)
    contrib = jnp.where(keep[:, None], out_flat[jnp.minimum(dest, e * cap - 1)], 0.0)
    gv_sorted = gate_vals.reshape(-1)[order]
    y = jnp.zeros((t, d), h.dtype).at[src_tok].add(
        (contrib * gv_sorted[:, None]).astype(h.dtype))
    y = y.reshape(b, s, d)
    if "shared" in p:
        y = y + L.mlp_apply(p["shared"], h, arch.mlp, ctx)
    return y
