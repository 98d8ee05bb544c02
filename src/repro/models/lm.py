"""Unified decoder-only LM stack covering dense / MoE / hybrid / SSM / VLM.

The layer stack is organised as
  prefix  — unrolled leading layers (e.g. DeepSeekMoE's dense first layer)
  body    — `repeats` copies of the arch's block pattern, stacked and
            scanned (keeps HLO size O(pattern), not O(layers))
  suffix  — unrolled trailing layers (pattern remainder, e.g.
            RecurrentGemma's 26 = 8×(r,r,a) + (r,r))

Under an XFER plan the body scan prefetches the next repeat's weights one
step ahead (core.xfer.scan_layers) — the paper's double-buffering at layer
granularity.
"""
from __future__ import annotations

from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.core.xfer import ShardingCtx, scan_layers
from repro.models import blocks as B
from repro.models import layers as L
from repro.models import recurrent as R

PyTree = Any

# Remat policy (§Perf iteration 4): save no-batch-dim dot outputs (layer
# weights' products) but recompute everything else — cheaper backward
# recompute traffic than nothing_saveable at ~1 activation per matmul of
# extra residency. Overridable for experiments.
_REMAT_POLICY = jax.checkpoint_policies.nothing_saveable


def set_remat_policy(policy):
    global _REMAT_POLICY
    _REMAT_POLICY = policy


def _pattern(arch: ArchConfig) -> Tuple[str, ...]:
    return arch.block_pattern or ("attn",)


def stack_structure(arch: ArchConfig) -> Tuple[List[str], int, List[str]]:
    """(prefix kinds, body repeats, suffix kinds)."""
    pat = _pattern(arch)
    n = arch.num_layers
    prefix = []
    if arch.family == "moe" and arch.first_dense_layers:
        prefix = ["attn"] * arch.first_dense_layers  # dense MLP layers
        n -= arch.first_dense_layers
    repeats, rem = divmod(n, len(pat))
    suffix = list(pat[:rem])
    return prefix, repeats, suffix


def _block_init(kind: str, key, arch: ArchConfig, dtype, moe: bool):
    if kind == "attn":
        return B.attn_init(key, arch, dtype, moe=moe,
                           d_ff=arch.d_ff if not moe else None)
    if kind == "rglru":
        return R.rglru_init(key, arch, dtype)
    if kind == "mlstm":
        return R.mlstm_init(key, arch, dtype)
    if kind == "slstm":
        return R.slstm_init(key, arch, dtype)
    raise ValueError(kind)


def _block_dims(kind: str, arch: ArchConfig, moe: bool):
    if kind == "attn":
        return B.attn_dims(arch, moe=moe, d_ff=arch.d_ff if not moe else None)
    if kind == "rglru":
        return R.rglru_dims(arch)
    if kind == "mlstm":
        return R.mlstm_dims(arch)
    if kind == "slstm":
        return R.slstm_dims(arch)
    raise ValueError(kind)


def _block_cache(kind: str, arch: ArchConfig, batch: int, length: int, dtype,
                 kv_quant: bool = False):
    if kind == "attn":
        win = arch.window if arch.family == "hybrid" else 0
        return B.make_kv_cache(arch, batch, length, dtype, window=win,
                               kv_quant=kv_quant)
    if kind == "rglru":
        return R.make_rglru_state(arch, batch, dtype)
    if kind == "mlstm":
        return R.make_mlstm_state(arch, batch)
    if kind == "slstm":
        return R.make_slstm_state(arch, batch)
    raise ValueError(kind)


def _block_apply(kind: str, arch: ArchConfig, p: PyTree, x, ctx, *,
                 positions, cache, prefix_len, moe: bool, seq_lens=None,
                 page_table=None, append: bool = False, layer=None):
    """``layer``: ``cache`` is the body's stacked state carried by the
    decode scan, and this block is layer ``layer`` of it."""
    if kind == "attn":
        win = arch.window if arch.family == "hybrid" else 0
        return B.attn_apply(arch, p, x, ctx, positions=positions, cache=cache,
                            window=win, prefix_len=prefix_len, moe=moe,
                            seq_lens=seq_lens, page_table=page_table,
                            append=append, layer=layer)
    if layer is not None:
        # recurrent state is small: slice this layer's, write it back whole
        x, state = _block_apply(
            kind, arch, p, x, ctx, positions=positions, prefix_len=prefix_len,
            moe=moe, seq_lens=seq_lens,
            cache=jax.tree.map(
                lambda c: jax.lax.dynamic_index_in_dim(c, layer, keepdims=False),
                cache))
        return x, jax.tree.map(
            lambda c, u: jax.lax.dynamic_update_index_in_dim(c, u, layer, 0),
            cache, state)
    if kind == "rglru":
        return R.rglru_apply(arch, p, x, ctx, state=cache, seq_lens=seq_lens)
    if kind == "mlstm":
        return R.mlstm_apply(arch, p, x, ctx, state=cache, seq_lens=seq_lens)
    if kind == "slstm":
        return R.slstm_apply(arch, p, x, ctx, state=cache, seq_lens=seq_lens)
    raise ValueError(kind)


# ---------------------------------------------------------------------------
# params / dims / caches
# ---------------------------------------------------------------------------

def init_params(arch: ArchConfig, key, dtype=jnp.float32) -> Dict:
    prefix, repeats, suffix = stack_structure(arch)
    moe = arch.family == "moe"
    keys = jax.random.split(key, 4 + len(prefix) + len(suffix))
    params: Dict[str, Any] = {
        "embed": L.dense_init(keys[0], (arch.vocab_size, arch.d_model), 1, dtype),
        "final_norm": jnp.zeros((arch.d_model,), dtype),
    }
    if not arch.tie_embeddings:
        params["unembed"] = L.dense_init(keys[1], (arch.d_model, arch.vocab_size), 0, dtype)
    for i, kind in enumerate(prefix):
        params[f"prefix{i}"] = _block_init(kind, keys[4 + i], arch, dtype, moe=False)
    pat = _pattern(arch)
    if repeats:
        def one_repeat(k):
            ks = jax.random.split(k, len(pat))
            return {f"b{j}_{kind}": _block_init(kind, ks[j], arch, dtype, moe)
                    for j, kind in enumerate(pat)}
        params["body"] = jax.vmap(one_repeat)(jax.random.split(keys[2], repeats))
    for i, kind in enumerate(suffix):
        params[f"suffix{i}"] = _block_init(kind, keys[4 + len(prefix) + i], arch, dtype, moe)
    return params


def param_dims(arch: ArchConfig) -> Dict:
    """Logical sharding roles matching init_params' tree."""
    prefix, repeats, suffix = stack_structure(arch)
    moe = arch.family == "moe"
    dims: Dict[str, Any] = {
        "embed": ("tp", "xfer"),
        "final_norm": (None,),
    }
    if not arch.tie_embeddings:
        dims["unembed"] = ("xfer", "tp")
    for i, kind in enumerate(prefix):
        dims[f"prefix{i}"] = _block_dims(kind, arch, moe=False)
    pat = _pattern(arch)
    if repeats:
        body = {f"b{j}_{kind}": _block_dims(kind, arch, moe)
                for j, kind in enumerate(pat)}
        dims["body"] = jax.tree.map(lambda d: (None,) + tuple(d), body,
                                    is_leaf=lambda x: isinstance(x, tuple))
    for i, kind in enumerate(suffix):
        dims[f"suffix{i}"] = _block_dims(kind, arch, moe)
    return dims


def body_dims_unstacked(arch: ArchConfig) -> Dict:
    pat = _pattern(arch)
    moe = arch.family == "moe"
    return {f"b{j}_{kind}": _block_dims(kind, arch, moe)
            for j, kind in enumerate(pat)}


def make_caches(arch: ArchConfig, batch: int, length: int, dtype=jnp.bfloat16,
                kv_quant: bool = False) -> Dict:
    prefix, repeats, suffix = stack_structure(arch)
    caches: Dict[str, Any] = {}
    for i, kind in enumerate(prefix):
        caches[f"prefix{i}"] = _block_cache(kind, arch, batch, length, dtype,
                                            kv_quant)
    pat = _pattern(arch)
    if repeats:
        def stack(*ts):
            return jnp.stack(ts) if repeats > 1 else ts[0][None]
        one = {f"b{j}_{kind}": _block_cache(kind, arch, batch, length, dtype,
                                            kv_quant)
               for j, kind in enumerate(pat)}
        caches["body"] = jax.tree.map(
            lambda leaf: jnp.broadcast_to(leaf[None], (repeats,) + leaf.shape), one)
    for i, kind in enumerate(suffix):
        caches[f"suffix{i}"] = _block_cache(kind, arch, batch, length, dtype,
                                            kv_quant)
    return caches


def cache_dims(arch: ArchConfig, kv_quant: bool = False) -> Dict:
    """Sharding roles for cache trees (head-major K/V ``[B, G, T, D]``:
    batch, and tp over the sequence)."""
    prefix, repeats, suffix = stack_structure(arch)

    def kv_roles(kind):
        if kind == "attn":
            # cache sharded over its sequence dim (flash-decoding
            # partials; kv-head counts rarely divide the TP degree)
            roles = {"k": ("batch", None, "tp", None),
                     "v": ("batch", None, "tp", None),
                     "pos": ("batch", "tp"), "count": ()}
            if kv_quant:
                # scales ride the same batch/length layout as the payload
                roles["k_scale"] = roles["k"][:-1] + (None,)
                roles["v_scale"] = roles["v"][:-1] + (None,)
            return roles
        if kind == "rglru":
            return {"h": ("batch", "tp"), "conv": ("batch", None, "tp")}
        if kind == "mlstm":
            return {"C": ("batch", "tp", None, None), "n": ("batch", "tp", None),
                    "m": ("batch", "tp")}
        return {"c": ("batch", "tp"), "n": ("batch", "tp"), "h": ("batch", "tp"),
                "m": ("batch", "tp")}

    dims: Dict[str, Any] = {}
    for i, kind in enumerate(prefix):
        dims[f"prefix{i}"] = kv_roles(kind)
    pat = _pattern(arch)
    if repeats:
        body = {f"b{j}_{kind}": kv_roles(kind) for j, kind in enumerate(pat)}
        dims["body"] = jax.tree.map(lambda d: (None,) + tuple(d), body,
                                    is_leaf=lambda x: isinstance(x, tuple))
    for i, kind in enumerate(suffix):
        dims[f"suffix{i}"] = kv_roles(kind)
    return dims


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------

def forward(arch: ArchConfig, params: Dict, tokens: jax.Array,
            ctx: Optional[ShardingCtx] = None, *,
            caches: Optional[Dict] = None,
            positions: Optional[jax.Array] = None,
            prefix_embeds: Optional[jax.Array] = None,
            seq_lens: Optional[jax.Array] = None,
            page_table: Optional[jax.Array] = None,
            remat: bool = False,
            append: bool = False) -> Tuple[jax.Array, Optional[Dict]]:
    """Returns (hidden [B,S,D] after final norm, updated caches or None).

    Single-token decode on the dense grid (``caches`` given, S == 1, not
    ``append``, no ``page_table``) carries the body's stacked grid through
    the layer scan: each layer scatters its new K/V row into the carried
    grid in place and attends over its layer of it (recurrent state is
    sliced and written back whole). Prefill, append and paged forwards
    scan the grid as per-layer inputs and outputs.

    ``append=True`` (speculative decoding): ``caches`` is a *filled*
    grid and the S fresh tokens per row are scattered at ``positions``
    instead of re-filling from scratch — attention-only archs, see
    ``blocks.attn_apply``.

    ``prefix_embeds``: modality-frontend stub output ([B, P, D]) prepended
    to the token embeddings (vlm/audio archs); attended bidirectionally.

    ``seq_lens`` ([B] int32, prefix included): true per-row length of a
    right-padded batch. Recurrent/windowed blocks then produce
    length-exact caches (the padded tail never enters the carried state
    — see ``models.recurrent``), which is what lets the serving
    scheduler prefill every arch family at power-of-two buckets.

    ``page_table`` ([B, M] int32): paged decode — ``caches`` is then the
    page-pool tree (``serving.pages.make_paged_caches``) shared by all
    slots, and the table maps each row's logical position blocks to
    physical pages.
    """
    prefix, repeats, suffix = stack_structure(arch)
    moe = arch.family == "moe"
    pat = _pattern(arch)

    x = L.embed_tokens(params["embed"], tokens, ctx)
    x = x * jnp.asarray(arch.d_model ** 0.5, x.dtype)
    prefix_len = None
    if prefix_embeds is not None:
        x = jnp.concatenate([prefix_embeds.astype(x.dtype), x], axis=1)
        if ctx is not None:
            x = ctx.constrain(x, "batch", "seq", None)
        prefix_len = jnp.full((x.shape[0],), prefix_embeds.shape[1], jnp.int32)
    b, s, _ = x.shape
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32)[None], (b, s))

    new_caches: Dict[str, Any] = {}

    def apply_one(kind, p, h, cache, moe_block=None, layer=None):
        use_moe = (moe and kind == "attn") if moe_block is None else moe_block

        def fn(p_, h_, cache_):
            return _block_apply(kind, arch, p_, h_, ctx, positions=positions,
                                prefix_len=prefix_len, moe=use_moe,
                                cache=cache_, seq_lens=seq_lens,
                                page_table=page_table, append=append,
                                layer=layer)
        if remat:
            fn = jax.checkpoint(fn, policy=_REMAT_POLICY)
        return fn(p, h, cache)

    for i, kind in enumerate(prefix):
        x, c = apply_one(kind, params[f"prefix{i}"], x,
                         None if caches is None else caches[f"prefix{i}"],
                         moe_block=False)
        if caches is not None:
            new_caches[f"prefix{i}"] = c

    if repeats:
        def pattern_body(p_rep, h, cache_rep=None):
            outs = {}
            for j, kind in enumerate(pat):
                key = f"b{j}_{kind}"
                h, c = apply_one(kind, p_rep[key], h,
                                 None if cache_rep is None else cache_rep[key])
                if cache_rep is not None:
                    outs[key] = c
            return h, outs

        if caches is None:
            x = scan_layers(lambda p, h: pattern_body(p, h)[0], params["body"], x,
                            ctx=ctx, specs=body_dims_unstacked(arch))
        elif s == 1 and not append and page_table is None:
            # decode: the stacked grid is carried, never sliced out and
            # stacked back, so each step writes one token per layer
            def body(carry, xs):
                h, grid = carry
                p_rep, i = xs
                grid = dict(grid)
                for j, kind in enumerate(pat):
                    key = f"b{j}_{kind}"
                    h, grid[key] = apply_one(kind, p_rep[key], h, grid[key],
                                             layer=i)
                return (h, grid), None

            (x, new_caches["body"]), _ = jax.lax.scan(
                body, (x, caches["body"]),
                (params["body"], jnp.arange(repeats, dtype=jnp.int32)))
        else:
            def body(h, xs):
                p_rep, cache_rep = xs
                h, outs = pattern_body(p_rep, h, cache_rep)
                return h, outs

            x, body_caches = jax.lax.scan(body, x, (params["body"], caches["body"]))
            new_caches["body"] = body_caches

    for i, kind in enumerate(suffix):
        x, c = apply_one(kind, params[f"suffix{i}"], x,
                         None if caches is None else caches[f"suffix{i}"])
        if caches is not None:
            new_caches[f"suffix{i}"] = c

    x = L.rms_norm(x, params["final_norm"])
    return x, (new_caches if caches is not None else None)


def unembed_matrix(arch: ArchConfig, params: Dict) -> jax.Array:
    return params["embed"].T if arch.tie_embeddings else params["unembed"]


def logits_fn(arch: ArchConfig, params: Dict, hidden: jax.Array, ctx=None) -> jax.Array:
    return L.unembed_logits(unembed_matrix(arch, params), hidden, ctx)


def loss_fn(arch: ArchConfig, params: Dict, tokens: jax.Array, labels: jax.Array,
            ctx=None, mask: Optional[jax.Array] = None,
            prefix_embeds: Optional[jax.Array] = None) -> jax.Array:
    hidden, _ = forward(arch, params, tokens, ctx, prefix_embeds=prefix_embeds,
                        remat=True)
    if prefix_embeds is not None:  # loss only on the text tail
        hidden = hidden[:, prefix_embeds.shape[1]:]
    return L.cross_entropy_chunked(unembed_matrix(arch, params), hidden, labels,
                                   mask=mask, ctx=ctx)
