"""Encoder-decoder stack (SeamlessM4T-medium backbone).

The audio frontend is a STUB per the brief: the encoder consumes
precomputed frame embeddings ``frames [B, S_src, D]`` (``input_specs()``
provides them). Encoder blocks are bidirectional; decoder blocks are
causal self-attention + cross-attention over the encoder output.
"""
from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.core.xfer import ShardingCtx, scan_layers
from repro.models import blocks as B
from repro.models import layers as L


def init_params(arch: ArchConfig, key, dtype=jnp.float32) -> Dict:
    ks = jax.random.split(key, 6)

    def stack_init(k, n, cross):
        def one(kk):
            return B.attn_init(kk, arch, dtype, cross=cross)
        return jax.vmap(one)(jax.random.split(k, n))

    return {
        "embed": L.dense_init(ks[0], (arch.vocab_size, arch.d_model), 1, dtype),
        "enc_body": stack_init(ks[1], arch.enc_layers, cross=False),
        "enc_norm": jnp.zeros((arch.d_model,), dtype),
        "dec_body": stack_init(ks[2], arch.dec_layers, cross=True),
        "final_norm": jnp.zeros((arch.d_model,), dtype),
        "unembed": L.dense_init(ks[3], (arch.d_model, arch.vocab_size), 0, dtype),
    }


def param_dims(arch: ArchConfig) -> Dict:
    enc = B.attn_dims(arch, cross=False)
    dec = B.attn_dims(arch, cross=True)
    add_l = lambda tree: jax.tree.map(lambda d: (None,) + tuple(d), tree,
                                      is_leaf=lambda x: isinstance(x, tuple))
    return {
        "embed": ("tp", "xfer"),
        "enc_body": add_l(enc),
        "enc_norm": (None,),
        "dec_body": add_l(dec),
        "final_norm": (None,),
        "unembed": ("xfer", "tp"),
    }


def make_caches(arch: ArchConfig, batch: int, length: int, dtype=jnp.bfloat16,
                kv_quant: bool = False) -> Dict:
    one = B.make_kv_cache(arch, batch, length, dtype, kv_quant=kv_quant)
    stack = jax.tree.map(
        lambda leaf: jnp.broadcast_to(leaf[None], (arch.dec_layers,) + leaf.shape), one)
    return {"dec_body": stack}


def cache_dims(arch: ArchConfig, kv_quant: bool = False) -> Dict:
    # head-major [L, B, G, T, D], sharded over the sequence like ``pos``
    kv = {"k": (None, "batch", None, "tp", None), "v": (None, "batch", None, "tp", None),
          "pos": (None, "batch", "tp"), "count": (None,)}
    if kv_quant:
        kv["k_scale"] = kv["k"][:-1] + (None,)
        kv["v_scale"] = kv["v"][:-1] + (None,)
    return {"dec_body": kv}


def encode(arch: ArchConfig, params: Dict, frames: jax.Array,
           ctx: Optional[ShardingCtx] = None, remat: bool = False,
           enc_lens: Optional[jax.Array] = None) -> jax.Array:
    """frames: [B, S_src, D] stub embeddings -> encoder output [B, S_src, D].

    ``enc_lens`` ([B] int32): true per-row frame count of a right-padded
    batch. The bidirectional encoder attention masks keys at-or-beyond it,
    so a valid position's output is bit-equal to encoding the unpadded
    frames — the property the serving scheduler's per-slot ``enc_out``
    admission relies on (requests with different source lengths share one
    padded encoder call).
    """
    b, s, _ = frames.shape
    pos = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32)[None], (b, s))
    x = frames
    if ctx is not None:
        x = ctx.constrain(x, "batch", "seq", None)

    def block(p, h):
        def fn(p_, h_):
            return B.attn_apply(arch, p_, h_, ctx, positions=pos, causal=False,
                                seq_lens=enc_lens)[0]
        if remat:
            fn = jax.checkpoint(fn, policy=jax.checkpoint_policies.nothing_saveable)
        return fn(p, h)

    x = scan_layers(block, params["enc_body"], x, ctx=ctx,
                    specs=B.attn_dims(arch, cross=False))
    return L.rms_norm(x, params["enc_norm"])


def decode(arch: ArchConfig, params: Dict, tokens: jax.Array, enc_out: jax.Array,
           ctx: Optional[ShardingCtx] = None, *,
           caches: Optional[Dict] = None,
           positions: Optional[jax.Array] = None,
           enc_lens: Optional[jax.Array] = None,
           remat: bool = False) -> Tuple[jax.Array, Optional[Dict]]:
    """``enc_lens`` masks right-padded ``enc_out`` rows out of every
    cross-attention (serving threads it per slot through DecodeState)."""
    b, s = tokens.shape
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32)[None], (b, s))
    x = L.embed_tokens(params["embed"], tokens, ctx)
    x = x * jnp.asarray(arch.d_model ** 0.5, x.dtype)

    def block(p, h, cache=None):
        def fn(p_, h_, cache_):
            return B.attn_apply(arch, p_, h_, ctx, positions=positions,
                                causal=True, enc=enc_out, enc_lens=enc_lens,
                                cache=cache_)
        if remat:
            fn = jax.checkpoint(fn, policy=jax.checkpoint_policies.nothing_saveable)
        return fn(p, h, cache)

    new_caches = None
    if caches is None:
        x = scan_layers(lambda p, h: block(p, h)[0], params["dec_body"], x,
                        ctx=ctx, specs=B.attn_dims(arch, cross=True))
    else:
        def body(h, xs):
            p, c = xs
            h, c2 = block(p, h, c)
            return h, c2

        x, body_caches = jax.lax.scan(body, x, (params["dec_body"], caches["dec_body"]))
        new_caches = {"dec_body": body_caches}
    return L.rms_norm(x, params["final_norm"]), new_caches


def loss_fn(arch: ArchConfig, params: Dict, frames: jax.Array, tokens: jax.Array,
            labels: jax.Array, ctx=None, mask=None) -> jax.Array:
    enc_out = encode(arch, params, frames, ctx, remat=True)
    hidden, _ = decode(arch, params, tokens, enc_out, ctx, remat=True)
    return L.cross_entropy_chunked(params["unembed"], hidden, labels, mask=mask, ctx=ctx)
