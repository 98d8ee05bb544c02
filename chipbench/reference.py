"""Plain float32 reference of a Llama-architecture decoder (Yi's published
form: token embedding, RMSNorm with a weight, RoPE over rotate-half pairs,
grouped-query causal attention, SwiGLU MLP, untied head). It imports
nothing of the program and takes nothing the program made: it rebuilds
the weights from the seed with :mod:`chipbench.weights`, one layer at a
time, so that it fits beside nothing else on the chip.

``score`` teacher-forces each sampled request (prompt followed by its
served tokens) and returns, for each served token, the gap by which its
reference logit lies below the reference's best at that position. With
``control=True`` it also runs the same pass with every linear layer in
int8 (weights per output channel, activations per token, symmetric, int32
accumulation) and returns the gaps of the tokens that the int8 pass puts
first: the control, one precision below bf16, that the limit must fail.
"""
from __future__ import annotations

import functools
import math
from typing import List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import weights as W

HI = jax.lax.Precision.HIGHEST
Q_BLOCK = 512


def _int8(x, axis):
    s = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / 127.0
    s = jnp.where(s == 0, 1.0, s)
    return jnp.clip(jnp.round(x / s), -127, 127).astype(jnp.int8), s


def linear(x, w, quant: bool):
    """x [..., K] f32 @ w [K, N] f32; int8 x int8 -> int32 when quant."""
    if not quant:
        return jnp.matmul(x, w, precision=HI)
    xq, sx = _int8(x, -1)
    wq, sw = _int8(w, 0)
    y = jax.lax.dot_general(xq, wq, (((x.ndim - 1,), (0,)), ((), ())),
                            preferred_element_type=jnp.int32)
    return y.astype(jnp.float32) * sx * sw


def rms_norm(x, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)


def rope(x, pos, theta):
    """x [R, S, H, D]; pairs (i, i + D/2)."""
    half = x.shape[-1] // 2
    freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = pos[:, :, None].astype(jnp.float32) * freq
    cos, sin = jnp.cos(ang)[:, :, None], jnp.sin(ang)[:, :, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def attention(q, k, v):
    """Causal GQA over the whole row, query blocks of Q_BLOCK.
    q [R, S, H, D]; k, v [R, S, G, D]. Padding sits after every valid
    position, so causality alone keeps it out of valid queries."""
    r, s, h, d = q.shape
    rep = h // k.shape[2]
    k = jnp.repeat(k, rep, axis=2)
    v = jnp.repeat(v, rep, axis=2)
    kpos = jnp.arange(s)

    def block(i):
        qb = jax.lax.dynamic_slice_in_dim(q, i * Q_BLOCK, Q_BLOCK, 1)
        sc = jnp.einsum("rqhd,rkhd->rhqk", qb, k, precision=HI) / math.sqrt(d)
        qpos = i * Q_BLOCK + jnp.arange(Q_BLOCK)
        sc = jnp.where(kpos[None, :] <= qpos[:, None], sc, -jnp.inf)
        p = jax.nn.softmax(sc, axis=-1)
        return jnp.einsum("rhqk,rkhd->rqhd", p, v, precision=HI)

    out = jax.lax.map(block, jnp.arange(s // Q_BLOCK))   # [nb, R, QB, H, D]
    return out.transpose(1, 0, 2, 3, 4).reshape(r, s, h, d)


@functools.partial(jax.jit, static_argnames=("cfg_items", "quant"))
def _layer(x, w, pos, cfg_items, quant):
    cfg = dict(cfg_items)
    r, s, _ = x.shape
    hd, eps = cfg["head_dim"], cfg["rms_norm_eps"]
    w = {n: a.astype(jnp.float32) for n, a in w.items()}
    h = rms_norm(x, eps) * w["input_layernorm"]
    q = linear(h, w["q_proj"], quant).reshape(r, s, -1, hd)
    k = linear(h, w["k_proj"], quant).reshape(r, s, -1, hd)
    v = linear(h, w["v_proj"], quant).reshape(r, s, -1, hd)
    q, k = rope(q, pos, cfg["rope_theta"]), rope(k, pos, cfg["rope_theta"])
    x = x + linear(attention(q, k, v).reshape(r, s, -1), w["o_proj"], quant)
    h = rms_norm(x, eps) * w["post_attention_layernorm"]
    mlp = jax.nn.silu(linear(h, w["gate_proj"], quant)) * linear(
        h, w["up_proj"], quant)
    return x + linear(mlp, w["down_proj"], quant)


@functools.partial(jax.jit, static_argnames=("cfg_items",))
def _gen_layer(key, index, cfg_items):
    return W.layer(key, dict(cfg_items), index)


@functools.partial(jax.jit, static_argnames=("cfg_items", "name"))
def _gen_top(key, cfg_items, name):
    return W.top(key, dict(cfg_items), name)


@functools.partial(jax.jit, static_argnames=("eps", "quant"))
def _head(x, idx, norm, head, eps, quant):
    """Logits [R, T, V] at the positions ``idx`` [R, T] of each row."""
    h = rms_norm(jnp.take_along_axis(x, idx[:, :, None], axis=1), eps)
    return linear(h * norm.astype(jnp.float32), head.astype(jnp.float32),
                  quant)


def _cfg_items(cfg: dict) -> Tuple:
    keys = ("head_dim", "rms_norm_eps", "rope_theta", "hidden_size",
            "intermediate_size", "num_attention_heads",
            "num_key_value_heads", "vocab_size")
    return tuple((k, cfg[k]) for k in keys)


def score(cfg: dict, seed: int, rows: Sequence[Tuple[np.ndarray, List[int]]],
          control: bool = False) -> dict:
    """Gaps of served tokens under the float32 reference.

    ``rows``: (prompt, served tokens) per sampled request; every row is
    padded to the longest, rounded up to ``Q_BLOCK``. Returns ``served`` (per row, the
    gap of each served token) and, with ``control``, ``control`` (per
    row, the gap of the int8 pass's first choice)."""
    items = _cfg_items(cfg)
    key = W.base_key(seed)
    n = len(rows)
    width = max(len(p) + len(o) - 1 for p, o in rows)
    width = -(-width // Q_BLOCK) * Q_BLOCK
    toks = np.zeros((n, width), np.int32)
    idx = np.zeros((n, max(len(o) for _, o in rows)), np.int32)
    for i, (p, o) in enumerate(rows):
        seq = np.concatenate([p, np.asarray(o[:-1], np.int32)])
        toks[i, :len(seq)] = seq
        idx[i, :len(o)] = np.arange(len(p) - 1, len(p) - 1 + len(o))
    pos = jnp.broadcast_to(jnp.arange(width, dtype=jnp.int32), (n, width))
    emb = _gen_top(key, items, "embed_tokens").astype(jnp.float32)
    x0 = jnp.take(emb, jnp.asarray(toks), axis=0)
    del emb
    passes = {"served": x0, "control": x0} if control else {"served": x0}
    for li in range(cfg["num_hidden_layers"]):
        w = _gen_layer(key, li, items)
        for name in passes:
            passes[name] = _layer(passes[name], w, pos, items,
                                  quant=(name == "control"))
        del w
    head = _gen_top(key, items, "lm_head")
    norm = _gen_top(key, items, "norm")
    idx_j = jnp.asarray(idx)
    eps = cfg["rms_norm_eps"]
    ref = np.asarray(_head(passes["served"], idx_j, norm, head, eps, False))
    out = {"served": []}
    best = ref.max(-1)
    for i, (_, o) in enumerate(rows):
        t = np.asarray(o, np.int64)
        out["served"].append(best[i, :len(o)] - ref[i, np.arange(len(o)), t])
    if control:
        ctl = np.asarray(_head(passes["control"], idx_j, norm, head, eps,
                               True))
        first = ctl.argmax(-1)
        out["control"] = [best[i, :len(o)]
                          - ref[i, np.arange(len(o)), first[i, :len(o)]]
                          for i, (_, o) in enumerate(rows)]
    return out
