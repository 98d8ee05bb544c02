"""Seeded weights, made by the benchmark and not by the program.

Canonical (Llama / Hugging Face) names per layer; every value is an
integer drawn from ``jax.random.bits`` times a power of two, so it is
exact in float32 and its bf16 rounding is the same in every program that
makes it: the one jitted call that builds the served tree and the
reference that rebuilds one layer at a time agree bit for bit.

``program_tree`` converts to the layout the serving program loads, as a
checkpoint loader would: its embedding is multiplied by sqrt(d_model)
inside the program (so it gets the table divided by that, a power of two
at d_model 4096), and its RMSNorm applies ``1 + scale`` (so it gets
``weight - 1``).
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

LAYER_LEAVES = ("q_proj", "k_proj", "v_proj", "o_proj", "gate_proj",
                "up_proj", "down_proj")
LAYER_NORMS = ("input_layernorm", "post_attention_layernorm")
LEAF_ID = {name: i for i, name in enumerate(
    ("embed_tokens", "lm_head") + LAYER_LEAVES + LAYER_NORMS + ("norm",))}


def base_key(seed: int) -> jax.Array:
    """A key from any non-negative seed, also above 32 bits."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF),
                              seed >> 31)


def shapes(cfg: dict) -> dict:
    d, f = cfg["hidden_size"], cfg["intermediate_size"]
    q = cfg["num_attention_heads"] * cfg["head_dim"]
    kv = cfg["num_key_value_heads"] * cfg["head_dim"]
    return {"embed_tokens": (cfg["vocab_size"], d),
            "lm_head": (d, cfg["vocab_size"]),
            "q_proj": (d, q), "k_proj": (d, kv), "v_proj": (d, kv),
            "o_proj": (q, d), "gate_proj": (d, f), "up_proj": (d, f),
            "down_proj": (f, d)}


def scale_exp(cfg: dict, name: str) -> int:
    """Values are uniform on [-2^e, 2^e): e = 0 for the embedding, else
    the power of two nearest sqrt(3 / fan_in) (std about 1/sqrt(fan_in))."""
    if name == "embed_tokens":
        return 0
    fan_in = shapes(cfg)[name][0]
    return round(math.log2(math.sqrt(3.0 / fan_in)))


def leaf(key: jax.Array, cfg: dict, name: str, dtype=jnp.bfloat16,
         extra: int = 0) -> jax.Array:
    """One weight: 16 random bits, centred, times 2^(e-15) (exact)."""
    shape = shapes(cfg)[name]
    bits = jax.random.bits(jax.random.fold_in(key, LEAF_ID[name]), shape,
                           jnp.uint16)
    ints = bits.astype(jnp.int32) - 32768
    return (ints.astype(jnp.float32)
            * (2.0 ** (scale_exp(cfg, name) - 15 + extra))).astype(dtype)


def norm_weight(key: jax.Array, cfg: dict, name: str,
                dtype=jnp.bfloat16) -> jax.Array:
    """One RMSNorm weight: 1 + k / 128 with k uniform on [-64, 64), so in
    [0.5, 1.5); both it and k / 128 are exact in bf16."""
    bits = jax.random.bits(jax.random.fold_in(key, LEAF_ID[name]),
                           (cfg["hidden_size"],), jnp.uint16)
    k = (bits >> 9).astype(jnp.int32) - 64
    return (1.0 + k.astype(jnp.float32) / 128.0).astype(dtype)


def layer(key: jax.Array, cfg: dict, index, dtype=jnp.bfloat16) -> dict:
    """Canonical weights of one decoder layer. ``key`` is ``base_key``'s;
    ``index`` may be traced."""
    k = jax.random.fold_in(jax.random.fold_in(key, 1), index)
    out = {n: leaf(k, cfg, n, dtype) for n in LAYER_LEAVES}
    out.update({n: norm_weight(k, cfg, n, dtype) for n in LAYER_NORMS})
    return out


def top(key: jax.Array, cfg: dict, name: str, dtype=jnp.bfloat16,
        extra: int = 0) -> jax.Array:
    """``embed_tokens``, ``lm_head`` or the final ``norm``."""
    k = jax.random.fold_in(key, 0)
    if name == "norm":
        return norm_weight(k, cfg, name, dtype)
    return leaf(k, cfg, name, dtype, extra)


def program_tree(key: jax.Array, cfg: dict, dtype=jnp.bfloat16) -> dict:
    """The served parameter tree (dense ``repro`` layout, layers stacked
    under ``body``) from ``base_key(seed)``. Call inside one ``jax.jit``
    with ``out_shardings``."""
    d = cfg["hidden_size"]
    emb_shift = -int(round(math.log2(math.sqrt(d))))
    assert 2.0 ** -emb_shift == math.sqrt(d), "sqrt(d_model) not a power of 2"
    stacked = jax.vmap(lambda i: layer(key, cfg, i, dtype))(
        jnp.arange(cfg["num_hidden_layers"]))
    # the program's RMSNorm multiplies by 1 + scale
    scale = lambda w: (w.astype(jnp.float32) - 1.0).astype(dtype)  # noqa: E731
    block = {"ln1": scale(stacked["input_layernorm"]),
             "ln2": scale(stacked["post_attention_layernorm"]),
             "wq": stacked["q_proj"], "wk": stacked["k_proj"],
             "wv": stacked["v_proj"], "wo": stacked["o_proj"],
             "mlp": {"w_gate": stacked["gate_proj"],
                     "w_up": stacked["up_proj"],
                     "w_down": stacked["down_proj"]}}
    return {"embed": top(key, cfg, "embed_tokens", dtype, extra=emb_shift),
            "final_norm": scale(top(key, cfg, "norm", dtype)),
            "unembed": top(key, cfg, "lm_head", dtype),
            "body": {"b0_attn": block}}
