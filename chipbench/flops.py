"""Operations and bytes the algorithm needs, from the configuration's
shapes alone: never from what one implementation happens to read (the
dense grid's whole ``max_len`` window, padded buckets, idle slots)."""
from __future__ import annotations

BF16 = 2


def layer_matmul_params(cfg: dict) -> int:
    d, f = cfg["hidden_size"], cfg["intermediate_size"]
    q = cfg["num_attention_heads"] * cfg["head_dim"]
    kv = cfg["num_key_value_heads"] * cfg["head_dim"]
    return d * q + 2 * d * kv + q * d + 3 * d * f


def head_params(cfg: dict) -> int:
    return cfg["hidden_size"] * cfg["vocab_size"]


def weight_bytes(cfg: dict) -> int:
    """Every weight a step reads once: the layers and the head (the
    embedding is read a row per token, which is negligible)."""
    norms = (2 * cfg["num_hidden_layers"] + 1) * cfg["hidden_size"]
    return BF16 * (cfg["num_hidden_layers"] * layer_matmul_params(cfg)
                   + head_params(cfg) + norms)


def kv_bytes_per_position(cfg: dict) -> int:
    """K and V of one position in every layer."""
    return (BF16 * 2 * cfg["num_hidden_layers"] * cfg["num_key_value_heads"]
            * cfg["head_dim"])


def attn_flops(cfg: dict, context: int) -> int:
    """Scores and weighted sum of one query over ``context`` keys, all
    layers."""
    q = cfg["num_attention_heads"] * cfg["head_dim"]
    return 4 * q * context * cfg["num_hidden_layers"]


def decode_token_flops(cfg: dict, context: int) -> int:
    """One decoded token that attends ``context`` positions (itself
    included): every matmul, the head, and attention."""
    return (2 * (cfg["num_hidden_layers"] * layer_matmul_params(cfg)
                 + head_params(cfg)) + attn_flops(cfg, context))


def prefill_flops(cfg: dict, prompt: int) -> int:
    """A prompt of ``prompt`` tokens: every layer for every token, causal
    attention, and the head at the last position only."""
    causal = prompt * (prompt + 1) // 2
    q = cfg["num_attention_heads"] * cfg["head_dim"]
    return (2 * cfg["num_hidden_layers"] * layer_matmul_params(cfg) * prompt
            + 4 * q * causal * cfg["num_hidden_layers"]
            + 2 * head_params(cfg))


def decode_step_need(cfg: dict, contexts) -> tuple:
    """(FLOPs, bytes) one decode step needs for the active slots whose
    live contexts are ``contexts``: the weights once, the K/V of every
    live position read, the new position's K/V written."""
    flops = sum(decode_token_flops(cfg, c) for c in contexts)
    kvb = kv_bytes_per_position(cfg)
    return flops, weight_bytes(cfg) + sum(c * kvb for c in contexts)


def roofline_seconds(flops: float, nbytes: float, peaks: dict,
                     chips: int) -> float:
    """Least time on ``chips`` chips sharing the work evenly."""
    return max(flops / (chips * peaks["bf16_flops_per_s"]),
               nbytes / (chips * peaks["hbm_bytes_per_s"]))
