"""A tiny configuration and mixes for running the harness on the CPU
(with the harness's sizes cut to match by ``conftest.tiny_sizes``)."""
import copy

CONFIG = {
    "name": "tiny", "source": "test", "architecture": "llama",
    "reference": "llama", "hidden_size": 64, "intermediate_size": 128,
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
    "num_hidden_layers": 2, "vocab_size": 256,
    "max_position_embeddings": 128, "rope_theta": 10000.0,
    "rms_norm_eps": 1e-6, "hidden_act": "silu", "tie_word_embeddings": False,
    "torch_dtype": "bfloat16", "serve": {"slots": 4, "max_len": 128},
    "chips": 1, "reference_rows": 2, "limits": {"served_gap": 0.5},
}

CHAT = {
    "name": "tiny_chat", "kind": "open_loop", "rate_per_s": 20.0,
    "prompt": {"dist": "lognormal", "median": 20, "sigma": 0.5, "min": 8,
               "max": 60},
    "output": {"dist": "lognormal", "median": 8, "sigma": 0.5, "min": 4,
               "max": 16},
    "drain_seconds": 30,
}

DECODE = {
    "name": "tiny_decode", "kind": "backlog", "backlog_per_slot": 1,
    "prompt": {"dist": "uniform", "min": 8, "max": 30},
    "output": {"dist": "uniform", "min": 8, "max": 24},
    "drain_seconds": 30,
}


def cell(mix):
    return {"name": "tiny." + mix["name"], "config": "tiny",
            "traffic": mix["name"], "chips": 1}


def fresh(d):
    return copy.deepcopy(d)
