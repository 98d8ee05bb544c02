"""The seeded weights, their conversion to the program's layout, and the
float32 reference agree with the program's own float32 forward."""
import jax
import jax.numpy as jnp
import numpy as np

import tiny
from chipbench import harness as H
from chipbench import reference as R
from chipbench import weights as W

SEED = 2**31 + 3


def test_stacked_layers_equal_rebuilt_layers():
    cfg = tiny.fresh(tiny.CONFIG)
    key = W.base_key(SEED)
    tree = jax.jit(lambda k: W.program_tree(k, cfg))(key)
    for i in range(cfg["num_hidden_layers"]):
        one = jax.jit(lambda k: W.layer(k, cfg, i))(key)
        assert np.array_equal(np.asarray(tree["body"]["b0_attn"]["wq"][i]),
                              np.asarray(one["q_proj"]))
        assert np.array_equal(
            np.asarray(tree["body"]["b0_attn"]["mlp"]["w_down"][i]),
            np.asarray(one["down_proj"]))
        # the program's RMSNorm multiplies by 1 + ln: exact in bf16
        ln = np.asarray(tree["body"]["b0_attn"]["ln2"][i], np.float32)
        assert np.array_equal(ln + 1, np.asarray(
            one["post_attention_layernorm"], np.float32))
        assert np.ptp(ln) > 0
    norm = np.asarray(W.top(key, cfg, "norm"), np.float32)
    assert np.array_equal(np.asarray(tree["final_norm"], np.float32) + 1, norm)
    emb = W.top(key, cfg, "embed_tokens")
    # the program multiplies its table by sqrt(64) = 8: exact
    assert np.array_equal(np.asarray(tree["embed"], np.float32) * 8,
                          np.asarray(emb, np.float32))


def test_seeds_above_32_bits_differ():
    cfg = tiny.fresh(tiny.CONFIG)
    a = W.top(W.base_key(5), cfg, "lm_head")
    b = W.top(W.base_key(5 + 2**31), cfg, "lm_head")
    assert not np.array_equal(np.asarray(a), np.asarray(b))


def test_reference_matches_program_forward():
    from repro.models import lm as LM
    cfg = tiny.fresh(tiny.CONFIG)
    arch = H.arch_from(cfg)
    params = jax.tree.map(lambda a: a.astype(jnp.float32),
                          W.program_tree(W.base_key(SEED), cfg))
    rng = np.random.default_rng(0)
    prompt = rng.integers(1, cfg["vocab_size"], 40, dtype=np.int32)
    served = list(rng.integers(1, cfg["vocab_size"], 9))
    toks = np.concatenate([prompt, np.asarray(served[:-1], np.int32)])
    with jax.default_matmul_precision("highest"):
        hidden, _ = LM.forward(arch, params, jnp.asarray(toks[None]))
        logits = np.asarray(LM.logits_fn(arch, params, hidden))[0]
    pos = np.arange(len(prompt) - 1, len(toks))
    want = logits[pos].max(-1) - logits[pos, served]
    got = R.score(cfg, SEED, [(prompt, served)])["served"][0]
    np.testing.assert_allclose(got, want, atol=2e-4)
    assert (got >= 0).all()
