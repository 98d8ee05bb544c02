"""Single-token decode writes into the stacked K/V grid in place.

``LM.forward`` carries the body's stacked caches through the layer scan
when it decodes one token on the dense grid: each layer scatters its new
K/V row at ``[layer, row, :, pos % t]`` of the head-major grid and
attends over its layer of the carried grid. These tests hold that path
bit for bit to the per-layer semantics it replaced: every block reads and
writes its own ``[B, G, t, ...]`` slice of the grid through
``blocks.attn_apply`` (or the
recurrent block's apply), as the scan over per-layer inputs and outputs
still does for prefill, append and paged forwards.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro
from repro.models import blocks as B
from repro.models import layers as L
from repro.models import lm as LM
from repro.models import registry as REG
from repro.serving.sampler import GREEDY
from repro.serving.state import make_decode_state

SLOTS, MAX_LEN, BUCKET = 4, 32, 24
# per-slot prompt lengths; slot 2 is inert in the serve step. With a ring
# of 16 (the reduced window) slots 0 and 3 decode at positions that wrap.
LENS = np.array([21, 7, 12, 16], np.int32)
ACTIVE = np.array([True, True, False, True])


def _archs():
    dense = dataclasses.replace(repro.get_arch("qwen1.5-0.5b").reduced(),
                                num_layers=3)
    gemma = repro.get_arch("recurrentgemma-2b").reduced()
    return {
        "dense": (dense, False),
        "dense-int8kv": (dense, True),
        # hybrid family, attention only: every block is a windowed ring
        "windowed": (dataclasses.replace(gemma, block_pattern=("attn",),
                                         num_layers=3), False),
        # (rglru, rglru, attn) × 2 + an rglru suffix
        "hybrid-recurrent": (dataclasses.replace(gemma, num_layers=7), False),
        # one unrolled dense prefix layer ahead of the MoE body
        "moe-prefix": (dataclasses.replace(
            repro.get_arch("deepseek-moe-16b").reduced(), num_layers=3), False),
        "xlstm": (repro.get_arch("xlstm-350m").reduced(), False),
    }


ARCHS = _archs()


def _per_layer_decode(arch, params, caches, tokens, positions):
    """The per-layer semantics, unrolled: each block gets its own slice of
    the stacked caches and returns its own updated slice."""
    prefix, repeats, suffix = LM.stack_structure(arch)
    pat = arch.block_pattern or ("attn",)
    moe = arch.family == "moe"

    def block(kind, p, x, cache, use_moe):
        return LM._block_apply(kind, arch, p, x, None, positions=positions,
                               cache=cache, prefix_len=None, moe=use_moe)

    x = L.embed_tokens(params["embed"], tokens, None)
    x = x * jnp.asarray(arch.d_model ** 0.5, x.dtype)
    new = {}
    for i, kind in enumerate(prefix):
        x, new[f"prefix{i}"] = block(kind, params[f"prefix{i}"], x,
                                     caches[f"prefix{i}"], False)
    layers = []
    for r in range(repeats):
        p_rep, c_rep = jax.tree.map(lambda a: a[r],
                                    (params["body"], caches["body"]))
        outs = {}
        for j, kind in enumerate(pat):
            key = f"b{j}_{kind}"
            x, outs[key] = block(kind, p_rep[key], x, c_rep[key],
                                 moe and kind == "attn")
        layers.append(outs)
    if repeats:
        new["body"] = jax.tree.map(lambda *a: jnp.stack(a), *layers)
    for i, kind in enumerate(suffix):
        x, new[f"suffix{i}"] = block(kind, params[f"suffix{i}"], x,
                                     caches[f"suffix{i}"], moe and kind == "attn")
    return L.rms_norm(x, params["final_norm"]), new


def _prefilled(arch, kv_quant, key):
    """Params and a grid prefilled with ``LENS`` prompts (bucket
    ``BUCKET``) through the prefill path."""
    kp, kt = jax.random.split(key)
    params = REG.init_params(arch, kp, jnp.float32)
    caches = REG.make_caches(arch, SLOTS, MAX_LEN, jnp.float32,
                             kv_quant=kv_quant)
    prompt = jax.random.randint(kt, (SLOTS, BUCKET), 0, arch.vocab_size)
    _, caches = jax.jit(lambda p, c, t: LM.forward(
        arch, p, t, caches=c, seq_lens=jnp.asarray(LENS)))(params, caches,
                                                          prompt)
    return params, caches


def _assert_trees_equal(got, want):
    assert jax.tree.structure(got) == jax.tree.structure(want)
    for (path, g), w in zip(jax.tree_util.tree_leaves_with_path(got),
                            jax.tree.leaves(want)):
        assert g.shape == w.shape and g.dtype == w.dtype, path
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w),
                                      err_msg=jax.tree_util.keystr(path))


@pytest.mark.parametrize("name", list(ARCHS))
def test_decode_step_in_place_matches_per_layer(name, key):
    arch, kv_quant = ARCHS[name]
    params, caches = _prefilled(arch, kv_quant, key)
    tokens = jax.random.randint(jax.random.fold_in(key, 1), (SLOTS, 1), 0,
                                arch.vocab_size)
    positions = jnp.asarray(LENS)[:, None]

    want_h, want_c = jax.jit(lambda p, c: _per_layer_decode(
        arch, p, c, tokens, positions))(params, caches)
    got_h, got_c = jax.jit(lambda p, c: LM.forward(
        arch, p, tokens, caches=c, positions=positions))(params, caches)
    np.testing.assert_array_equal(np.asarray(got_h), np.asarray(want_h))
    _assert_trees_equal(got_c, want_c)

    # the engine's fused serve step, an inert slot among the active ones
    state = dataclasses.replace(
        make_decode_state(SLOTS), tokens=tokens, positions=positions,
        active=jnp.asarray(ACTIVE), max_new=jnp.full((SLOTS,), 100, jnp.int32))
    step = jax.jit(REG.build_serve_step(arch, sampling=GREEDY))
    state2, step_c, record = step(params, caches, state)
    _assert_trees_equal(step_c, want_c)
    want_next = np.asarray(jnp.argmax(
        LM.logits_fn(arch, params, want_h)[:, -1], axis=-1))
    np.testing.assert_array_equal(np.asarray(state2.tokens[:, 0])[ACTIVE],
                                  want_next[ACTIVE])
    np.testing.assert_array_equal(np.asarray(record["emit"]), ACTIVE)


@pytest.mark.parametrize(
    "kv_quant,stacked", [(False, True), (True, True), (False, False),
                         (True, False)],
    ids=["fp", "int8", "fp-per-layer", "int8-per-layer"])
def test_token_write_matches_row_update(kv_quant, stacked, key):
    """The scatter writes what a per-row ``dynamic_update_slice`` at
    ``[row, :, pos % t]`` of the head-major ``[B, G, t, ·]`` leaves
    writes, into layer ``i`` of a stacked grid and into a per-layer
    slice alike."""
    arch = repro.get_arch("qwen1.5-0.5b").reduced()
    t, g, d = 8, arch.num_kv_heads, arch.head_dim
    cache = B.make_kv_cache(arch, SLOTS, t, jnp.float32, kv_quant=kv_quant)
    assert cache["k"].shape == (SLOTS, g, t, d)
    kk, kv = jax.random.split(key)
    k_new = jax.random.normal(kk, (SLOTS, 1, g, d))
    v_new = jax.random.normal(kv, (SLOTS, 1, g, d))
    pos_new = jnp.asarray([[3], [8], [0], [13]], jnp.int32)
    slot = pos_new[:, 0] % t

    def row_update(c, u):
        """c [B, G, t, ·] (or pos [B, t]), u the fresh [B, 1, G, ·] rows
        (or [B, 1]), written head-major at ``[:, slot]`` of each row."""
        if c.ndim == 2:
            return jax.vmap(lambda c_, u_, i: jax.lax.dynamic_update_slice(
                c_, u_.astype(c_.dtype), (i,)))(c, u, slot)
        return jax.vmap(lambda c_, u_, i: jax.lax.dynamic_update_slice(
            c_, u_.swapaxes(0, 1).astype(c_.dtype), (0, i, 0)))(c, u, slot)

    want = dict(cache)
    for name, u in B._kv_leaves(cache, k_new, v_new):
        want[name] = row_update(cache[name], u)
    want["pos"] = row_update(cache["pos"], pos_new)
    want["count"] = cache["count"] + 1
    if not stacked:
        _assert_trees_equal(B._cache_write(cache, k_new, v_new, pos_new),
                            want)
        return
    grid = jax.tree.map(lambda a: jnp.stack([a] * 3), cache)
    got = B._cache_write(grid, k_new, v_new, pos_new, jnp.int32(1))
    _assert_trees_equal(jax.tree.map(lambda a: a[1], got), want)
    _assert_trees_equal(jax.tree.map(lambda a: a[::2], got),
                        jax.tree.map(lambda a: a[::2], grid))


@pytest.mark.parametrize("name", ["dense", "dense-int8kv", "moe-prefix"])
def test_paged_round_trip_matches_dense_grid(name, key):
    """Prefill rows spliced into the dense grid (``splice_rows``) and into
    a paged pool (``splice_pages``, which turns the head-major rows
    token-major) decode the same tokens, with bit-equal hidden states:
    the pool holds what the grid holds, whatever order each stores."""
    from repro.serving import pages as PG
    from repro.serving.scheduler import invalidate_padding, splice_rows

    arch, kv_quant = ARCHS[name]
    ps = 8
    m = MAX_LEN // ps
    kp, kt = jax.random.split(key)
    params = REG.init_params(arch, kp, jnp.float32)
    prompt = jax.random.randint(kt, (SLOTS, BUCKET), 0, arch.vocab_size)
    axes = REG.cache_axes(arch, jnp.float32, kv_quant=kv_quant)
    lens = jnp.asarray(LENS)

    def prefill(p, toks):
        rows = REG.make_caches(arch, SLOTS, BUCKET, jnp.float32,
                               kv_quant=kv_quant)
        h, rows = LM.forward(arch, p, toks, caches=rows, seq_lens=lens)
        last = jnp.take_along_axis(h, (lens - 1)[:, None, None], axis=1)
        return invalidate_padding(rows, lens, axes), last

    rows, h_last = jax.jit(prefill)(params, prompt)
    dense = splice_rows(REG.make_caches(arch, SLOTS, MAX_LEN, jnp.float32,
                                        kv_quant=kv_quant),
                        rows, jnp.arange(SLOTS, dtype=jnp.int32), axes)
    table = 1 + jnp.arange(SLOTS * m, dtype=jnp.int32).reshape(SLOTS, m)
    pools = PG.splice_pages(
        PG.make_paged_caches(arch, 1 + SLOTS * m, ps, jnp.float32,
                             kv_quant=kv_quant), rows, table)

    step_dense = jax.jit(lambda p, c, t, pos: LM.forward(
        arch, p, t, caches=c, positions=pos))
    step_paged = jax.jit(lambda p, c, t, pos: LM.forward(
        arch, p, t, caches=c, positions=pos, page_table=table))
    tokens = jnp.argmax(LM.logits_fn(arch, params, h_last), axis=-1)
    positions = lens[:, None]
    for _ in range(3):
        h_d, dense = step_dense(params, dense, tokens, positions)
        h_p, pools = step_paged(params, pools, tokens, positions)
        np.testing.assert_array_equal(np.asarray(h_p), np.asarray(h_d))
        tokens = jnp.argmax(LM.logits_fn(arch, params, h_d), axis=-1)
        positions = positions + 1
