"""Serving runtime unit tests: DecodeState, sampler, scheduler (bucketed
prefill + metadata splice), drain contract, and the deprecation shim.

Decode *equivalence* against the frozen reference engine lives in the
conformance suite (tests/test_conformance.py + repro.testing.serving_equiv);
this file covers the package's pieces in the fast tier-1 set.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro
from repro.configs.base import ShapeConfig
from repro.models import registry as REG
from repro.serving import ServeConfig
from repro.serving.engine import IncompleteDrainError, Request, ServingEngine
from repro.serving.sampler import GREEDY, SamplingParams, sample
from repro.serving.scheduler import bucket_len, splice_row
from repro.serving.state import admit_slot, make_decode_state
from repro.testing.serving_equiv import _legacy_splice_leaf

ARCH = repro.get_arch("qwen1.5-0.5b").reduced()
DECODE_SHAPE = ShapeConfig("d", 32, 4, "decode")


# ------------------------- cache-axes metadata -------------------------

def test_cache_axes_metadata_matches_constructors():
    """Batch/length axes are derived structurally from make_caches for
    every family — including leaves whose batch axis is not leading."""
    ax = REG.cache_axes(ARCH)
    body = ax["body"]["b0_attn"]
    # K/V are head-major, [L, B, G, T, D]; pos is [L, B, T]
    assert (body["k"].batch, body["k"].length) == (1, 3)
    assert (body["pos"].batch, body["pos"].length) == (1, 2)
    assert (body["count"].batch, body["count"].length) == (None, None)

    moe = REG.cache_axes(repro.get_arch("deepseek-moe-16b").reduced())
    assert (moe["prefix0"]["k"].batch, moe["prefix0"]["k"].length) == (0, 2)

    rec = REG.cache_axes(repro.get_arch("recurrentgemma-2b").reduced())
    flat = jax.tree_util.tree_flatten_with_path(
        rec, is_leaf=lambda x: isinstance(x, REG.CacheAxes))[0]
    # every leaf except the scalar attn `count` has an explicit batch axis
    assert all(a.batch is not None for p, a in flat
               if "count" not in jax.tree_util.keystr(p))
    # rglru conv state has no length axis
    conv = [a for p, a in flat if "conv" in jax.tree_util.keystr(p)]
    assert conv and all(a.length is None for a in conv)

    enc = REG.cache_axes(repro.get_arch("seamless-m4t-medium").reduced())
    k = enc["dec_body"]["k"]
    assert (k.batch, k.length) == (1, 3)  # layer-stacked: batch axis is NOT 0


def test_splice_row_regression_slots_collide_with_model_dim():
    """The old shape heuristic mis-splices when a non-batch dim equals the
    slot count and the row is shorter (bucketed prefill): the first
    matching axis broadcasts a length-1 row across the whole cache row,
    marking every position valid. The metadata-driven splice writes only
    the row's extent and invalidates the tail."""
    slots = 4  # cache length chosen == slots: the collision
    axes = {"k": REG.CacheAxes(batch=0, length=1),
            "pos": REG.CacheAxes(batch=0, length=1)}
    grid = {"k": jnp.zeros((slots, slots, 2)),
            "pos": jnp.full((slots, slots), -1, jnp.int32)}
    row = {"k": jnp.ones((1, 1, 2)),
           "pos": jnp.zeros((1, 1), jnp.int32)}  # one-token bucket, pos=0

    good = splice_row(grid, row, 2, axes)
    np.testing.assert_array_equal(np.asarray(good["pos"])[2], [0, -1, -1, -1])
    assert np.asarray(good["k"])[2, 0].tolist() == [1.0, 1.0]
    assert np.abs(np.asarray(good["k"])[2, 1:]).max() == 0.0
    np.testing.assert_array_equal(np.asarray(good["pos"])[[0, 1, 3]], -1)

    legacy = jax.tree.map(_legacy_splice_leaf(2, slots), grid, row)
    # the heuristic broadcast the single position over the whole row:
    # every cache slot claims pos=0 (valid) — stale-tail corruption
    assert np.asarray(legacy["pos"])[2].tolist() == [0, 0, 0, 0]


def test_splice_row_full_length_matches_legacy_on_well_formed_rows():
    """For max_len-aligned rows (the old engine's only case) the explicit
    splice and the heuristic agree on every real arch cache tree."""
    slots, length = 3, 8
    for arch_id in ("qwen1.5-0.5b", "recurrentgemma-2b"):
        arch = repro.get_arch(arch_id).reduced()
        axes = REG.cache_axes(arch, jnp.float32)
        grid = REG.make_caches(arch, slots, length, jnp.float32)
        row = jax.tree.map(lambda l: jnp.asarray(
            np.random.RandomState(0).standard_normal(l.shape).astype(l.dtype))
            if jnp.issubdtype(l.dtype, jnp.floating) else l,
            REG.make_caches(arch, 1, length, jnp.float32))
        got = splice_row(grid, row, 1, axes)
        want = jax.tree.map(_legacy_splice_leaf(1, slots), grid, row)
        for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# ----------------------------- bucketing ------------------------------

def test_bucket_len_policy():
    assert bucket_len(3, 64, aligned=False) == 8    # min bucket
    assert bucket_len(9, 64, aligned=False) == 16   # next pow2
    assert bucket_len(16, 64, aligned=False) == 16  # exact
    assert bucket_len(40, 48, aligned=False) == 48  # clamped to max_len
    assert bucket_len(3, 64, aligned=True) == 64    # explicit alignment


def test_scheduler_alignment_policy_per_family():
    """Every family buckets now that prefill is length-exact (recurrent
    mask-carry, windowed ring-exact fill, masked encoder); windowed archs
    keep a bucket floor of ``window`` so the prefill row's ring size
    equals the grid's."""
    from repro.serving.scheduler import _bucketable, bucket_floor
    for arch_id in ("qwen1.5-0.5b", "deepseek-moe-16b", "recurrentgemma-2b",
                    "xlstm-350m", "seamless-m4t-medium", "paligemma-3b"):
        assert _bucketable(repro.get_arch(arch_id).reduced()), arch_id
    hybrid = repro.get_arch("recurrentgemma-2b").reduced()
    assert hybrid.window == 16
    assert bucket_floor(hybrid, max_len=64) == 16   # ring floor = window
    assert bucket_floor(hybrid, max_len=8) == 8     # clamped to max_len
    assert bucket_floor(repro.get_arch("xlstm-350m").reduced(), 64) == 8


def test_submit_rejects_overlong_prompt(key):
    plan = repro.plan(ARCH, DECODE_SHAPE)
    engine = plan.compile().serve(config=ServeConfig(slots=1, max_len=16))
    with pytest.raises(ValueError, match="exceeds"):
        engine.submit(Request(rid=0, prompt=np.arange(20, dtype=np.int32)))


# ------------------------------ sampler -------------------------------

def test_sampling_params_validation():
    with pytest.raises(ValueError, match="unknown sampling method"):
        SamplingParams(method="beam")
    with pytest.raises(ValueError, match="temperature"):
        SamplingParams(method="temperature", temperature=0.0)
    with pytest.raises(ValueError, match="top_k"):
        SamplingParams(method="top_k", top_k=0)


def test_sampler_greedy_is_argmax_and_keeps_rng(key):
    logits = jax.random.normal(key, (3, 17))
    rng = jax.vmap(lambda i: jax.random.fold_in(key, i))(jnp.arange(3))
    rng2, toks = sample(logits, rng, GREEDY)
    assert rng2 is rng
    np.testing.assert_array_equal(np.asarray(toks),
                                  np.asarray(jnp.argmax(logits, -1)))


def test_sampler_topk_stays_in_topk_and_advances_rng(key):
    logits = jax.random.normal(key, (4, 33))
    rng = jax.vmap(lambda i: jax.random.fold_in(key, i))(jnp.arange(4))
    sp = SamplingParams(method="top_k", temperature=0.7, top_k=3)
    rng2, toks = sample(logits, rng, sp)
    assert not np.array_equal(np.asarray(rng2), np.asarray(rng))
    top3 = np.asarray(jax.lax.top_k(logits, 3)[1])
    for i, t in enumerate(np.asarray(toks)):
        assert t in top3[i]
    # deterministic given the same keys
    _, toks_again = sample(logits, rng, sp)
    np.testing.assert_array_equal(np.asarray(toks), np.asarray(toks_again))


def test_engine_temperature_sampling_decodes(key):
    plan = repro.plan(ARCH, DECODE_SHAPE)
    engine = plan.compile().serve(config=ServeConfig(
        slots=2, max_len=32,
        sampling=SamplingParams(method="temperature", temperature=0.9)))
    for i in range(3):
        engine.submit(Request(rid=i, prompt=np.arange(1, 7, dtype=np.int32),
                              max_new_tokens=3))
    engine.run_until_drained(max_steps=50)
    assert len(engine.completed) == 3
    assert all(len(r.out_tokens) == 3 for r in engine.completed)
    assert all(0 <= t < ARCH.vocab_size
               for r in engine.completed for t in r.out_tokens)


# --------------------------- decode state -----------------------------

def test_decode_state_shapes_and_admit():
    st = make_decode_state(4, seed=3)
    assert st.tokens.shape == (4, 1) and st.rng.shape == (4, 2)
    assert not bool(st.active.any())
    st2 = jax.jit(admit_slot)(st, jnp.int32(2), jnp.int32(7), jnp.int32(5),
                              jnp.int32(9), st.rng[2])
    assert np.asarray(st2.active).tolist() == [False, False, True, False]
    assert int(st2.tokens[2, 0]) == 7 and int(st2.positions[2, 0]) == 5
    assert int(st2.max_new[2]) == 9 and int(st2.emitted[2]) == 0
    # untouched slots keep their keys
    np.testing.assert_array_equal(np.asarray(st2.rng[0]), np.asarray(st.rng[0]))


# ------------------------- drain-contract tests ------------------------

def test_run_until_drained_raises_with_unfinished_rids(key):
    plan = repro.plan(ARCH, DECODE_SHAPE)
    engine = plan.compile().serve(config=ServeConfig(slots=1, max_len=32))
    for i in range(3):
        engine.submit(Request(rid=i, prompt=np.arange(1, 7, dtype=np.int32),
                              max_new_tokens=8))
    with pytest.raises(IncompleteDrainError) as ei:
        engine.run_until_drained(max_steps=2)
    assert set(ei.value.unfinished) <= {0, 1, 2} and ei.value.unfinished


def test_run_until_drained_warn_mode(key):
    plan = repro.plan(ARCH, DECODE_SHAPE)
    engine = plan.compile().serve(config=ServeConfig(slots=1, max_len=32))
    engine.submit(Request(rid=5, prompt=np.arange(1, 7, dtype=np.int32),
                          max_new_tokens=8))
    with pytest.warns(RuntimeWarning, match="rids=\\[5\\]"):
        steps = engine.run_until_drained(max_steps=1, on_incomplete="warn")
    assert steps == 1


# ------------------------ deprecation shim parity ----------------------

def test_legacy_construction_parity(key):
    """ServingEngine(arch, ...) routes through the new scheduler and
    produces the same greedy streams as plan-based construction."""
    params = REG.init_params(ARCH, key)
    prompts = [np.arange(1, 7, dtype=np.int32),
               np.arange(3, 12, dtype=np.int32)]

    with pytest.warns(DeprecationWarning):
        legacy = ServingEngine(ARCH, params, slots=2, max_len=32,
                               dtype=jnp.float32)
    plan = repro.plan(ARCH, DECODE_SHAPE)
    modern = plan.compile().serve(
        params, config=ServeConfig(slots=2, max_len=32))
    for eng in (legacy, modern):
        for i, p in enumerate(prompts):
            eng.submit(Request(rid=i, prompt=p.copy(), max_new_tokens=4))
        eng.run_until_drained(max_steps=50)
    got = {r.rid: r.out_tokens for r in legacy.completed}
    want = {r.rid: r.out_tokens for r in modern.completed}
    assert got == want and len(got) == 2


def test_legacy_shim_drains_with_varying_max_new(key):
    """Per-request ``max_new_tokens`` budgets through the legacy
    ``ServingEngine(arch, ...)`` shim: every stream stops at exactly its
    own budget (retirement is per-slot, not batch-wide) and the streams
    match plan-based construction."""
    params = REG.init_params(ARCH, key)
    rng = np.random.RandomState(7)
    prompts = [rng.randint(1, 100, size=s).astype(np.int32)
               for s in (6, 9, 4)]
    budgets = [2, 7, 5]

    with pytest.warns(DeprecationWarning):
        legacy = ServingEngine(ARCH, params, slots=2, max_len=32,
                               dtype=jnp.float32)
    plan = repro.plan(ARCH, DECODE_SHAPE)
    modern = plan.compile().serve(
        params, config=ServeConfig(slots=2, max_len=32))
    for eng in (legacy, modern):
        for i, (p, b) in enumerate(zip(prompts, budgets)):
            eng.submit(Request(rid=i, prompt=p.copy(), max_new_tokens=b))
        eng.run_until_drained(max_steps=80)
    got = {r.rid: r.out_tokens for r in legacy.completed}
    want = {r.rid: r.out_tokens for r in modern.completed}
    assert got == want and len(got) == 3
    assert [len(got[i]) for i in range(3)] == budgets


# ---------------------- batched bucket admission -----------------------

def test_same_bucket_burst_is_one_prefill_dispatch(key):
    """Acceptance: a same-bucket admission burst of N requests issues O(1)
    prefill dispatches (one batched prefill + splice + state scatter),
    not N — asserted via prefill_stats()."""
    plan = repro.plan(ARCH, DECODE_SHAPE)
    engine = plan.compile().serve(config=ServeConfig(slots=4, max_len=32))
    rng = np.random.RandomState(0)
    for i in range(4):  # lengths 4..6 all land in the 8-bucket
        engine.submit(Request(rid=i,
                              prompt=rng.randint(1, 100, size=4 + (i % 3))
                              .astype(np.int32), max_new_tokens=3))
    engine.step()  # one serving-loop iteration admits the whole burst
    stats = engine.prefill_stats()
    assert stats["prefill_dispatches"] == 1.0
    assert stats["prefills"] == 4.0
    assert stats["prefill_batch_mean"] == 4.0
    assert all(r is not None for r in engine.active.values())
    engine.run_until_drained(max_steps=50)
    assert len(engine.completed) == 4


def test_mixed_bucket_batch_admits_in_one_step(key):
    """Churn shape: one step's admission wave spans several buckets —
    each bucket becomes exactly one dispatch, all slots fill in that
    step, and the streams match per-request (unbatched) admission."""
    rng = np.random.RandomState(1)
    prompts = [rng.randint(1, 100, size=s).astype(np.int32)
               for s in (3, 5, 9, 20)]  # buckets 8, 8, 16, 32

    def run(slots):
        plan = repro.plan(ARCH, DECODE_SHAPE)
        eng = plan.compile().serve(config=ServeConfig(slots=slots, max_len=32))
        for i, p in enumerate(prompts):
            eng.submit(Request(rid=i, prompt=p.copy(), max_new_tokens=3))
        if slots == 4:
            eng.step()
            st = eng.prefill_stats()
            assert st["prefill_dispatches"] == 3.0  # {8: two, 16: one, 32: one}
            assert st["prefills"] == 4.0
            assert all(r is not None for r in eng.active.values())
        eng.run_until_drained(max_steps=80)
        return {r.rid: r.out_tokens for r in eng.completed}

    batched = run(slots=4)
    serial = run(slots=1)  # one slot -> strictly per-request prefill
    assert batched == serial and len(batched) == 4


def test_recurrent_padfree_prefill_bitexact_vs_aligned(key):
    """Pad-free prefill: for recurrent/hybrid archs the prefill at a
    power-of-two bucket matches the old max_len-aligned path — the
    property that let them leave max_len alignment.

    Structural part, bit-exact: at a fixed padded shape, what the pad
    positions hold never reaches the prompt's hidden states or any
    prefill row (the ``seq_lens`` mask). Across shapes
    (bucket 16 vs aligned 32) XLA may order a reduction differently, so
    there the two agree to a few ulp of each tensor's magnitude."""
    from repro.models import lm as LM

    ulp = np.finfo(np.float32).eps
    for arch_id in ("xlstm-350m", "recurrentgemma-2b"):
        arch = repro.get_arch(arch_id).reduced()
        params = REG.init_params(arch, key, jnp.float32)
        prompt = np.random.RandomState(2).randint(1, 100, 5).astype(np.int32)
        states = {}
        for pad in (16, 32):  # bucket vs max_len-aligned
            runs = []
            for fill in (np.zeros((1, pad), np.int32),
                         np.random.RandomState(pad).randint(
                             1, 200, (1, pad)).astype(np.int32)):
                toks = fill.copy()
                toks[0, :5] = prompt
                caches = REG.make_caches(arch, 1, pad, jnp.float32)
                hidden, rows = LM.forward(arch, params, jnp.asarray(toks),
                                          caches=caches,
                                          seq_lens=jnp.asarray([5], jnp.int32))
                runs.append((np.asarray(hidden[0, :5]),
                             jax.tree_util.tree_flatten_with_path(
                                 jax.tree.map(np.asarray, rows))[0]))
            (h0, rows0), (h1, rows1) = runs
            np.testing.assert_array_equal(h0, h1, err_msg=f"{arch_id} pad={pad}")
            for (path, l0), (_, l1) in zip(rows0, rows1):
                np.testing.assert_array_equal(
                    l0, l1, err_msg=f"{arch_id}{jax.tree_util.keystr(path)}")
            states[pad] = runs[0]

        def close(a, b, msg):
            np.testing.assert_allclose(
                a, b, rtol=0, atol=8 * ulp * max(np.abs(a).max(), 1e-30),
                err_msg=msg)

        close(states[16][0], states[32][0], f"{arch_id} hidden")
        for (p16, l16), (p32, l32) in zip(states[16][1], states[32][1]):
            ks = jax.tree_util.keystr(p16)
            if "count" in ks:  # count records the padded length (unspliced)
                continue
            if l16.shape == l32.shape:  # recurrent state (length-free) leaves
                close(l16, l32, f"{arch_id}{ks}")


# --------------------------- encdec / vlm admission ---------------------

def test_mixed_encdec_and_dense_workload_drains(key):
    """Acceptance: serve drains a mixed encdec + dense workload — encdec
    decode streams are bit-exact vs the golden unbatched reference
    (exact-length encoder, per-request prefill), while the dense engine's
    same-bucket burst stays a single batched dispatch."""
    from repro.testing.serving_equiv import ReferenceEngine

    arch = repro.get_arch("seamless-m4t-medium").reduced()
    rng = np.random.RandomState(3)
    prompts = [rng.randint(1, 100, size=s).astype(np.int32)
               for s in (4, 6, 5, 4, 7)]
    frames = [rng.standard_normal((f, arch.d_model)).astype(np.float32)
              for f in (3, 9, 16, 2, 6)]

    def submit_all(eng):
        for i, (p, f) in enumerate(zip(prompts, frames)):
            eng.submit(Request(rid=i, prompt=p.copy(), src_frames=f,
                               max_new_tokens=4))
        eng.run_until_drained(max_steps=100)
        return {r.rid: list(r.out_tokens) for r in eng.completed}

    plan = repro.plan(arch, ShapeConfig("ed", 32, 4, "decode"))
    engine = plan.compile().serve(
        config=ServeConfig(slots=2, max_len=32, max_src_len=16))
    got = submit_all(engine)  # 2 slots over 5 requests: churn + batching
    params = engine.params
    want = submit_all(ReferenceEngine(arch, params, slots=2, max_len=32,
                                      max_src_len=16, dtype=jnp.float32))
    assert got == want and len(got) == 5

    # the dense half of the workload: burst admission stays O(1) dispatch
    dense = repro.plan(ARCH, DECODE_SHAPE).compile().serve(
        config=ServeConfig(slots=3, max_len=32))
    for i in range(3):
        dense.submit(Request(rid=i, prompt=prompts[i][:4], max_new_tokens=2))
    dense.run_until_drained(max_steps=30)
    assert dense.prefill_stats()["prefill_dispatches"] == 1.0
    assert len(dense.completed) == 3


def test_encdec_submit_requires_frames_and_validates_lengths():
    arch = repro.get_arch("seamless-m4t-medium").reduced()
    plan = repro.plan(arch, ShapeConfig("ed", 32, 4, "decode"))
    engine = plan.compile().serve(
        config=ServeConfig(slots=1, max_len=16, max_src_len=8))
    with pytest.raises(ValueError, match="needs.*frames"):
        engine.submit(Request(rid=0, prompt=np.arange(1, 4, dtype=np.int32)))
    with pytest.raises(ValueError, match="max_src_len"):
        engine.submit(Request(
            rid=1, prompt=np.arange(1, 4, dtype=np.int32),
            src_frames=np.zeros((9, arch.d_model), np.float32)))


def test_vlm_prefix_admission_attends_patches(key):
    """vlm requests carry patch embeddings; the prefix is part of the
    cache row (bucketed on prefix + prompt) and changes the decode
    stream, and batched admission matches per-request admission."""
    arch = repro.get_arch("paligemma-3b").reduced()
    plan = repro.plan(arch, ShapeConfig("vlm", 32, 4, "decode"))
    rng = np.random.RandomState(4)
    prompt = rng.randint(1, 100, size=4).astype(np.int32)
    patch_sets = [rng.standard_normal((6, arch.d_model)).astype(np.float32)
                  for _ in range(2)]

    def run(slots, patches_list):
        eng = plan.compile().serve(config=ServeConfig(slots=slots, max_len=32))
        for i, pa in enumerate(patches_list):
            eng.submit(Request(rid=i, prompt=prompt.copy(),
                               patch_embeds=pa, max_new_tokens=3))
        eng.run_until_drained(max_steps=60)
        return {r.rid: list(r.out_tokens) for r in eng.completed}

    batched = run(2, patch_sets)
    serial = run(1, patch_sets)
    assert batched == serial and len(batched) == 2
    # the prefix is part of the cache row: admission sets the decode
    # position past prefix + prompt (6 + 4), vs prompt-only 4
    eng = plan.compile().serve(config=ServeConfig(slots=2, max_len=32))
    eng.submit(Request(rid=0, prompt=prompt.copy(),
                       patch_embeds=patch_sets[0], max_new_tokens=2))
    eng.submit(Request(rid=1, prompt=prompt.copy(), max_new_tokens=2))
    eng.step()
    pos = np.asarray(eng.state.positions)[:, 0]
    assert sorted(pos.tolist()) == [5, 11]  # 4+1 and 6+4+1 after one step
    # and the patch embeddings do reach the logits
    from repro.models import lm as LM
    h0, _ = LM.forward(arch, eng.params, jnp.asarray(prompt[None]),
                       prefix_embeds=jnp.asarray(patch_sets[0][None]))
    h1, _ = LM.forward(arch, eng.params, jnp.asarray(prompt[None]),
                       prefix_embeds=jnp.asarray(patch_sets[1][None]))
    assert not np.allclose(np.asarray(h0[:, -1]), np.asarray(h1[:, -1]))
    # prefix overflow is rejected at submit
    eng = plan.compile().serve(config=ServeConfig(slots=1, max_len=8))
    with pytest.raises(ValueError, match="exceeds"):
        eng.submit(Request(rid=9, prompt=prompt.copy(),
                           patch_embeds=patch_sets[0]))


def test_lookahead_zero_matches_lookahead_one(key):
    params = REG.init_params(ARCH, key)
    plan = repro.plan(ARCH, DECODE_SHAPE)
    streams = []
    for la in (0, 1, 2):
        eng = plan.compile().serve(params, config=ServeConfig(
            slots=2, max_len=32, lookahead=la))
        for i in range(5):
            eng.submit(Request(rid=i, prompt=np.arange(1, 7, dtype=np.int32),
                               max_new_tokens=3))
        eng.run_until_drained(max_steps=60)
        streams.append({r.rid: r.out_tokens for r in eng.completed})
    assert streams[0] == streams[1] == streams[2]
    assert all(len(s) == 5 for s in streams)


# ---------------------------- telemetry --------------------------------

def test_step_stats_reset_between_drains(key):
    """A reused engine's counters describe exactly one drain:
    ``run_until_drained`` resets step/prefill telemetry at entry, so the
    second drain's stats never blend with the first's (regression: the
    deques used to accumulate across drains until they aged out)."""
    params = REG.init_params(ARCH, key)
    plan = repro.plan(ARCH, DECODE_SHAPE)
    eng = plan.compile().serve(params, config=ServeConfig(slots=2, max_len=32))
    for i in range(4):
        eng.submit(Request(rid=i, prompt=np.arange(1, 7, dtype=np.int32),
                           max_new_tokens=3))
    eng.run_until_drained(max_steps=60)
    first = eng.step_stats()
    assert first["tokens"] == 12.0 and first["steps"] > 0

    eng.submit(Request(rid=9, prompt=np.arange(1, 7, dtype=np.int32),
                       max_new_tokens=2))
    eng.run_until_drained(max_steps=60)
    second = eng.step_stats()
    pf = eng.prefill_stats()
    assert second["tokens"] == 2.0          # only the second drain's tokens
    assert second["steps"] < first["steps"]
    assert pf["prefills"] == 1.0 and pf["prefill_dispatches"] == 1.0
    assert second["queue_depth"] >= 0.0
    assert second["accepted_tokens_mean"] == 1.0  # plain decoding: 1 tok/slot-step


# ------------------------ speculative decoding -------------------------

def test_spec_engine_streams_match_target_only(key):
    """Draft-k + batched-verify smoke test on one device: a self-draft
    speculative engine commits bit-identical greedy streams to the
    target-only engine while accepting >1 token per slot-step."""
    from repro.serving import SpecConfig
    params = REG.init_params(ARCH, key)
    plan = repro.plan(ARCH, DECODE_SHAPE, draft=ARCH)

    base = plan.compile().serve(params, config=ServeConfig(slots=2, max_len=32))
    spec = plan.compile().serve({"target": params, "draft": params},
                                config=ServeConfig(slots=2, max_len=32,
                                                   spec=SpecConfig(k=3)))
    # budget = 2 full k+1 chains: a budget that stops a chain mid-way
    # counts the unconsumed proposals as rejected (by design), which
    # would obscure the full-acceptance assertion below
    for eng in (base, spec):
        for i in range(3):
            eng.submit(Request(rid=i, prompt=np.arange(1, 7, dtype=np.int32),
                               max_new_tokens=8))
        eng.run_until_drained(max_steps=60)
    want = {r.rid: r.out_tokens for r in base.completed}
    got = {r.rid: r.out_tokens for r in spec.completed}
    assert got == want and len(got) == 3
    stats = spec.step_stats()
    assert stats["accepted_tokens_mean"] > 1.0   # the speedup lever
    assert stats["draft_acceptance"] > 0.99      # self-draft: full acceptance
