"""INT8 quantization (repro.quant): round-trip properties, quantizer
hardening regressions (adamw clip, compression treedef, sampler top_k
ties), cache/param structure, and planner-aware capacity.

The property block uses hypothesis.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro import quant as Q
from repro.configs import SHAPES, get_arch
from repro.core.planner import ShardingPlan, capacity_bytes, plan_cell
from repro.models import registry as REG

MESH = (("data", 16), ("model", 16))
PLAN = ShardingPlan(MESH, batch_axes=("data",), tp_axes=("model",), xfer=False)


# ---------------------------------------------------------------------------
# round-trip properties (hypothesis)
# ---------------------------------------------------------------------------

def _adversarial(seed: int, n: int, log_amax: int) -> np.ndarray:
    """Wide-dynamic-range vectors whose amax element appears exactly (and
    duplicated, with both signs) — the rounding-edge case for the int8
    clip: amax/scale lands exactly on ±127."""
    rng = np.random.RandomState(seed)
    amax = np.float32(2.0) ** log_amax
    x = rng.standard_normal(n).astype(np.float32) * amax * rng.uniform(0, 1)
    x[0], x[1] = amax, -amax  # both clip edges, exact ties
    return x


@given(st.integers(0, 2**31 - 1), st.integers(2, 257), st.integers(-24, 24))
@settings(max_examples=50, deadline=None)
def test_roundtrip_error_bound_and_scale_positivity(seed, n, log_amax):
    x = _adversarial(seed, n, log_amax)
    t = Q.quantize(jnp.asarray(x))
    q = np.asarray(t.q)
    scale = np.asarray(t.scale, np.float64)
    assert q.dtype == np.int8
    assert (scale > 0).all()  # never zero/negative, even for zero input
    assert q.max() <= 127 and q.min() >= -127  # -128 never emitted
    err = np.abs(np.asarray(Q.dequantize(t), np.float64) - x.astype(np.float64))
    # symmetric round-to-nearest: half a quantization step (+ fp slack)
    assert (err <= scale * 0.5 + 1e-6 * scale * 127).all()


@given(st.integers(0, 2**31 - 1), st.integers(2, 257), st.integers(-24, 24))
@settings(max_examples=25, deadline=None)
def test_roundtrip_idempotence(seed, n, log_amax):
    """quantize(dequantize(t)) reproduces t bit-for-bit: the amax element
    dequantizes to ±127*scale, so the second pass derives the same scale
    and every code round-trips exactly."""
    x = _adversarial(seed, n, log_amax)
    t = Q.quantize(jnp.asarray(x))
    t2 = Q.quantize(Q.dequantize(t))
    np.testing.assert_array_equal(np.asarray(t.q), np.asarray(t2.q))
    np.testing.assert_allclose(np.asarray(t.scale), np.asarray(t2.scale),
                               rtol=1e-6)


def test_zero_and_tiny_inputs_quantize_safely():
    for x in (np.zeros(8, np.float32),
              np.full(8, 1e-38, np.float32),
              np.array([0.0, -0.0, 5e-39, -5e-39], np.float32)):
        t = Q.quantize(jnp.asarray(x))
        assert float(np.asarray(t.scale).min()) > 0
        assert np.isfinite(np.asarray(Q.dequantize(t))).all()


def test_per_channel_and_per_token_axes():
    x = jnp.asarray(np.random.RandomState(0)
                    .standard_normal((4, 6, 8)).astype(np.float32))
    t = Q.quantize(x)              # per-tensor: scalar scale
    assert np.asarray(t.scale).shape == ()
    tw = Q.quantize(x, axis=(0, 1))  # per-output-channel (weights)
    assert tw.scale.shape == (1, 1, 8)
    tk = Q.quantize_kv(x)           # per-token over the trailing head_dim
    assert tk.scale.shape == (4, 6, 1)
    for t_ in (tw, tk):
        err = np.abs(np.asarray(Q.dequantize(t_)) - np.asarray(x))
        bound = np.asarray(t_.scale) * 0.5 + 1e-6
        assert (err <= bound).all()


# ---------------------------------------------------------------------------
# hardening regressions: adamw clip, compression treedef, sampler ties
# ---------------------------------------------------------------------------

def test_adamw_quant_state_never_wraps():
    """Regression for the optimizer's historical unclipped `_quant`: fp
    error at the amax element could round to 128 and wrap to -128,
    flipping the largest moment's sign. The shared helper clips, so every
    int8 state leaf stays in [-127, 127] and updates stay finite."""
    from repro.optim.adamw import AdamWConfig, adamw_init, adamw_update
    cfg = AdamWConfig(quantize=True)
    rng = np.random.RandomState(1)
    params = {"w": jnp.asarray(rng.standard_normal((8, 16)).astype(np.float32)),
              "b": jnp.asarray(rng.standard_normal(16).astype(np.float32))}
    state = adamw_init(params, cfg)
    grads = jax.tree.map(
        lambda p: jnp.asarray((rng.standard_normal(p.shape) *
                               np.float32(2.0) ** 20).astype(np.float32)),
        params)
    for _ in range(3):
        params, state, stats = adamw_update(params, grads, state, cfg,
                                            lr=jnp.float32(1e-3))
    for leaf in jax.tree.leaves(state, is_leaf=Q.is_qtensor):
        if Q.is_qtensor(leaf):
            qv = np.asarray(leaf.q)
            assert qv.dtype == np.int8
            assert qv.max() <= 127 and qv.min() >= -127
    assert all(np.isfinite(np.asarray(p)).all()
               for p in jax.tree.leaves(params))


def test_compressed_grads_rejects_mismatched_error_tree():
    from repro.runtime.compression import compressed_grads, init_error_feedback
    grads = {"a": jnp.ones((4, 4)), "b": jnp.ones((4,))}
    err = init_error_feedback(grads)
    out_g, out_e = compressed_grads(grads, err)  # matching trees: fine
    assert jax.tree.structure(out_g) == jax.tree.structure(grads)
    stale = {"a": err["a"], "c": err["b"]}  # renamed leaf (elastic replan)
    with pytest.raises(ValueError, match="error-feedback tree"):
        compressed_grads(grads, stale)


def test_top_k_ties_keep_exactly_k():
    """With logits tied at the k-th value, the old >=-threshold mask kept
    every tied candidate; the index mask keeps exactly k (lowest indices
    win), so sampling can never emit a token outside the true top-k."""
    from repro.serving.sampler import SamplingParams, sample
    v, s, k = 16, 64, 2
    logits = np.full((s, v), -10.0, np.float32)
    logits[:, :5] = 3.0  # five-way tie for the top value
    rng = jax.vmap(jax.random.PRNGKey)(jnp.arange(s, dtype=jnp.uint32))
    sp = SamplingParams(method="top_k", top_k=k, temperature=1.0)
    _, toks = sample(jnp.asarray(logits), rng, sp)
    toks = np.asarray(toks)
    assert set(toks.tolist()) <= set(range(k)), toks
    # and the survivors are actually reachable (not all-argmax collapse)
    assert len(set(toks.tolist())) > 1


# ---------------------------------------------------------------------------
# param/cache structure
# ---------------------------------------------------------------------------

def test_quantize_params_structure_and_roundtrip(key):
    arch = get_arch("qwen1.5-0.5b").reduced()
    params = REG.init_params(arch, key, jnp.float32)
    qp = Q.quantize_params(params)
    n_q = sum(Q.is_qtensor(x) for x in
              jax.tree.leaves(qp, is_leaf=Q.is_qtensor))
    assert n_q > 0
    for leaf in jax.tree.leaves(qp, is_leaf=Q.is_qtensor):
        if Q.is_qtensor(leaf):
            assert leaf.q.dtype == jnp.int8
            assert leaf.scale.dtype == jnp.float32
            assert leaf.scale.shape[-1] == leaf.q.shape[-1]  # per-channel
        else:  # rank<2 (norms/biases) and integer leaves pass through
            assert leaf.ndim < 2 or not jnp.issubdtype(leaf.dtype,
                                                       jnp.floating)
    deq = Q.dequantize_params(qp)
    assert (jax.tree.structure(deq, is_leaf=Q.is_qtensor)
            == jax.tree.structure(params))
    for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(deq)):
        assert a.shape == b.shape and a.dtype == b.dtype
        # per-channel int8: worst-case half-step error, ~0.4% of amax
        amax = float(jnp.abs(a).max())
        assert float(jnp.abs(a - b).max()) <= amax / 127.0 * 0.51 + 1e-6


def test_quantized_caches_structure():
    arch = get_arch("qwen1.5-0.5b").reduced()
    fp = REG.make_caches(arch, 2, 16, jnp.float32)
    qc = REG.make_caches(arch, 2, 16, jnp.float32, kv_quant=True)
    assert not REG.caches_quantized(fp)
    assert REG.caches_quantized(qc)

    def leaves_named(tree, name):
        found = []

        def walk(t):
            if isinstance(t, dict):
                for k, v in t.items():
                    if k == name:
                        found.append(v)
                    else:
                        walk(v)
        walk(tree)
        return found

    ks, kq = leaves_named(qc, "k_scale"), leaves_named(qc, "k")
    assert ks and len(ks) == len(leaves_named(qc, "v_scale"))
    for k, s in zip(kq, ks):
        assert k.dtype == jnp.int8
        assert s.shape == k.shape[:-1] + (1,)  # per-token scale
    # the dims tree mirrors the quantized cache tree leaf-for-leaf
    dims = REG.cache_dims(arch, kv_quant=True)
    jax.tree.map(lambda c, d: None, qc, dims)  # raises on mismatch
    # the scheduler's probed splice/admit axes cover the scale leaves:
    # each k_scale entry resolves the same batch/length axes as its k
    axes = REG.cache_axes(arch, jnp.float32, kv_quant=True)
    for blk in axes["body"].values():
        assert blk["k_scale"].batch == blk["k"].batch
        assert blk["k_scale"].length == blk["k"].length


# ---------------------------------------------------------------------------
# planner-aware capacity
# ---------------------------------------------------------------------------

def test_capacity_shrinks_under_quant():
    arch, shape = get_arch("qwen1.5-0.5b"), SHAPES["decode_32k"]
    cap_fp = capacity_bytes(arch, shape, PLAN, opt_bytes_per_param=0.0)
    cap_q = capacity_bytes(arch, shape, PLAN, opt_bytes_per_param=0.0,
                           quant=Q.INT8_SERVE)
    # fp32 serving -> int8 weights + int8 KV: ~4x on the weight and KV
    # terms (activations and the scale leaves keep the total well short
    # of the full 4x, but the resident bytes must drop substantially)
    assert cap_q < 0.6 * cap_fp
    kv_only = Q.QuantConfig(kv="int8")
    cap_kv = capacity_bytes(arch, shape, PLAN, opt_bytes_per_param=0.0,
                            quant=kv_only)
    assert cap_q < cap_kv < cap_fp


def test_plan_cell_threads_quant():
    arch, shape = get_arch("qwen1.5-0.5b"), SHAPES["decode_32k"]
    rep_fp = plan_cell(arch, shape, MESH)
    rep_q = plan_cell(arch, shape, MESH, quant=Q.INT8_SERVE)
    assert rep_q.hbm_bytes_per_device < rep_fp.hbm_bytes_per_device


def test_quant_config_bytes_per_elem():
    cfg = Q.INT8_SERVE
    assert cfg.param_bytes_per_elem(2.0) == 1.0
    assert cfg.kv_bytes_per_elem(2.0, head_dim=64) == 1.0 + 4.0 / 64
    off = Q.QuantConfig()
    assert not off.enabled
    assert off.param_bytes_per_elem(2.0) == 2.0
    assert off.kv_bytes_per_elem(2.0, head_dim=64) == 2.0
    with pytest.raises(ValueError):
        Q.QuantConfig(weights="int4")
