"""The sharded serving path at Yi-9B's form, small, on four virtual CPU
devices, against a plain float32 forward.

The plan puts the model 4-way tensor parallel and splits the dense K/V
grid by sequence (``models/lm.py`` ``cache_dims``), so every decode step
runs flash-decoding (``models/layers.py`` ``decode_attention_sharded``):
each device attends over its quarter of the grid and the partials merge
with ``pmax``/``psum``. Prefill logits and 8 teacher-forced decode steps
through that grid are held to one unsharded forward over the whole
sequence, with no cache, in float32 at the highest matmul precision; the
engine's greedy tokens are held to the same reference; and the run with
the merge's ``psum`` left out has to fail the same comparison.
"""
import json

from repro.testing.mesh_fixtures import run_in_subprocess

CHILD = r'''
import dataclasses
import json

import jax
import jax.numpy as jnp
import numpy as np

import repro
from repro.configs.base import ArchConfig, ShapeConfig
from repro.models import lm as LM
from repro.core.planner import candidate_plans, evaluate_plan
from repro.models import registry as REG
from repro.serving import ServeConfig
from repro.serving.engine import Request

# Yi-9B's form (RMSNorm, RoPE, GQA 2:1, SwiGLU, untied head) at d_model 256
ARCH = ArchConfig(name="yi-small", family="dense", num_layers=4, d_model=256,
                  num_heads=8, num_kv_heads=4, d_ff=704, vocab_size=512,
                  head_dim=32, mlp="swiglu", rope_theta=10000.0,
                  tie_embeddings=False)
SLOTS, MAX_LEN, BUCKET, STEPS = 4, 64, 32, 8
# 16 grid positions per device: decoding crosses from the first quarter
# into the second (row 1) and from the second into the third (row 2)
LENS = np.array([9, 13, 30, 17], np.int32)
F32 = jnp.float32

SHAPE = ShapeConfig("t", MAX_LEN, SLOTS, "decode")
# the plan the planner picks for Yi-9B whole on a 2x2 (it alone fits):
# 4-way tensor parallel over both axes. At this size the planner would
# split the batch instead, so the cell's plan is taken by name.
auto = repro.plan(ARCH, SHAPE)
tp4 = next(p for p in candidate_plans(ARCH, SHAPE, auto.mesh_axes)
           if p.tp_axes == ("data", "model") and not p.xfer)
plan = dataclasses.replace(auto, report=evaluate_plan(ARCH, SHAPE, tp4))
exe = plan.compile(dtype=F32)
mesh, ctx = exe.mesh, exe.ctx
rng = np.random.default_rng(7)
params = REG.init_params(ARCH, jax.random.PRNGKey(3), F32)
prompt = rng.integers(1, ARCH.vocab_size, (SLOTS, BUCKET)).astype(np.int32)
forced = rng.integers(1, ARCH.vocab_size, (SLOTS, STEPS)).astype(np.int32)

# the reference: one forward over prompt + forced tokens, unsharded, no
# cache; causality keeps each row's padding out of its valid positions
seqs = np.zeros((SLOTS, BUCKET + STEPS), np.int32)
for i, n in enumerate(LENS):
    seqs[i, :n + STEPS] = np.concatenate([prompt[i, :n], forced[i]])
one = jax.devices()[0]
ref_params = jax.device_put(params, one)


def reference(tokens):
    with jax.default_matmul_precision("highest"):
        h, _ = LM.forward(ARCH, ref_params, jax.device_put(tokens, one))
        return np.asarray(LM.logits_fn(ARCH, ref_params, h))


ref = reference(seqs)
sharded = jax.device_put(params, plan.param_shardings(params, mesh))
grid = jax.device_put(REG.make_caches(ARCH, SLOTS, MAX_LEN, F32),
                      plan.cache_shardings(
                          REG.make_caches(ARCH, SLOTS, MAX_LEN, F32), mesh))
k_spec = str(grid["body"]["b0_attn"]["k"].sharding.spec)


def prefill(p, c, t, lens):
    h, c = LM.forward(ARCH, p, t, ctx, caches=c, seq_lens=lens)
    return LM.logits_fn(ARCH, p, h, ctx), c


pre_logits, filled = jax.jit(prefill)(sharded, grid, jnp.asarray(prompt),
                                      jnp.asarray(LENS))
pre_logits = np.asarray(pre_logits)
pre_err = max(float(np.abs(pre_logits[i, :n] - ref[i, :n]).max())
              for i, n in enumerate(LENS))


def decode_errors():
    def one_token(p, c, t, pos):
        h, c = LM.forward(ARCH, p, t, ctx, caches=c, positions=pos)
        return LM.logits_fn(ARCH, p, h, ctx)[:, 0], c

    step = jax.jit(one_token)
    c, err = filled, 0.0
    for j in range(STEPS):
        logits, c = step(sharded, c, jnp.asarray(forced[:, j:j + 1]),
                         jnp.asarray(LENS[:, None] + j))
        want = ref[np.arange(SLOTS), LENS + j]
        err = max(err, float(np.abs(np.asarray(logits) - want).max()))
    return err


dec_err = decode_errors()
psum = jax.lax.psum
jax.lax.psum = lambda x, axis_name, **kw: x   # the merge left out
no_merge_err = decode_errors()
jax.lax.psum = psum

# the engine: bucketed prefill, splice into the sharded grid, fused decode
engine = exe.serve(params, config=ServeConfig(slots=SLOTS, max_len=MAX_LEN))
for i, n in enumerate(LENS):
    engine.submit(Request(rid=i, prompt=prompt[i, :n], max_new_tokens=STEPS))
engine.run_until_drained(max_steps=64)
served = {r.rid: list(r.out_tokens) for r in engine.completed}
rows = np.zeros((SLOTS, BUCKET + STEPS), np.int32)
for i, n in enumerate(LENS):
    rows[i, :n + STEPS - 1] = np.concatenate([prompt[i, :n],
                                              served[i][:-1]])
served_ref = reference(rows)
gap = 0.0
for i, n in enumerate(LENS):
    at = served_ref[i, n - 1:n - 1 + STEPS]
    gap = max(gap, float((at.max(-1) - at[np.arange(STEPS), served[i]]).max()))

alone = repro.plan(ARCH, SHAPE, mesh=(("data", 1), ("model", 1))).compile(
    dtype=F32)
print("RESULT " + json.dumps({
    "tp": ctx.plan.degree(ctx.plan.tp_axes), "k_spec": k_spec,
    "logit_scale": float(np.abs(ref).max()), "prefill_err": pre_err,
    "decode_err": dec_err, "no_merge_err": no_merge_err,
    "served_tokens": sum(len(v) for v in served.values()),
    "served_gap": gap, "collectives": engine.collective_stats(),
    "one_device": alone.serve(params, config=ServeConfig(
        slots=SLOTS, max_len=MAX_LEN)).collective_stats()}))
'''


# Both sides compute in float32; they differ in summation order only
# (projections reduced over a 4-way split, four partial softmaxes merged
# by log-sum-exp against one softmax over the row). Read: about 3e-6 on
# logits up to 3.8 (some 30 float32 ulps at that scale); the bound gives
# room of 30x and stays 4 orders below the merge left out (about 2.7).
LOGIT_TOL = 1e-4
# A greedy token may lose to the reference's best only by a tie inside
# the logit error, on each of the two logits compared.
GAP_TOL = 2 * LOGIT_TOL


def test_sharded_decode_matches_float32_reference():
    # about 20 s on the CPU; the bound is for a loaded machine
    r = run_in_subprocess(CHILD, devices=4, timeout=300, marker="RESULT ")
    res = json.loads(r.stdout.split("RESULT ", 1)[1])
    assert res["tp"] == 4
    # the head-major grid [L, B, G, T, D] split by sequence
    assert res["k_spec"] == "PartitionSpec(None, None, None, ('data', 'model'), None)"
    assert res["prefill_err"] <= LOGIT_TOL, res
    assert res["decode_err"] <= LOGIT_TOL, res
    assert res["no_merge_err"] > 1e3 * LOGIT_TOL, res
    assert res["served_tokens"] == 4 * 8
    assert res["served_gap"] <= GAP_TOL, res
    # the live 4-device engine's serve step exchanges; one device none
    assert res["collectives"]["all-reduce"]["count"] > 0, res
    assert res["one_device"] == {}
