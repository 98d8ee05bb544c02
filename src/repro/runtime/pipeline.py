"""GPipe-style pipeline parallelism over the `pod` axis — the ISLPED16
baseline the paper compares against (§1/§6: layer pipelining preserves
throughput but not latency).

The layer stack is split into `S` contiguous stages (stage = pod index);
microbatches stream through with `collective_permute` hand-offs between
stages. Under SPMD every device executes the same tick loop; a device is
"active" when its stage holds a valid microbatch. Autodiff flows through
`collective_permute` (its transpose is the reverse permute), so the same
construction trains.

This exists as a *comparison baseline*: the paper's point (and ours —
benchmarks/tpu_xfer.py::pipeline_baseline) is that Super-LIP partitioning
beats pipelining on latency at equal throughput for low-batch inference.
"""
from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.models import layers as L
from repro.models import lm as LM

PyTree = Any


def _stage_apply(arch: ArchConfig, stage_params: PyTree, x: jax.Array,
                 positions: jax.Array) -> jax.Array:
    """Run this stage's slice of the layer stack (scan over local layers)."""
    pat = arch.block_pattern or ("attn",)
    assert pat == ("attn",), "pipeline baseline supports uniform attn stacks"

    def body(h, p):
        h, _ = LM._block_apply("attn", arch, p["b0_attn"], h, None,
                               positions=positions, cache=None,
                               prefix_len=None, moe=False)
        return h, None

    x, _ = jax.lax.scan(body, x, stage_params)
    return x


def pipelined_forward(arch: ArchConfig, params: PyTree, tokens: jax.Array,
                      mesh, *, stage_axis: str = "pod",
                      num_microbatches: int = 4) -> jax.Array:
    """Forward pass with the body pipelined across `stage_axis`.

    params: standard LM params; `params['body']` leaves are [L, ...] and are
    sharded over `stage_axis` on dim 0 (L % stages == 0). Embed/unembed are
    replicated across stages. Returns hidden states [B, S, D].
    """
    from jax.sharding import PartitionSpec as P
    from jax import shard_map

    stages = dict(mesh.shape)[stage_axis]
    b, s = tokens.shape
    m = num_microbatches
    assert b % m == 0
    x = L.embed_tokens(params["embed"], tokens) * jnp.asarray(
        arch.d_model ** 0.5, jnp.float32)
    positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32)[None],
                                 (b // m, s))
    xs = x.reshape(m, b // m, s, arch.d_model)

    body_specs = jax.tree.map(lambda _: P(stage_axis), params["body"])

    def run(xs_local, stage_params):
        # xs_local: [M, mb, S, D] (replicated over the stage axis)
        idx = jax.lax.axis_index(stage_axis)
        ticks = m + stages - 1
        buf = jnp.zeros_like(xs_local[0])
        outs = jnp.zeros_like(xs_local)

        def tick(carry, t):
            buf, outs = carry
            feed = xs_local[jnp.minimum(t, m - 1)]
            x_in = jnp.where(idx == 0, feed, buf)
            y = _stage_apply(arch, stage_params, x_in, positions)
            # last stage emits microbatch t-(stages-1); others forward
            out_t = t - (stages - 1)
            emit = jnp.logical_and(idx == stages - 1, out_t >= 0)
            slot = jnp.maximum(out_t, 0)
            cur = jax.lax.dynamic_index_in_dim(outs, slot, 0, keepdims=False)
            outs = jax.lax.dynamic_update_index_in_dim(
                outs, jnp.where(emit, y.astype(outs.dtype), cur), slot, 0)
            perm = [(i, (i + 1) % stages) for i in range(stages)]
            buf = jax.lax.ppermute(y, stage_axis, perm)
            return (buf, outs), None

        (buf, outs), _ = jax.lax.scan(tick, (buf, outs), jnp.arange(ticks))
        # broadcast the last stage's outputs to every stage (replicated out)
        if stages > 1:
            outs = jax.lax.psum(
                jnp.where(idx == stages - 1, outs, jnp.zeros_like(outs)),
                stage_axis)
        return outs

    kwargs = dict(mesh=mesh, in_specs=(P(*([None] * 4)), body_specs),
                  out_specs=P(*([None] * 4)))
    fn = shard_map(run, check_vma=False, **kwargs)
    outs = fn(xs, params["body"])
    hidden = outs.reshape(b, s, arch.d_model)
    return L.rms_norm(hidden, params["final_norm"])


def pipelined_loss(arch: ArchConfig, params: PyTree, tokens, labels, mesh, *,
                   stage_axis: str = "pod", num_microbatches: int = 4):
    hidden = pipelined_forward(arch, params, tokens, mesh,
                               stage_axis=stage_axis,
                               num_microbatches=num_microbatches)
    return L.cross_entropy_chunked(LM.unembed_matrix(arch, params), hidden,
                                   labels)
