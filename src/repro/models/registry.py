"""Arch registry: step builders + input specs for every (arch × shape) cell.

The three step kinds (DESIGN.md §5):
  train_step(params, opt_state, batch)          -> (params, opt_state, metrics)
  prefill_step(params, batch)                   -> (caches, last_logits)
  serve_step(params, caches, batch)             -> (next_token, caches)

``build_serve_step`` has a second, state-threaded form for the serving
runtime (``sampling=`` given): the decode step consumes a device-resident
:class:`repro.serving.state.DecodeState`, folds on-device sampling into
the same jit, and returns a small per-step record instead of forcing the
host to read the token grid back every step:

  serve_step(params, caches, state)  -> (state', caches', record)

``input_specs(arch, shape)`` returns ShapeDtypeStructs for the batch — the
dry-run lowers against these without allocating (modality frontends are
stubs: audio frames / vision patches arrive as precomputed embeddings).
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig, ShapeConfig
from repro.core.xfer import ShardingCtx
from repro.kernels.ragged_decode_attention import live_blocks
from repro.models import blocks as B
from repro.models import encdec as ED
from repro.models import lm as LM
from repro.optim import adamw as OPT

PyTree = Any

DEC_FRAC = 8  # enc-dec: decoder target length = seq_len // DEC_FRAC


# ---------------------------------------------------------------------------
# params / dims / caches dispatch
# ---------------------------------------------------------------------------

def init_params(arch: ArchConfig, key, dtype=jnp.float32) -> PyTree:
    if arch.family == "encdec":
        return ED.init_params(arch, key, dtype)
    return LM.init_params(arch, key, dtype)


def param_dims(arch: ArchConfig) -> PyTree:
    if arch.family == "encdec":
        return ED.param_dims(arch)
    return LM.param_dims(arch)


def make_caches(arch: ArchConfig, batch: int, length: int, dtype=jnp.bfloat16,
                kv_quant: bool = False) -> PyTree:
    if arch.family == "encdec":
        return ED.make_caches(arch, batch, length, dtype, kv_quant=kv_quant)
    return LM.make_caches(arch, batch, length, dtype, kv_quant=kv_quant)


def cache_dims(arch: ArchConfig, kv_quant: bool = False) -> PyTree:
    if arch.family == "encdec":
        return ED.cache_dims(arch, kv_quant=kv_quant)
    return LM.cache_dims(arch, kv_quant=kv_quant)


def caches_quantized(caches: PyTree) -> bool:
    """Structural probe: does this cache tree carry int8 KV scale leaves
    (``k_scale`` / paged ``kps``)? Used to derive matching dims trees
    without threading a flag through every call site."""
    def walk(node):
        if not isinstance(node, dict):
            return False
        if "k_scale" in node or "kps" in node:
            return True
        return any(walk(v) for v in node.values())

    return walk(caches)


def body_kv_grid(arch: ArchConfig, caches: PyTree) -> Optional[dict]:
    """The first attention grid of the scanned body of an LM's dense
    cache tree (stacked ``[L, B, G, T, D]`` leaves): the grid that
    single-token decode carries through the layer scan. None for
    encoder-decoders, paged pools and bodies without attention."""
    if arch.family == "encdec" or not isinstance(caches, dict):
        return None
    for key, grid in caches.get("body", {}).items():
        if key.endswith("_attn") and "k" in grid:
            return grid
    return None


@dataclasses.dataclass(frozen=True)
class CacheAxes:
    """Which axis of one cache leaf is the batch-slot axis and which (if
    any) scales with the cache length. Deliberately NOT a registered
    pytree: it is carried as a leaf in a tree parallel to the cache.

    ``page`` is the pool axis of a *paged* cache leaf (scales with
    ``kv_pages``, see ``serving.pages.paged_cache_axes``); pool leaves
    have no batch-slot axis — the page table carries slot identity."""

    batch: Optional[int]
    length: Optional[int]
    page: Optional[int] = None


def cache_axes(arch: ArchConfig, dtype=jnp.bfloat16,
               kv_quant: bool = False) -> PyTree:
    """Per-leaf :class:`CacheAxes` metadata, derived from ``make_caches``.

    The axes are found structurally — ``eval_shape`` the cache skeleton at
    two batch sizes and two lengths and diff the leaf shapes — so the
    metadata can never drift from the constructor, and cache-splicing code
    need not guess the batch axis from runtime shapes (the old heuristic
    mis-matched when a model dim collided with the slot count).

    Leaves whose shape depends on neither (e.g. the scalar ``count``) get
    ``CacheAxes(None, None)``; windowed KV caches whose length saturates at
    the window report ``length=None`` at probe sizes beyond the window.
    ``kv_quant=True`` probes the int8 layout, so the per-token scale
    leaves get their own (identical batch/length) axes entries — splice
    and admit then handle them with zero special cases.
    """
    probes = [jax.eval_shape(lambda b=b, l=l: make_caches(arch, b, l, dtype,
                                                          kv_quant=kv_quant))
              for b, l in ((2, 16), (3, 16), (2, 32))]

    def one(base, bdiff, ldiff):
        b_ax = [i for i, (p, q) in enumerate(zip(base.shape, bdiff.shape))
                if p != q]
        l_ax = [i for i, (p, q) in enumerate(zip(base.shape, ldiff.shape))
                if p != q]
        assert len(b_ax) <= 1 and len(l_ax) <= 1, (base.shape, b_ax, l_ax)
        return CacheAxes(batch=b_ax[0] if b_ax else None,
                         length=l_ax[0] if l_ax else None)

    return jax.tree.map(one, *probes)


# ---------------------------------------------------------------------------
# input specs (ShapeDtypeStruct stand-ins; no allocation)
# ---------------------------------------------------------------------------

def input_specs(arch: ArchConfig, shape: ShapeConfig,
                dtype=jnp.bfloat16) -> Dict[str, jax.ShapeDtypeStruct]:
    B, S = shape.global_batch, shape.seq_len
    i32 = jnp.int32
    sds = jax.ShapeDtypeStruct
    if arch.family == "encdec":
        if shape.kind == "train":
            T = max(S // DEC_FRAC, 1)
            return {"frames": sds((B, S, arch.d_model), dtype),
                    "tokens": sds((B, T), i32), "labels": sds((B, T), i32)}
        if shape.kind == "prefill":
            return {"frames": sds((B, S, arch.d_model), dtype),
                    "tokens": sds((B, max(S // DEC_FRAC, 1)), i32)}
        return {"tokens": sds((B, 1), i32), "positions": sds((B, 1), i32),
                "enc_out": sds((B, S, arch.d_model), dtype)}
    out: Dict[str, jax.ShapeDtypeStruct] = {}
    text = S
    if arch.frontend == "vision_patches" and shape.kind in ("train", "prefill"):
        out["patches"] = sds((B, arch.frontend_tokens, arch.d_model), dtype)
        text = S - arch.frontend_tokens
    if shape.kind == "train":
        out["tokens"] = sds((B, text), i32)
        out["labels"] = sds((B, text), i32)
    elif shape.kind == "prefill":
        out["tokens"] = sds((B, text), i32)
    else:  # decode
        out["tokens"] = sds((B, 1), i32)
        out["positions"] = sds((B, 1), i32)
    return out


def input_dims(arch: ArchConfig, shape: ShapeConfig) -> Dict[str, tuple]:
    """Logical sharding roles for each batch input."""
    d: Dict[str, tuple] = {}
    for k, v in input_specs(arch, shape).items():
        if k in ("tokens", "labels", "positions"):
            d[k] = ("batch", "seq")[: len(v.shape)] if len(v.shape) == 2 else ("batch",)
            d[k] = ("batch", "seq") if shape.kind != "decode" else ("batch", None)
        elif k in ("frames", "patches", "enc_out"):
            d[k] = ("batch", "seq", None)
    return d


# ---------------------------------------------------------------------------
# step builders
# ---------------------------------------------------------------------------

def build_train_step(arch: ArchConfig, cfg: OPT.AdamWConfig,
                     ctx: Optional[ShardingCtx] = None,
                     lr_schedule: Optional[Callable] = None,
                     accum_steps: int = 1) -> Callable:
    """Train step; with ``accum_steps > 1`` the batch is split into equal
    microbatches along the batch dim and gradients are averaged before the
    single optimizer update (distributed-optimization trick: holds the
    global batch while shrinking per-step activation memory by the factor)."""
    schedule = lr_schedule or (lambda step: jnp.asarray(cfg.lr, jnp.float32))

    def loss(params, batch):
        if arch.family == "encdec":
            return ED.loss_fn(arch, params, batch["frames"], batch["tokens"],
                              batch["labels"], ctx)
        return LM.loss_fn(arch, params, batch["tokens"], batch["labels"], ctx,
                          prefix_embeds=batch.get("patches"))

    def grads_of(params, batch):
        if accum_steps <= 1:
            return jax.value_and_grad(loss)(params, batch)

        def split(x):
            b = x.shape[0]
            assert b % accum_steps == 0, (b, accum_steps)
            return x.reshape(accum_steps, b // accum_steps, *x.shape[1:])

        micro = jax.tree.map(split, batch)

        def body(carry, mb):
            lsum, gsum = carry
            lval, g = jax.value_and_grad(loss)(params, mb)
            return (lsum + lval,
                    jax.tree.map(jnp.add, gsum, g)), None

        zeros = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)
        (lsum, gsum), _ = jax.lax.scan(body, (jnp.float32(0.0), zeros), micro)
        inv = 1.0 / accum_steps
        return lsum * inv, jax.tree.map(lambda g: g * inv, gsum)

    def train_step(params, opt_state, batch):
        lval, grads = grads_of(params, batch)
        lr = schedule(opt_state["step"])
        params, opt_state, info = OPT.adamw_update(params, grads, opt_state, cfg, lr)
        metrics = {"loss": lval, "lr": lr, **info}
        return params, opt_state, metrics

    return train_step


def build_prefill_step(arch: ArchConfig, shape: ShapeConfig,
                       ctx: Optional[ShardingCtx] = None,
                       cache_dtype=jnp.bfloat16) -> Callable:
    B, S = shape.global_batch, shape.seq_len

    def prefill_step(params, batch):
        if arch.family == "encdec":
            enc_out = ED.encode(arch, params, batch["frames"], ctx)
            caches = ED.make_caches(arch, B, S, cache_dtype)
            hidden, caches = ED.decode(arch, params, batch["tokens"], enc_out,
                                       ctx, caches=caches)
            logits = hidden[:, -1:] @ params["unembed"]
            return caches, logits, enc_out
        caches = LM.make_caches(arch, B, S, cache_dtype)
        hidden, caches = LM.forward(arch, params, batch["tokens"], ctx,
                                    caches=caches,
                                    prefix_embeds=batch.get("patches"))
        logits = LM.logits_fn(arch, params, hidden[:, -1:], ctx)
        return caches, logits

    return prefill_step


def build_serve_step(arch: ArchConfig, ctx: Optional[ShardingCtx] = None, *,
                     sampling=None, eos_id: Optional[int] = None,
                     paged: bool = False, spec=None) -> Callable:
    """Decode-step builder.

    Without ``sampling`` (legacy form) the step is the stateless
    ``(params, caches, batch) -> (next_token, caches)`` greedy kernel the
    dry-run and differential suites lower.

    With ``sampling`` (a :class:`repro.serving.sampler.SamplingParams`)
    the step is the serving runtime's fused kernel — decode state threads
    through on device, token selection (greedy/temperature/top-k) and all
    per-slot lifecycle arithmetic (EOS detection, emission budgets,
    position advance) happen inside the jit, and only a small per-step
    ``record`` ({token, emit, finished}, one entry per slot) ever needs
    host readback:

        serve_step(params, caches, state) -> (state', caches', record)

    EOS semantics match the engine contract: EOS is a stop signal, not an
    output token — it is never emitted, never counts toward ``max_new``,
    and an EOS arriving straight out of prefill finishes the slot without
    emitting anything.

    With ``paged=True`` (sampling form only, all-attention families)
    ``caches`` is the page-pool tree (``serving.pages``) and the state's
    ``page_table``/``seq_len`` leaves drive the per-slot KV mapping:
    inactive slots' table rows are nulled *inside* the step, so the
    host's lagging retire bookkeeping (lookahead dispatch) can never
    route a stale write into a freed — possibly re-allocated — page.

    With ``spec`` (a :class:`repro.serving.config.SpecConfig`) the step
    is the **speculative** kernel: the draft model proposes ``k`` tokens
    per slot (k+1 sequential single-token forwards against the state's
    ``draft_caches``; the extra forward is the catch-up that closes the
    draft-cache gap at full acceptance), the target verifies all ``k+1``
    positions in one batched ``append=True`` forward, and the
    longest-accepted-prefix commit — emission budgets, EOS, rollback of
    positions/seq_len — happens per slot inside the same jit. Params are
    then the pair ``{"target": ..., "draft": ...}``, the record's
    ``token``/``emit`` are ``[slots, k+1]`` (commit order within the
    step), and acceptance bookkeeping lands in ``state.accepted`` /
    ``state.proposed``. Greedy target sampling commits exactly the
    tokens the single-step path would (accept requires the draft
    proposal to equal the previous target sample; the first divergence
    breaks the chain), so greedy streams are bit-exact vs target-only.
    Dense-attention, non-windowed LMs only (both models).
    """
    if paged and sampling is None:
        raise ValueError("paged serve steps require the sampling "
                         "(state-threaded) form")
    if paged:
        from repro.serving.pages import check_paged_supported
        check_paged_supported(arch)
    if spec is not None:
        if sampling is None:
            raise ValueError("speculative serve steps require the sampling "
                             "(state-threaded) form")
        draft = spec.draft
        if draft is None:
            raise ValueError("SpecConfig.draft unresolved — pair the plan "
                             "with a draft arch (repro.plan(..., draft=...)) "
                             "or set SpecConfig(draft=...)")
        for label, a in (("target", arch), ("draft", draft)):
            if a.family != "dense":
                raise NotImplementedError(
                    f"speculative decoding requires dense-attention "
                    f"non-windowed LMs; {label} {a.name!r} has family "
                    f"{a.family!r}")
        if draft.vocab_size != arch.vocab_size:
            raise ValueError(
                f"draft vocab {draft.vocab_size} != target vocab "
                f"{arch.vocab_size}: proposals must be target tokens")
        return _build_spec_serve_step(arch, draft, int(spec.k), ctx,
                                      sampling=sampling, eos_id=eos_id,
                                      paged=paged)
    if sampling is None:
        def serve_step(params, caches, batch):
            if arch.family == "encdec":
                hidden, caches = ED.decode(arch, params, batch["tokens"],
                                           batch["enc_out"], ctx, caches=caches,
                                           positions=batch["positions"],
                                           enc_lens=batch.get("enc_len"))
                logits = hidden @ params["unembed"]
            else:
                hidden, caches = LM.forward(arch, params, batch["tokens"], ctx,
                                            caches=caches,
                                            positions=batch["positions"])
                logits = LM.logits_fn(arch, params, hidden, ctx)
            next_tok = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)
            return next_tok, caches

        return serve_step

    from repro.serving import sampler as SMP
    from repro.serving.state import DecodeState
    eos = jnp.int32(-1 if eos_id is None else eos_id)

    def serve_step(params, caches, state):
        if arch.family == "encdec":
            # cross-attending decode: every slot attends its own cached
            # enc_out row, padded source positions masked by enc_len
            hidden, caches = ED.decode(arch, params, state.tokens,
                                       state.enc_out, ctx, caches=caches,
                                       positions=state.positions,
                                       enc_lens=state.enc_len)
            logits = hidden @ params["unembed"]
        elif paged:
            # stale-write gate: inactive slots write the null page
            table = jnp.where(state.active[:, None], state.page_table, 0)
            hidden, caches = LM.forward(arch, params, state.tokens, ctx,
                                        caches=caches,
                                        positions=state.positions,
                                        page_table=table)
            logits = LM.logits_fn(arch, params, hidden, ctx)
        else:
            hidden, caches = LM.forward(arch, params, state.tokens, ctx,
                                        caches=caches,
                                        positions=state.positions)
            logits = LM.logits_fn(arch, params, hidden, ctx)
        kv_blocks = None
        grid = None if paged else body_kv_grid(arch, caches)
        if grid is not None and B.ragged_decode_applies(ctx, grid):
            # K/V blocks the ragged kernel read this step, for one layer
            kv_blocks = jnp.sum(live_blocks(state.positions[:, 0],
                                            grid["pos"].shape[-1]))
        rng, nxt = SMP.sample(logits[:, -1], state.rng, sampling)
        cur = state.tokens[:, 0]
        active = state.active
        eos_at_prefill = active & (cur == eos)
        emit = active & ~eos_at_prefill
        emitted = state.emitted + emit.astype(jnp.int32)
        stop = emit & ((emitted >= state.max_new) | (nxt == eos))
        new_active = emit & ~stop
        state = DecodeState(
            # inert slots hold token/position so the grid stays fixed-shape
            tokens=jnp.where(new_active, nxt, cur)[:, None],
            positions=state.positions + new_active.astype(jnp.int32)[:, None],
            active=new_active, emitted=emitted, max_new=state.max_new,
            rng=rng, enc_out=state.enc_out, enc_len=state.enc_len,
            page_table=state.page_table,
            seq_len=(None if state.seq_len is None
                     else state.seq_len + active.astype(jnp.int32)))
        record = {"token": jnp.where(emit, cur, -1), "emit": emit,
                  "finished": active & ~new_active}
        if kv_blocks is not None:
            record["kv_blocks"] = kv_blocks
        return state, caches, record

    return serve_step


def _build_spec_serve_step(arch: ArchConfig, draft: ArchConfig, k: int,
                           ctx: Optional[ShardingCtx] = None, *,
                           sampling, eos_id: Optional[int] = None,
                           paged: bool = False) -> Callable:
    """The fused draft-k + batched-verify + commit step (see
    :func:`build_serve_step`).

    Commit semantics replicate the single-token lifecycle exactly, one
    sub-step ``j`` per verify position: sub-step ``j`` consumes input
    ``in_j`` (the current token at j=0, draft proposal ``d_j`` after)
    and samples ``t_j`` from the target's logits at that position. A
    sub-step runs (``can_j``) while the slot is still live *and* the
    draft's proposal matched the previous target sample — the first
    mismatch breaks the chain for the rest of the step (the slot
    continues next step from the corrected token), while a budget/EOS
    stop kills the slot permanently. Per-slot PRNG keys advance once
    per executed sub-step, exactly the once-per-active-step cadence of
    the non-speculative path, so seeded sampled streams are invariant
    to speculation depth."""
    from repro.serving import sampler as SMP
    from repro.serving.state import DecodeState
    eos = jnp.int32(-1 if eos_id is None else eos_id)

    def serve_step(params, caches, state):
        tpar, dpar = params["target"], params["draft"]
        active = state.active
        cur = state.tokens[:, 0]
        pos0 = state.positions[:, 0]

        # --- draft: k greedy proposals + the catch-up forward ----------
        dcaches = state.draft_caches
        tok = cur
        drafts = []
        for j in range(k + 1):
            dh, dcaches = LM.forward(draft, dpar, tok[:, None], ctx,
                                     caches=dcaches,
                                     positions=(pos0 + j)[:, None],
                                     append=True)
            if j < k:
                dl = LM.logits_fn(draft, dpar, dh, ctx)
                tok = jnp.argmax(dl[:, -1], axis=-1).astype(jnp.int32)
                drafts.append(tok)
            # j == k: catch-up — consuming d_k at pos0+k completes the
            # draft cache through the full-acceptance frontier; its
            # logits are discarded.
        drafts = jnp.stack(drafts, axis=1)  # [B, k]

        # --- target: one batched verify over [cur, d_1..d_k] -----------
        vtoks = jnp.concatenate([cur[:, None], drafts], axis=1)
        vpos = pos0[:, None] + jnp.arange(k + 1, dtype=jnp.int32)[None]
        if paged:
            # stale-write gate: inactive slots write the null page
            table = jnp.where(active[:, None], state.page_table, 0)
            hidden, caches = LM.forward(arch, tpar, vtoks, ctx, caches=caches,
                                        positions=vpos, page_table=table,
                                        append=True)
        else:
            hidden, caches = LM.forward(arch, tpar, vtoks, ctx, caches=caches,
                                        positions=vpos, append=True)
        logits = LM.logits_fn(arch, tpar, hidden, ctx)  # [B, k+1, V]

        # --- longest-accepted-prefix commit, per slot ------------------
        keys = state.rng
        emitted = state.emitted
        alive = active      # survives the step (False after a stop-break)
        live = active       # still chaining within this step
        tokf = cur          # next step's input token
        pos_adv = jnp.zeros_like(pos0)
        seq_adv = jnp.zeros_like(pos0)
        rec_tok, rec_emit = [], []
        t_prev = cur  # unused at j=0
        for j in range(k + 1):
            in_j = cur if j == 0 else drafts[:, j - 1]
            can = active if j == 0 else live & (in_j == t_prev)
            new_keys, t_j = SMP.sample(logits[:, j], keys, sampling)
            keys = jnp.where(can[:, None], new_keys, keys)
            eos_at_input = can & (in_j == eos)
            emit = can & ~eos_at_input
            emitted = emitted + emit.astype(jnp.int32)
            stop = emit & ((emitted >= state.max_new) | (t_j == eos))
            new_live = emit & ~stop
            tokf = jnp.where(new_live, t_j, jnp.where(can, in_j, tokf))
            alive = jnp.where(can, new_live, alive)
            pos_adv = pos_adv + new_live.astype(jnp.int32)
            seq_adv = seq_adv + can.astype(jnp.int32)
            rec_tok.append(jnp.where(emit, in_j, -1))
            rec_emit.append(emit)
            live, t_prev = new_live, t_j

        a32 = active.astype(jnp.int32)
        state = DecodeState(
            tokens=tokf[:, None],
            positions=state.positions + pos_adv[:, None],
            active=alive, emitted=emitted, max_new=state.max_new,
            rng=keys, enc_out=state.enc_out, enc_len=state.enc_len,
            page_table=state.page_table,
            seq_len=(None if state.seq_len is None
                     else state.seq_len + seq_adv),
            draft_caches=dcaches,
            accepted=state.accepted + (seq_adv - a32),
            proposed=state.proposed + k * a32)
        emit2d = jnp.stack(rec_emit, axis=1)             # [B, k+1]
        record = {"token": jnp.stack(rec_tok, axis=1),   # [B, k+1]
                  "emit": emit2d,
                  "finished": active & ~alive,
                  "committed": emit2d.sum(axis=1).astype(jnp.int32)}
        return state, caches, record

    return serve_step


def build_step(arch: ArchConfig, shape: ShapeConfig,
               ctx: Optional[ShardingCtx] = None,
               opt_cfg: Optional[OPT.AdamWConfig] = None) -> Callable:
    if shape.kind == "train":
        return build_train_step(arch, opt_cfg or OPT.AdamWConfig(), ctx)
    if shape.kind == "prefill":
        return build_prefill_step(arch, shape, ctx)
    return build_serve_step(arch, ctx)
