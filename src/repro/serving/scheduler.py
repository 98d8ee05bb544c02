"""Admission, slot lifecycle, and batched bucketed prefill for the engine.

The scheduler owns everything between "a request arrives" and "its slot
decodes": the FIFO queue, the slot → request map, and the prefill path
that computes cache rows and splices them into the device-resident slot
grid.

Three properties define the admission path:

* **Batched bucketed prefill** — prompts are padded to the next
  power-of-two bucket (≥ ``MIN_BUCKET``) instead of to ``max_len``, and
  *all* waiting requests that land in the same bucket are prefilled as
  one batched forward, spliced with one :func:`splice_rows` call and
  admitted with one state scatter: a same-bucket admission burst of N
  requests costs O(1) device dispatches, not N. One jit compilation per
  (bucket, group size); group size is bounded by the slot count.
* **Every family buckets** — recurrent/hybrid/windowed prefill is
  length-exact under padding (``seq_lens`` mask-carry, see
  ``models.recurrent`` / ``models.blocks._ring_exact_fill``), so the
  bucket length is no longer part of the computation and those archs
  left ``max_len`` alignment. Windowed archs keep a bucket floor of
  ``window`` so a prefill row's ring size equals the grid's. Enc-dec
  archs run the encoder once per admission over frames padded to
  ``max_src_len`` (masked — padded frames contribute exactly zero) and
  cache ``enc_out`` in the slot's :class:`DecodeState` row; vlm archs
  prepend per-request patch embeddings, bucketing on the total
  (prefix + prompt) length. MoE note: routing capacity scales with the
  *batched* token count, so under a dropping capacity factor an MoE
  request's prefill may depend on its bucket companions — same
  contention continuous batching already accepts per decode step.
* **Metadata-driven cache splice** — the batch-slot axis of every cache
  leaf comes from :func:`repro.models.registry.cache_axes` (derived
  structurally from ``make_caches``), not from a runtime shape heuristic
  that mis-matched when a model dim collided with the slot count. The
  splice is a jitted ``dynamic_update_slice`` sweep that donates the
  grid, so admission never rewrites the whole KV grid at Python level.

K/V written by a shorter bucket leave the grid row's tail stale; the
spliced ``pos`` leaves mark it ``-1`` (invalid), which the decode
attention masks — same invariant the ring buffer relies on.
"""
from __future__ import annotations

import dataclasses
import time
import warnings
from collections import deque
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from repro.configs.base import ArchConfig
from repro.models import registry as REG
from repro.quant import QuantConfig, dequantize_params
from repro.serving import pages as PG
from repro.serving import sampler as SMP
from repro.serving import spans as SP
from repro.serving.state import DecodeState, admit_rows

PyTree = Any

MIN_BUCKET = 8


class RequestValidationError(ValueError):
    """A request was rejected at ``submit()`` (wrong modality payload for
    the arch family, or prompt + budget exceeding the slot grid)."""


class Request:
    """One serving request.

    The modality payload is explicit per family: ``src_frames``
    ([S_src, D]) are encoder source frames (enc-dec archs — the encoder
    input, *not* resident in the decoder cache row), ``patch_embeds``
    ([P, D]) are vlm patch embeddings (prepended to the prompt's cache
    row). The old ambiguous ``frames=`` kwarg / attribute is kept as a
    deprecated alias; ``submit()`` resolves it to the family's field.
    """

    def __init__(self, rid: int, prompt: np.ndarray, max_new_tokens: int = 16,
                 frames: Optional[np.ndarray] = None, *,
                 src_frames: Optional[np.ndarray] = None,
                 patch_embeds: Optional[np.ndarray] = None,
                 out_tokens: Optional[List[int]] = None,
                 submitted_at: float = 0.0, finished_at: float = 0.0):
        if frames is not None:
            if src_frames is not None or patch_embeds is not None:
                raise RequestValidationError(
                    f"request {rid}: pass src_frames=/patch_embeds= or the "
                    f"deprecated frames=, not both")
            warnings.warn(
                "Request(frames=...) is deprecated: pass src_frames= "
                "(enc-dec source frames) or patch_embeds= (vlm patch "
                "embeddings)", DeprecationWarning, stacklevel=2)
        self.rid = rid
        self.prompt = prompt
        self.max_new_tokens = max_new_tokens
        self.src_frames = src_frames
        self.patch_embeds = patch_embeds
        self._legacy_frames = frames
        self.out_tokens: List[int] = [] if out_tokens is None else out_tokens
        # time.perf_counter() stamps: queued (submit), given a slot
        # (admit), first token read back and finished (engine retire)
        self.submitted_at = submitted_at
        self.admitted_at: Optional[float] = None
        self.first_token_at: Optional[float] = None
        self.finished_at = finished_at

    @property
    def frames(self) -> Optional[np.ndarray]:
        """Deprecated alias: whichever modality payload is set."""
        for v in (self.src_frames, self.patch_embeds, self._legacy_frames):
            if v is not None:
                return v
        return None

    def _resolve_payload(self, family: str) -> None:
        """Route a legacy ``frames=`` payload to the family's field
        (called by ``submit()``, where the arch family is known)."""
        if self._legacy_frames is not None:
            if family == "encdec":
                self.src_frames = self._legacy_frames
            else:
                self.patch_embeds = self._legacy_frames
            self._legacy_frames = None

    @property
    def done(self) -> bool:
        return len(self.out_tokens) >= self.max_new_tokens

    def __repr__(self) -> str:
        return (f"Request(rid={self.rid}, prompt_len={len(self.prompt)}, "
                f"max_new_tokens={self.max_new_tokens})")


def _bucketable(arch: ArchConfig) -> bool:
    """True when prefill length is free to vary per request. Since
    prefill went length-exact (recurrent mask-carry, windowed ring-exact
    fill, masked encoder), every registered family qualifies; the hook
    stays for archs whose prefill state could still depend on the padded
    length."""
    return True


def bucket_floor(arch: ArchConfig, max_len: int,
                 min_bucket: int = MIN_BUCKET) -> int:
    """Smallest admissible bucket: windowed archs must build prefill rows
    whose ring size equals the grid's (``min(bucket, window)`` ==
    ``min(max_len, window)``), so their floor is the window."""
    win = arch.window if arch.family == "hybrid" else 0
    return max(min_bucket, min(win, max_len)) if win else min_bucket


def bucket_len(prompt_len: int, max_len: int, *, aligned: bool = False,
               min_bucket: int = MIN_BUCKET) -> int:
    """Power-of-two bucket ≥ prompt_len, clamped to ``max_len``."""
    if aligned:
        return max_len
    b = min_bucket
    while b < prompt_len:
        b *= 2
    return min(b, max_len)


def _leaf_key(path) -> Optional[str]:
    return getattr(path[-1], "key", None) if path else None


def mesh_jit(mesh, fn, **kw):
    """jit ``fn`` under the plan's mesh context when one is bound (the
    single place the serving package enters a mesh to compile)."""
    if mesh is not None:
        with mesh:
            return jax.jit(fn, **kw)
    return jax.jit(fn, **kw)


def splice_row(grid: PyTree, row: PyTree, slot, axes: PyTree) -> PyTree:
    """Write a batch-1 prefill row into ``grid`` at ``slot``.

    ``axes`` is the :func:`repro.models.registry.cache_axes` tree: the
    batch axis is explicit per leaf (never guessed from shapes). Rows may
    be shorter than the grid on their length axis (bucketed prefill);
    ``pos`` leaves are padded with ``-1`` so the stale K/V tail of the
    grid row stays masked, other leaves leave the tail untouched.
    Jit-friendly: ``slot`` may be a traced scalar.
    """

    def one(path, g, r, ax):
        if ax.batch is None or g.ndim == 0:
            return g
        r = r.astype(g.dtype)
        if ax.length is not None and r.shape[ax.length] < g.shape[ax.length]:
            if _leaf_key(path) == "pos":
                pad = [(0, 0)] * r.ndim
                pad[ax.length] = (0, g.shape[ax.length] - r.shape[ax.length])
                r = jnp.pad(r, pad, constant_values=-1)
        starts = [0] * g.ndim
        starts[ax.batch] = slot
        return jax.lax.dynamic_update_slice(g, r, tuple(starts))

    return jax.tree_util.tree_map_with_path(one, grid, row, axes)


def splice_rows(grid: PyTree, rows: PyTree, slots: jax.Array,
                axes: PyTree) -> PyTree:
    """Batched :func:`splice_row`: write ``n`` stacked prefill rows into
    ``grid`` at ``slots`` ([n] int32, distinct). The per-row update sweep
    is unrolled inside one jit, so a same-bucket admission burst is a
    single splice dispatch regardless of its size."""
    n = int(slots.shape[0])

    def row_i(i):
        def take(r, ax):
            if ax.batch is None or not hasattr(r, "ndim") or r.ndim == 0:
                return r
            return jax.lax.dynamic_slice_in_dim(r, i, 1, axis=ax.batch)
        return jax.tree.map(take, rows, axes)

    for i in range(n):
        grid = splice_row(grid, row_i(i), slots[i], axes)
    return grid


def invalidate_padding(row: PyTree, true_len, axes: PyTree) -> PyTree:
    """Mark ``pos`` entries at-or-beyond the true prompt length invalid
    (``-1``) — the in-bucket analog of the splice's tail padding.
    ``true_len`` is a scalar, or ``[n]`` for a stacked batch of rows
    (broadcast along each leaf's batch axis).

    The mask compares the stored position *value*, not the ring index:
    windowed caches keep the last ``window`` positions, so index ``i``
    does not hold position ``i`` there. For full-length caches the two
    coincide (prefill stores position ``i`` at index ``i``); already
    invalid entries (``-1``) stay invalid either way."""

    def one(path, leaf, ax):
        if _leaf_key(path) != "pos" or ax.length is None:
            return leaf
        lens = jnp.asarray(true_len)
        if lens.ndim and ax.batch is not None:
            shape = [1] * leaf.ndim
            shape[ax.batch] = lens.shape[0]
            lens = lens.reshape(shape)
        return jnp.where(leaf < lens, leaf, -1)

    return jax.tree_util.tree_map_with_path(one, row, axes)


class _Inflight:
    """One dispatched prefill→decode admission wave: the worker's
    transferred outputs plus the host bookkeeping needed to splice them
    (``ready()`` is the non-blocking all-leaves-arrived check)."""

    def __init__(self, *, kind, outs, group, slots, lens, max_new,
                 rids=None, flens=None, page_rows=None, dispatch_wall=0.0):
        self.kind = kind
        self.outs = outs
        self.group = group
        self.slots = slots
        self.lens = lens
        self.max_new = max_new
        self.rids = rids
        self.flens = flens
        self.page_rows = page_rows
        self.dispatch_wall = dispatch_wall

    def ready(self) -> bool:
        return all(leaf.is_ready() for leaf in jax.tree.leaves(self.outs))


class PrefillFactory:
    """Builds (and caches jits of) the batched bucketed prefill step,
    keyed ``(kind, bucket, n, prefix)``.

    Factored out of the :class:`Scheduler` so a disaggregated
    deployment's ``PrefillWorker`` (``serving.disagg``) can compile the
    *same* prefill programs under its own prefill-slice mesh: the
    arithmetic is identical, only the mesh (and therefore the sharding
    of the same logical computation) differs.

    kind "lm":     (params, tokens [n,B], lens [n])
    kind "vlm":    (params, patches [n,P,D], tokens [n,B-P], lens [n])
    kind "encdec": (params, frames [n,max_src,D], flens [n],
                    tokens [n,B], lens [n]) — also returns enc_out
    ``lens`` counts the prefix; every returned row is length-exact for
    its row's true length (mask-carry / ring-exact fill / invalidated
    pos tail).
    """

    def __init__(self, arch: ArchConfig, cache_axes: PyTree, cache_dtype,
                 mesh=None, quant: Optional[QuantConfig] = None):
        self.arch = arch
        self.cache_axes = cache_axes
        self.cache_dtype = cache_dtype
        self.mesh = mesh
        self.quant = quant if quant is not None else QuantConfig()
        self._fns: Dict[Tuple, Callable] = {}

    def build(self, kind: str, bucket: int, n: int,
              prefix: int = 0) -> Callable:
        """The raw (unjitted) prefill callable for one signature."""
        from repro.models import encdec as ED
        from repro.models import lm as LM
        arch, axes, dtype = self.arch, self.cache_axes, self.cache_dtype
        qkv, qw = self.quant.quant_kv, self.quant.quant_weights

        def last_hidden(hidden, lens):
            return jax.vmap(lambda h, l: jax.lax.dynamic_slice_in_dim(
                h, l - 1, 1, axis=0))(hidden, lens)

        if kind == "encdec":
            def prefill(params, frames, flens, tokens, lens):
                params = dequantize_params(params) if qw else params
                enc_out = ED.encode(arch, params, frames, enc_lens=flens)
                caches = ED.make_caches(arch, n, bucket, dtype, kv_quant=qkv)
                hidden, rows = ED.decode(arch, params, tokens, enc_out,
                                         caches=caches, enc_lens=flens)
                logits = last_hidden(hidden, lens) @ params["unembed"]
                return invalidate_padding(rows, lens, axes), logits, enc_out
        elif kind == "vlm":
            def prefill(params, patches, tokens, lens):
                params = dequantize_params(params) if qw else params
                caches = REG.make_caches(arch, n, bucket, dtype, kv_quant=qkv)
                hidden, rows = LM.forward(arch, params, tokens, caches=caches,
                                          prefix_embeds=patches, seq_lens=lens)
                logits = LM.logits_fn(arch, params, last_hidden(hidden, lens))
                return invalidate_padding(rows, lens, axes), logits
        else:
            def prefill(params, tokens, lens):
                params = dequantize_params(params) if qw else params
                caches = REG.make_caches(arch, n, bucket, dtype, kv_quant=qkv)
                hidden, rows = LM.forward(arch, params, tokens, caches=caches,
                                          seq_lens=lens)
                logits = LM.logits_fn(arch, params, last_hidden(hidden, lens))
                return invalidate_padding(rows, lens, axes), logits

        return prefill

    def get(self, kind: str, bucket: int, n: int, prefix: int = 0,
            **jit_kw) -> Callable:
        """Cached ``mesh_jit`` of :meth:`build` (``jit_kw`` — e.g.
        ``out_shardings`` — applies on first build of a signature)."""
        key = (kind, bucket, n, prefix)
        fn = self._fns.get(key)
        if fn is None:
            fn = self._fns[key] = mesh_jit(
                self.mesh, self.build(kind, bucket, n, prefix), **jit_kw)
        return fn


class Scheduler:
    """Host-side slot lifecycle; all device mutation goes through jits.

    The engine threads ``(caches, state)`` through :meth:`admit`; the
    scheduler never holds device buffers itself, so donation stays linear
    (exactly one live reference to the grid at any time).

    When a :attr:`worker` (``serving.disagg.PrefillWorker``) is attached,
    admission is **routed to the prefill role**: :meth:`admit` dispatches
    each admission group to the worker (which runs the same bucketed
    prefill on the prefill mesh slice and streams the results over) and
    returns immediately; arriving KV is spliced into the decode grid by
    :meth:`admit` on a later call, only once every transferred leaf
    reports ready — the fused decode step never waits on a prefill.
    """

    def __init__(self, arch: ArchConfig, *, slots: int, max_len: int,
                 cache_dtype, mesh=None, sampling: SMP.SamplingParams = SMP.GREEDY,
                 min_bucket: int = MIN_BUCKET,
                 max_src_len: Optional[int] = None,
                 paged: bool = False, page_size: int = PG.DEFAULT_PAGE_SIZE,
                 kv_pages: Optional[int] = None, prefix_cache: bool = True,
                 quant: Optional[QuantConfig] = None, seed: int = 0,
                 spec_draft: Optional[ArchConfig] = None):
        self.arch = arch
        self.slots = slots
        self.max_len = max_len
        # per-request sampling keys are fold_in(PRNGKey(seed), rid): a
        # request's stochastic token stream is a function of (seed, rid)
        # alone — independent of admission timing, slot assignment,
        # lookahead depth, and the plan (the invariance serving_equiv's
        # sampled mode certifies)
        self.seed = seed
        self.max_src_len = max_src_len if max_src_len is not None else max_len
        self.cache_dtype = cache_dtype
        self.mesh = mesh
        self.sampling = sampling
        self.quant = quant if quant is not None else QuantConfig()
        self.min_bucket = bucket_floor(arch, max_len, min_bucket)
        self.aligned = not _bucketable(arch)
        self.cache_axes = REG.cache_axes(arch, cache_dtype,
                                         kv_quant=self.quant.quant_kv)
        self.paged = paged
        self.page_size = page_size
        self.pool: Optional[PG.PagePool] = None
        self.registry: Optional[PG.PrefixRegistry] = None
        self.slot_pages: Dict[int, List[int]] = {}
        if paged:
            PG.check_paged_supported(arch)
            self.table_len = PG.num_pages_per_slot(max_len, page_size)
            if kv_pages is None:
                kv_pages = PG.default_kv_pages(slots, max_len, page_size)
            self.pool = PG.PagePool(kv_pages, page_size)
            # MoE routing capacity couples batch rows, so a compute-skip
            # suffix prefill would perturb its bucket companions — MoE
            # pages its KV but does not prefix-share.
            if prefix_cache and arch.family != "moe":
                self.registry = PG.PrefixRegistry(self.pool)
            self._matches: Dict[int, Tuple[int, Tuple[int, ...],
                                           Optional[int]]] = {}
        self.queue: List[Request] = []
        self.active: Dict[int, Optional[Request]] = {i: None for i in range(slots)}
        self.prefill_factory = PrefillFactory(arch, self.cache_axes,
                                              cache_dtype, mesh=mesh,
                                              quant=self.quant)
        # speculative decoding: the draft model's prompt KV is prefilled
        # at admission too (full prompt, always dense and full-precision,
        # bucketed on its own) and spliced into state.draft_caches
        self.draft = spec_draft
        self.draft_axes = self.draft_factory = None
        if spec_draft is not None:
            self.draft_axes = REG.cache_axes(spec_draft, cache_dtype)
            self.draft_factory = PrefillFactory(spec_draft, self.draft_axes,
                                                cache_dtype, mesh=mesh)
        # disagg: attached by DisaggServingEngine; admissions then route
        # to the prefill role and splice on arrival (see _integrate)
        self.worker = None
        self.inflight: deque = deque()
        self._prefill_fns: Dict[Tuple, Callable] = {}
        self._splice_fns: Dict[Tuple, Callable] = {}
        self._admit_fns: Dict[Tuple, Callable] = {}
        # prefill telemetry: host wall per admission (dispatch + splice
        # enqueue — the serving loop's critical-path cost; the prefill
        # compute itself overlaps the running decode grid). Batched
        # admission attributes a dispatch's wall evenly to its requests
        # and additionally records per-dispatch wall and batch size.
        self.prefill_times = deque(maxlen=4096)
        self.prefill_prompt_lens = deque(maxlen=4096)
        self.prefill_dispatch_times = deque(maxlen=4096)
        self.prefill_batch_sizes = deque(maxlen=4096)

    # ------------------------------ queue ------------------------------
    def submit(self, req: Request) -> None:
        req._resolve_payload(self.arch.family)
        if self.arch.family == "encdec":
            if req.patch_embeds is not None:
                raise RequestValidationError(
                    f"request {req.rid}: patch_embeds is a vlm payload; "
                    f"encdec arch {self.arch.name} takes src_frames")
            if req.src_frames is None:
                raise RequestValidationError(
                    f"request {req.rid}: encdec arch {self.arch.name} needs "
                    f"source frames ([S_src, {self.arch.d_model}]) to encode")
            if len(req.src_frames) > self.max_src_len:
                raise RequestValidationError(
                    f"request {req.rid}: {len(req.src_frames)} source frames "
                    f"exceed max_src_len {self.max_src_len}")
        elif req.src_frames is not None:
            raise RequestValidationError(
                f"request {req.rid}: src_frames is an encdec payload; "
                f"{self.arch.family} arch {self.arch.name} takes "
                f"patch_embeds")
        if self.draft is not None and req.patch_embeds is not None:
            raise RequestValidationError(
                f"request {req.rid}: speculative serving drafts token "
                f"prompts only; patch_embeds are unsupported with a "
                f"draft model")
        total = len(req.prompt) + self._prefix_len(req)
        if total > self.max_len:
            raise RequestValidationError(
                f"request {req.rid}: prompt length {total} (incl. prefix) "
                f"exceeds max_len {self.max_len}")
        if total + req.max_new_tokens > self.max_len:
            raise RequestValidationError(
                f"request {req.rid}: prompt {total} + max_new_tokens "
                f"{req.max_new_tokens} exceeds max_len {self.max_len} "
                f"(the slot's KV row holds prompt and decoded tokens)")
        req.submitted_at = time.perf_counter()
        self.queue.append(req)

    def _prefix_len(self, req: Request) -> int:
        """Prefix tokens the prompt's cache row must also hold (vlm patch
        embeddings ride in the decoder grid; encdec frames do not)."""
        if req.patch_embeds is not None:
            return len(req.patch_embeds)
        return 0

    def has_active(self) -> bool:
        return any(r is not None for r in self.active.values())

    # -------------------------- jit factories --------------------------
    def _jit(self, fn, **kw):
        return mesh_jit(self.mesh, fn, **kw)

    def rebind_mesh(self, mesh) -> None:
        """Re-home the scheduler on a new mesh (live plan→plan migration,
        see ``ServingEngine.migrate``). Host bookkeeping — queue, active
        slots, page pool, prefix registry, rid→key seeding — is
        mesh-independent and survives untouched; the cached
        prefill/splice/admit jits were compiled under the old mesh
        context, so they are dropped and rebuild lazily on the new one."""
        if self.worker is not None:
            raise NotImplementedError(
                "rebind_mesh on a disaggregated scheduler: migrating a "
                "two-role deployment would re-split the prefill/decode "
                "slices; migrate the fused engine instead")
        self.mesh = mesh
        self.prefill_factory.mesh = mesh
        self.prefill_factory._fns.clear()
        if self.draft_factory is not None:
            self.draft_factory.mesh = mesh
            self.draft_factory._fns.clear()
        self._prefill_fns.clear()
        self._splice_fns.clear()
        self._admit_fns.clear()

    def _get_prefill(self, kind: str, bucket: int, n: int,
                     prefix: int = 0) -> Callable:
        """Batched prefill step for ``n`` same-bucket requests (see
        :class:`PrefillFactory` for the per-kind signatures)."""
        return self.prefill_factory.get(kind, bucket, n, prefix)

    def _get_splice(self, n: int) -> Callable:
        fn = self._splice_fns.get(n)
        if fn is None:
            axes = self.cache_axes
            fn = self._splice_fns[n] = self._jit(
                lambda grid, rows, slots: splice_rows(grid, rows, slots, axes),
                donate_argnums=(0,))
        return fn

    def _admit_keys(self, rids: jax.Array) -> jax.Array:
        """Per-request sampling keys: ``fold_in(PRNGKey(seed), rid)``.
        Keying on the request id (not the slot) makes a sampled stream
        reproducible whatever slot, step, or plan the request lands on."""
        base = jax.random.PRNGKey(self.seed)
        return jax.vmap(lambda r: jax.random.fold_in(base, r))(rids)

    def _get_draft_splice(self, n: int) -> Callable:
        key = ("draft_splice", n)
        fn = self._splice_fns.get(key)
        if fn is None:
            axes = self.draft_axes
            fn = self._splice_fns[key] = self._jit(
                lambda grid, rows, slots: splice_rows(grid, rows, slots, axes),
                donate_argnums=(0,))
        return fn

    def _get_admit(self, n: int, enc: bool) -> Callable:
        key = (n, enc)
        fn = self._admit_fns.get(key)
        if fn is None:
            sampling = self.sampling
            admit_keys = self._admit_keys

            def admit(state, slots, rids, logits, positions, max_new,
                      enc_out=None, enc_len=None):
                rng, toks = SMP.sample(logits[:, -1], admit_keys(rids),
                                       sampling)
                return admit_rows(state, slots, toks, positions, max_new,
                                  rng, enc_out=enc_out, enc_len=enc_len)

            if enc:
                fn = self._jit(admit, donate_argnums=(0,))
            else:
                fn = self._jit(lambda state, slots, rids, logits, positions,
                               max_new: admit(state, slots, rids, logits,
                                              positions, max_new),
                               donate_argnums=(0,))
            self._admit_fns[key] = fn
        return fn

    # ------------------------- paged jit factories ----------------------
    def _get_page_splice(self, n: int) -> Callable:
        key = ("page_splice", n)
        fn = self._splice_fns.get(key)
        if fn is None:
            fn = self._splice_fns[key] = self._jit(
                PG.splice_pages, donate_argnums=(0,))
        return fn

    def _get_copy(self, n: int) -> Callable:
        key = ("page_copy", n)
        fn = self._splice_fns.get(key)
        if fn is None:
            fn = self._splice_fns[key] = self._jit(
                PG.copy_pages, donate_argnums=(0,))
        return fn

    def _get_admit_paged(self, n: int) -> Callable:
        key = (n, "paged")
        fn = self._admit_fns.get(key)
        if fn is None:
            sampling = self.sampling
            admit_keys = self._admit_keys

            def admit(state, slots, rids, logits, positions, max_new,
                      page_rows):
                rng, toks = SMP.sample(logits[:, -1], admit_keys(rids),
                                       sampling)
                return admit_rows(state, slots, toks, positions, max_new,
                                  rng, page_rows=page_rows)

            fn = self._admit_fns[key] = self._jit(admit, donate_argnums=(0,))
        return fn

    def _get_prefill_shared(self, bucket: int, n: int, span: int) -> Callable:
        """Compute-skip suffix prefill: gather the ``span`` prefix pages
        per row into dense KV blocks (``pages.gather_prefix``) and run
        only the suffix tokens through the stack, queries positioned at
        ``m..m+bucket-1`` (``models.blocks._shared_prefix_attention``).
        Returned rows carry absolute ``pos`` values, so the ordinary
        paged splice routes them past the shared region."""
        key = ("lm_shared", bucket, n, span)
        fn = self._prefill_fns.get(key)
        if fn is not None:
            return fn
        from repro.models import lm as LM
        arch, axes = self.arch, self.cache_axes
        qw = self.quant.quant_weights

        def prefill(params, pools, page_rows, m_arr, tokens, lens):
            params = dequantize_params(params) if qw else params
            pre = PG.gather_prefix(pools, page_rows, m_arr)
            positions = m_arr[:, None] + jnp.broadcast_to(
                jnp.arange(bucket, dtype=jnp.int32)[None], (n, bucket))
            hidden, rows = LM.forward(arch, params, tokens, caches=pre,
                                      positions=positions, seq_lens=lens)
            suf_lens = lens - m_arr
            last = jax.vmap(lambda h, l: jax.lax.dynamic_slice_in_dim(
                h, l - 1, 1, axis=0))(hidden, suf_lens)
            logits = LM.logits_fn(arch, params, last)
            return invalidate_padding(rows, lens, axes), logits

        fn = self._prefill_fns[key] = self._jit(prefill)
        return fn

    # ------------------------- page accounting --------------------------
    def _alloc_slot_pages(self, req: Request):
        """Reserve the physical pages one admission needs: fresh pages
        covering prompt + decode budget, with any matched prefix aliased
        (refcount+1) ahead of them. Returns ``(row [table_len] int32,
        owned pages, (cow_dst, cow_src) | None)``; raises
        :class:`pages.PagePoolExhausted` when the pool cannot satisfy.
        """
        total = len(req.prompt) + self._prefix_len(req)
        need = -(-(total + req.max_new_tokens) // self.page_size)
        waiting = [req.rid] + [r.rid for r in self.queue]
        row = np.zeros((self.table_len,), np.int32)
        match = self._matches.get(req.rid) if self.registry else None
        if match is not None and match[0]:
            m, chain, frontier = match
            j = len(chain)
            fresh = self.pool.alloc(need - j, waiting=waiting)
            self.pool.retain(chain)
            row[:j] = chain
            row[j:need] = fresh
            owned = list(chain) + fresh
            # mid-page match: the sharer's suffix continues inside the
            # owner's frontier page, so it writes into a private copy
            cow = (fresh[0], frontier) if frontier is not None else None
            return row, owned, cow
        pages = self.pool.alloc(need, waiting=waiting)
        row[:need] = pages
        return row, pages, None

    def release_slot(self, slot: int) -> None:
        """Return a retired slot's pages to the pool (refcount−1; pages
        still pinned by the prefix registry or a sharer stay resident)."""
        pages = self.slot_pages.pop(slot, None)
        if pages is not None:
            self.pool.release(pages)

    # ---------------------------- admission ----------------------------
    def _group_key(self, req: Request) -> Tuple[str, int, int]:
        total = len(req.prompt) + self._prefix_len(req)
        bucket = bucket_len(total, self.max_len, aligned=self.aligned,
                            min_bucket=self.min_bucket)
        if self.arch.family == "encdec":
            return ("encdec", bucket, 0)
        if req.patch_embeds is not None:
            return ("vlm", bucket, len(req.patch_embeds))
        if self.registry is not None:
            m, chain, frontier = self.registry.lookup(
                np.asarray(req.prompt, np.int32))
            if m:
                # compute-skip admission: only the unmatched suffix runs
                # through prefill, bucketed on its own length. The third
                # key component is the shared prefix length, so every
                # group member gathers the same page span.
                self._matches[req.rid] = (m, chain, frontier)
                suf_bucket = bucket_len(total - m, self.max_len,
                                        aligned=self.aligned,
                                        min_bucket=self.min_bucket)
                return ("lm_shared", suf_bucket, m)
        return ("lm", bucket, 0)

    def _marshal_frames(self, group):
        """Host-side [n, max_src, D] frame grid + true lengths (encdec)."""
        n = len(group)
        frames = np.zeros((n, self.max_src_len, self.arch.d_model),
                          np.float32)
        flens = np.zeros((n,), np.int32)
        for i, (req, _) in enumerate(group):
            flens[i] = len(req.src_frames)
            frames[i, :flens[i]] = req.src_frames
        return frames, flens

    def _integrate(self, caches, state: DecodeState):
        """Splice arrived prefill→decode transfers into the grid.

        Waves integrate in dispatch order, and only once **every**
        transferred leaf reports ready (non-blocking ``is_ready``), so
        the fused decode step the engine dispatches right after never
        data-depends on an in-flight transfer — a prefill storm on the
        other slice cannot stall the decode stream. The slots were
        reserved at dispatch; until the splice lands they are device-
        inactive and the serve step treats them as inert rows.
        """
        while self.inflight:
            inf = self.inflight[0]
            if not inf.ready():
                break
            self.inflight.popleft()
            t0 = time.perf_counter()
            n = len(inf.group)
            slots_j = jnp.asarray(inf.slots)
            lens_j = jnp.asarray(inf.lens)
            max_new_j = jnp.asarray(inf.max_new)
            rids_j = jnp.asarray(inf.rids)
            rows, logits = inf.outs[0], inf.outs[1]
            if self.paged:
                page_rows_j = jnp.asarray(inf.page_rows)
                caches = self._get_page_splice(n)(caches, rows, page_rows_j)
                state = self._get_admit_paged(n)(
                    state, slots_j, rids_j, logits, lens_j, max_new_j,
                    page_rows_j)
            elif inf.kind == "encdec":
                caches = self._get_splice(n)(caches, rows, slots_j)
                state = self._get_admit(n, enc=True)(
                    state, slots_j, rids_j, logits, lens_j, max_new_j,
                    inf.outs[2], jnp.asarray(inf.flens))
            else:
                caches = self._get_splice(n)(caches, rows, slots_j)
                state = self._get_admit(n, enc=False)(
                    state, slots_j, rids_j, logits, lens_j, max_new_j)
            wall = time.perf_counter() - t0
            self.prefill_dispatch_times.append(wall + inf.dispatch_wall)
            self.prefill_batch_sizes.append(n)
            for req, _ in inf.group:
                self.prefill_times.append((wall + inf.dispatch_wall) / n)
                self.prefill_prompt_lens.append(len(req.prompt))
        return caches, state

    def admit(self, params, caches, state: DecodeState):
        """Fill free slots from the queue; returns updated (caches, state).

        All waiting requests that land in the same bucket become one
        batched prefill + one batched splice + one state scatter — O(1)
        dispatches per bucket, however many requests arrived. Pure
        dispatch: the work is enqueued on the device stream and overlaps
        the in-flight decode step — the serving-loop analog of the
        paper's §4.3 transfer/compute overlap.

        With a disagg :attr:`worker` attached the group's prefill runs on
        the prefill slice instead and this call only *dispatches* (and
        integrates previously-arrived waves); see :meth:`_integrate`.

        Speculative engines pass ``params`` as ``{"target", "draft"}``:
        every admission additionally prefills the draft model over the
        **full** prompt (dense, full-precision, bucketed on its own —
        even for prefix-shared groups whose target prefill is
        suffix-only) and splices the rows into ``state.draft_caches``.
        """
        dparams = None
        if self.draft is not None:
            dparams = params["draft"]
            params = params["target"]
        if self.worker is not None:
            caches, state = self._integrate(caches, state)
        free = [s for s, occ in self.active.items() if occ is None]
        take = min(len(free), len(self.queue))
        if take == 0:
            return caches, state
        pairs = list(zip(self.queue[:take], free))
        del self.queue[:take]
        if self.paged:
            self._matches.clear()
        groups: Dict[Tuple[str, int, int], List[Tuple[Request, int]]] = {}
        for req, slot in pairs:
            groups.setdefault(self._group_key(req), []).append((req, slot))

        admitted: set = set()
        exhausted = False
        for (kind, bucket, prefix), group in sorted(groups.items()):
            if exhausted:
                break
            page_rows_np: List[np.ndarray] = []
            owned_list: List[List[int]] = []
            cows: List[Optional[Tuple[int, int]]] = []
            if self.paged:
                kept = []
                for req, slot in group:
                    try:
                        row, owned, cow = self._alloc_slot_pages(req)
                    except PG.PagePoolExhausted:
                        # degrade to queueing: un-admitted requests go
                        # back to the queue head and wait for retiring
                        # slots (or a registry eviction) to free pages
                        exhausted = True
                        if self.registry is not None:
                            self.registry.evict_unreferenced()
                        break
                    kept.append((req, slot))
                    page_rows_np.append(row)
                    owned_list.append(owned)
                    cows.append(cow)
                group = kept
                if not group:
                    continue
            with TraceAnnotation(SP.PREFILL, bucket=bucket, size=len(group),
                                 rids=tuple(r.rid for r, _ in group)):
                t0 = time.perf_counter()
                n = len(group)
                width = bucket if kind == "lm_shared" else bucket - prefix
                toks = np.zeros((n, width), np.int32)
                lens = np.zeros((n,), np.int32)
                slots_arr = np.zeros((n,), np.int32)
                max_new = np.zeros((n,), np.int32)
                rids_arr = np.zeros((n,), np.int32)
                for i, (req, slot) in enumerate(group):
                    s = len(req.prompt)
                    if kind == "lm_shared":  # suffix tokens only; lens = total
                        toks[i, :s - prefix] = req.prompt[prefix:]
                        lens[i] = s
                    else:
                        toks[i, :s] = req.prompt
                        lens[i] = s + prefix if kind == "vlm" else s
                    slots_arr[i] = slot
                    max_new[i] = req.max_new_tokens
                    rids_arr[i] = req.rid
                if self.worker is not None:
                    # disagg: run this group's prefill on the prefill slice;
                    # the outputs stream over asynchronously and splice in a
                    # later _integrate call. Slots are reserved host-side now
                    # (device-inactive until the splice lands).
                    frames = flens = patches = None
                    if kind == "encdec":
                        frames, flens = self._marshal_frames(group)
                    elif kind == "vlm":
                        patches = np.stack([req.patch_embeds for req, _ in group]
                                           ).astype(np.float32)
                    outs = self.worker.dispatch(kind, bucket, prefix, toks=toks,
                                                lens=lens, frames=frames,
                                                flens=flens, patches=patches)
                    self.inflight.append(_Inflight(
                        kind=kind, outs=outs, group=list(group), slots=slots_arr,
                        lens=lens, max_new=max_new, rids=rids_arr, flens=flens,
                        page_rows=(np.stack(page_rows_np) if self.paged
                                   else None),
                        dispatch_wall=time.perf_counter() - t0))
                    now = time.perf_counter()
                    for i, (req, slot) in enumerate(group):
                        self.active[slot] = req
                        req.admitted_at = now
                        admitted.add(req.rid)
                        if self.paged:
                            self.slot_pages[slot] = owned_list[i]
                    continue
                slots_j = jnp.asarray(slots_arr)
                lens_j = jnp.asarray(lens)
                rids_j = jnp.asarray(rids_arr)
                if kind == "lm_shared":
                    page_rows_j = jnp.asarray(np.stack(page_rows_np))
                    cow_pairs = [c for c in cows if c is not None]
                    if cow_pairs:
                        dst = jnp.asarray([d for d, _ in cow_pairs], jnp.int32)
                        src = jnp.asarray([s_ for _, s_ in cow_pairs], jnp.int32)
                        caches = self._get_copy(len(cow_pairs))(caches, dst, src)
                    span = -(-prefix // self.page_size)
                    m_arr = jnp.full((n,), prefix, jnp.int32)
                    rows, logits = self._get_prefill_shared(bucket, n, span)(
                        params, caches, page_rows_j[:, :span], m_arr,
                        jnp.asarray(toks), lens_j)
                    caches = self._get_page_splice(n)(caches, rows, page_rows_j)
                    state = self._get_admit_paged(n)(
                        state, slots_j, rids_j, logits, lens_j,
                        jnp.asarray(max_new), page_rows_j)
                elif kind == "encdec":
                    frames, flens = self._marshal_frames(group)
                    rows, logits, enc_out = self._get_prefill(
                        kind, bucket, n)(params, jnp.asarray(frames),
                                         jnp.asarray(flens), jnp.asarray(toks),
                                         lens_j)
                    caches = self._get_splice(n)(caches, rows, slots_j)
                    state = self._get_admit(n, enc=True)(
                        state, slots_j, rids_j, logits, lens_j,
                        jnp.asarray(max_new), enc_out, jnp.asarray(flens))
                else:
                    if kind == "vlm":
                        patches = np.stack([req.patch_embeds for req, _ in group]
                                           ).astype(np.float32)
                        rows, logits = self._get_prefill(kind, bucket, n, prefix)(
                            params, jnp.asarray(patches), jnp.asarray(toks),
                            lens_j)
                    else:
                        rows, logits = self._get_prefill(kind, bucket, n)(
                            params, jnp.asarray(toks), lens_j)
                    if self.paged:
                        # prefill compute stays dense and bucketed — paging
                        # only redirects the splice target to the page pool
                        page_rows_j = jnp.asarray(np.stack(page_rows_np))
                        caches = self._get_page_splice(n)(caches, rows,
                                                          page_rows_j)
                        state = self._get_admit_paged(n)(
                            state, slots_j, rids_j, logits, lens_j,
                            jnp.asarray(max_new), page_rows_j)
                    else:
                        caches = self._get_splice(n)(caches, rows, slots_j)
                        state = self._get_admit(n, enc=False)(
                            state, slots_j, rids_j, logits, lens_j,
                            jnp.asarray(max_new))
                if self.draft is not None:
                    # draft prompt KV: full-prompt dense prefill at the
                    # group's full-length bucket (a prefix-shared group's
                    # target prefill is suffix-only, the draft's never is),
                    # spliced into the state's draft grid
                    dbucket = bucket_len(int(lens.max()), self.max_len,
                                         min_bucket=MIN_BUCKET)
                    dtoks = np.zeros((n, dbucket), np.int32)
                    for i, (req, _) in enumerate(group):
                        dtoks[i, :len(req.prompt)] = req.prompt
                    drows, _ = self.draft_factory.get("lm", dbucket, n)(
                        dparams, jnp.asarray(dtoks), lens_j)
                    state = dataclasses.replace(
                        state, draft_caches=self._get_draft_splice(n)(
                            state.draft_caches, drows, slots_j))
                now = time.perf_counter()
                for i, (req, slot) in enumerate(group):
                    self.active[slot] = req
                    req.admitted_at = now
                    admitted.add(req.rid)
                    if self.paged:
                        self.slot_pages[slot] = owned_list[i]
                        if self.registry is not None and req.patch_embeds is None:
                            total = len(req.prompt)
                            cover = -(-total // self.page_size)
                            self.registry.register(
                                np.asarray(req.prompt, np.int32),
                                page_rows_np[i][:cover].tolist())
                wall = time.perf_counter() - t0
                self.prefill_dispatch_times.append(wall)
                self.prefill_batch_sizes.append(n)
                for req, _ in group:
                    self.prefill_times.append(wall / n)
                    self.prefill_prompt_lens.append(len(req.prompt))
        leftover = [req for req, _ in pairs if req.rid not in admitted]
        if leftover:  # pool exhausted mid-wave: requeue in arrival order
            self.queue[:0] = leftover
        return caches, state

    def reset_stats(self) -> None:
        self.prefill_times.clear()
        self.prefill_prompt_lens.clear()
        self.prefill_dispatch_times.clear()
        self.prefill_batch_sizes.clear()
