"""Device-resident serving engine: lookahead dispatch over a slot grid.

The engine is the thin top of the ``serving`` package (see also
``state.py`` / ``sampler.py`` / ``scheduler.py``): it wires the plan, the
fused jitted ``serve_step`` (donated caches + :class:`DecodeState`, see
``models.registry.build_serve_step``), and the scheduler together, and
runs **one-step-lookahead dispatch** — the serving-loop analog of the
paper's §4.3 tile double buffering. Step *N+1* is dispatched before step
*N*'s per-step record is read back, so the host's Python bookkeeping
overlaps the device's decode compute instead of serialising with it:

    step N:    [retire N-2] [admit] [dispatch N] ──┐ device runs N
    step N+1:  [retire N-1] [admit] [dispatch N+1] ┘ host never waits

Public surface (unchanged from the monolithic engine): construct with an
:class:`~repro.core.execution_plan.ExecutionPlan` first, then
``submit`` / ``step`` / ``run_until_drained`` and the ``step_stats`` /
``prefill_stats`` telemetry hooks. The old ``ServingEngine(arch, ...)``
construction still works but is deprecated (it routes through the same
scheduler, unsharded).
"""
from __future__ import annotations

import dataclasses
import time
import warnings
from collections import deque
from typing import Callable, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import StepTraceAnnotation, TraceAnnotation
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs.base import ArchConfig
from repro.core.execution_plan import ExecutionPlan
from repro.models import registry as REG
from repro.quant import dequantize_params, quantize_params
from repro.serving import spans as SP
from repro.serving.config import PagingConfig, ServeConfig
from repro.serving.pages import DEFAULT_PAGE_SIZE as PG_DEFAULT
from repro.serving.sampler import GREEDY, SamplingParams
from repro.serving.scheduler import Request, Scheduler, mesh_jit
from repro.serving.state import DecodeState, decode_state_dims, make_decode_state

__all__ = ["ServingEngine", "Request", "SamplingParams", "DecodeState",
           "IncompleteDrainError", "MigrationReport", "ServeConfig"]


@dataclasses.dataclass(frozen=True)
class MigrationReport:
    """One plan→plan live migration (``ServingEngine.migrate``).

    Byte fields follow the disagg transfer accounting: ``*_moved_bytes``
    are the logical bytes of leaves whose sharding actually changed
    (a leaf equivalently placed on both plans is a no-op ``device_put``
    and counts as kept); ``dst_shard_bytes`` is the analytic per-device
    total the destination placement implies, reconciled against
    ``actual_shard_bytes`` read back from the committed arrays within the
    disagg tolerance band."""

    from_axes: tuple
    to_axes: tuple
    stall_s: float             # wall from migrate() entry to transfer done
    flushed_records: int       # lookahead records retired before the move
    active_slots: int          # in-flight streams carried across
    drained_slots: int         # of those, slots whose rows physically moved
    params_moved_bytes: int
    caches_moved_bytes: int
    state_moved_bytes: int
    logical_bytes: int         # Σ global bytes over params + caches + state
    moved_bytes: int           # Σ logical bytes that physically moved
    dst_shard_bytes: int       # analytic bytes landed across all devices
    actual_shard_bytes: int    # committed bytes read back after the put
    verified: bool


class IncompleteDrainError(RuntimeError):
    """``run_until_drained`` hit ``max_steps`` with requests in flight."""

    def __init__(self, msg: str, unfinished: List[int]):
        super().__init__(msg)
        self.unfinished = unfinished


def _record_ready(rec) -> bool:
    """True when every leaf of a step record has finished on device
    (non-blocking)."""
    return all(leaf.is_ready() for leaf in jax.tree.leaves(rec))


class ServingEngine:
    """Plan-aware construction takes an :class:`ExecutionPlan` first::

        engine = ServingEngine(plan, params,
                               config=ServeConfig(slots=4, max_len=128))

    which places params, the cache grid and the decode state with the
    plan's NamedShardings and jits the fused decode step under the plan's
    mesh. ``sampling`` selects on-device token choice (default greedy);
    ``lookahead`` is the dispatch depth (1 = double-buffered, 0 =
    synchronous like the old engine).

    Passing an ``ArchConfig`` first is the legacy (unsharded)
    construction: still supported, now with a ``DeprecationWarning``.
    """

    def __init__(self, arch, params, *, config: Optional[ServeConfig] = None,
                 slots: Optional[int] = None, max_len: Optional[int] = None,
                 ctx=None, eos_id: Optional[int] = None, dtype=jnp.float32,
                 on_step: Optional[Callable[[Dict[str, float]], None]] = None,
                 sampling: Optional[SamplingParams] = None,
                 lookahead: Optional[int] = None, seed: Optional[int] = None,
                 max_src_len: Optional[int] = None,
                 paged: Optional[bool] = None,
                 page_size: Optional[int] = None,
                 kv_pages: Optional[int] = None,
                 prefix_cache: Optional[bool] = None):
        import dataclasses as _dc
        if config is None:
            if slots is None or max_len is None:
                raise TypeError("ServingEngine needs config=ServeConfig(...) "
                                "or explicit slots=/max_len=")
            config = ServeConfig(
                slots=slots, max_len=max_len, eos_id=eos_id,
                seed=0 if seed is None else seed, sampling=sampling,
                lookahead=1 if lookahead is None else lookahead,
                max_src_len=max_src_len,
                paging=PagingConfig(
                    paged=bool(paged), page_size=page_size, kv_pages=kv_pages,
                    prefix_cache=(True if prefix_cache is None
                                  else prefix_cache)))
        elif any(v is not None for v in (slots, max_len, eos_id, sampling,
                                         lookahead, seed, max_src_len, paged,
                                         page_size, kv_pages, prefix_cache)):
            raise TypeError("ServingEngine: pass either config= or the flat "
                            "serve kwargs, not both")
        config = config.resolve()
        slots, max_len = config.slots, config.max_len
        seed = config.seed
        spec = config.spec
        if spec is not None and config.disagg is not None:
            raise NotImplementedError(
                "speculative decoding does not compose with disaggregated "
                "serving yet: the draft's prompt KV would have to stream "
                "across role slices alongside the target's")
        self.plan: Optional[ExecutionPlan] = None
        self.mesh = None
        if isinstance(arch, ExecutionPlan):
            self.plan = arch
            exe = self.plan.compile()
            arch = self.plan.arch
            ctx = exe.ctx if ctx is None else ctx
            self.mesh = exe.mesh
        else:
            warnings.warn(
                "ServingEngine(arch, ...) construction is deprecated; plan "
                "the cell and use ExecutionPlan.compile().serve(...) (or "
                "pass the ExecutionPlan first) so params and caches are "
                "placed with the plan's shardings",
                DeprecationWarning, stacklevel=2)
        self.arch: ArchConfig = arch
        self.slots = slots
        self.max_len = max_len
        self.max_src_len = config.max_src_len
        self.eos_id = config.eos_id
        self.sampling = config.sampling
        self.lookahead = config.lookahead
        paged = config.paging.paged
        self.paged = paged
        self.quant = config.quant
        if spec is not None and spec.draft is None:
            draft = self.plan.draft if self.plan is not None else None
            if draft is None:
                raise ValueError(
                    "ServeConfig.spec set but no draft arch: pass "
                    "SpecConfig(draft=...) or plan the cell with "
                    "repro.plan(..., draft=...)")
            spec = _dc.replace(spec, draft=draft)
            config = _dc.replace(config, spec=spec)
        self.spec = spec
        if spec is not None and not (isinstance(params, dict)
                                     and set(params) == {"target", "draft"}):
            raise TypeError(
                "speculative serving takes params as "
                "{'target': <target tree>, 'draft': <draft tree>} "
                "(Executable.serve builds the pair for you)")
        is_encdec = arch.family == "encdec"
        if paged:
            from repro.serving import pages as PG
            PG.check_paged_supported(arch)
            self.page_size = config.paging.page_size or PG.DEFAULT_PAGE_SIZE
            self.kv_pages = (config.paging.kv_pages
                             if config.paging.kv_pages is not None else
                             PG.default_kv_pages(slots, max_len,
                                                 self.page_size))
            table_len = PG.num_pages_per_slot(max_len, self.page_size)
            self.caches = PG.make_paged_caches(arch, self.kv_pages,
                                               self.page_size, dtype,
                                               kv_quant=self.quant.quant_kv)
        else:
            self.page_size = config.paging.page_size
            self.kv_pages = config.paging.kv_pages
            table_len = None

            def grid():
                return REG.make_caches(arch, slots, max_len, dtype,
                                       kv_quant=self.quant.quant_kv)
            if self.plan is None:
                self.caches = grid()
            else:
                # made in the plan's shardings: a grid larger than one
                # device's memory (Yi-9B whole at 128 × 2048: 25.8 GB)
                # never lands whole on the first device
                self.caches = mesh_jit(self.mesh, grid, out_shardings=(
                    self.plan.cache_shardings(jax.eval_shape(grid),
                                              self.mesh)))()
        # the resolved surface (page geometry made concrete) — what
        # `engine.config` exposes
        self.config: ServeConfig = _dc.replace(
            config, paging=_dc.replace(config.paging,
                                       page_size=self.page_size,
                                       kv_pages=self.kv_pages))
        # speculative decoding: the draft's dense KV grid rides inside the
        # DecodeState (threaded through the donated fused step alongside
        # the target caches); the draft always runs dense + full-precision
        draft_caches = draft_dims = None
        if spec is not None:
            draft_caches = REG.make_caches(spec.draft, slots, max_len, dtype)
            draft_dims = REG.cache_dims(spec.draft)
        self.state = make_decode_state(
            slots, seed,
            enc_shape=(self.max_src_len, arch.d_model) if is_encdec else None,
            enc_dtype=dtype, table_len=table_len, draft_caches=draft_caches)
        if self.plan is not None:
            from repro.core.xfer import tree_shardings
            replicated = NamedSharding(self.mesh, P())
            if spec is not None:
                # target params take the plan's shardings; the draft is
                # small by construction and is replicated on every device
                params = {"target": jax.device_put(
                    params["target"],
                    self.plan.param_shardings(params["target"], self.mesh)),
                    "draft": jax.device_put(params["draft"], replicated)}
            else:
                params = jax.device_put(
                    params, self.plan.param_shardings(params, self.mesh))
            if paged:
                # page pools have no slot axis, so the plan's dense cache
                # shardings don't apply: every device holds the whole pool
                # (gathered reads are resharded on the fly)
                self.caches = jax.device_put(self.caches, replicated)
            self.state = jax.device_put(
                self.state, tree_shardings(self.plan.ctx(self.mesh),
                                           self.state,
                                           decode_state_dims(
                                               enc=is_encdec, paged=paged,
                                               draft_dims=draft_dims)))
        if self.quant.quant_weights:
            # int8 weights stay HBM-resident; every step (prefill and
            # decode alike) rehydrates a transient fp working copy inside
            # its own jit. Quantising on device keeps the placed shardings
            # (the QTensor's int8 leaf inherits the param's placement).
            # Spec engines quantise only the target: a draft cheap enough
            # to speculate with gains nothing from int8 residency.
            if spec is not None:
                params = dict(params, target=mesh_jit(
                    self.mesh, quantize_params)(params["target"]))
            else:
                params = mesh_jit(self.mesh, quantize_params)(params)
        self.params = params
        step_fn = REG.build_serve_step(arch, ctx, sampling=self.sampling,
                                       eos_id=self.eos_id, paged=paged,
                                       spec=spec)
        if self.quant.quant_weights:
            inner_step = step_fn
            if spec is not None:
                step_fn = (lambda params, caches, state:
                           inner_step({"target":
                                       dequantize_params(params["target"]),
                                       "draft": params["draft"]},
                                      caches, state))
            else:
                step_fn = (lambda params, caches, state:
                           inner_step(dequantize_params(params), caches,
                                      state))
        # caches and state are donated: the per-step KV-grid copy the old
        # engine paid (fresh output buffers every step) goes away.
        self._serve_step = mesh_jit(self.mesh, step_fn, donate_argnums=(1, 2))
        self.scheduler = Scheduler(arch, slots=slots, max_len=max_len,
                                   cache_dtype=dtype, mesh=self.mesh,
                                   sampling=self.sampling,
                                   max_src_len=self.max_src_len,
                                   paged=paged,
                                   page_size=(self.page_size if paged
                                              else PG_DEFAULT),
                                   kv_pages=self.kv_pages,
                                   prefix_cache=self.config.paging.prefix_cache,
                                   quant=self.quant, seed=seed,
                                   spec_draft=(spec.draft if spec is not None
                                               else None))
        self.completed: List[Request] = []
        self._pending: deque = deque()  # dispatched, unread step records
        # elastic serving: migrate() appends a MigrationReport per resize;
        # Executable.serve attaches a runtime.elastic.LoadController here
        # when ServeConfig.elastic is set (see maybe_resize())
        self.migrations: List[MigrationReport] = []
        self.elastic = None
        # step-timing hooks (repro.bench serve scenarios read these):
        # wall seconds per step() call and tokens retired per call, plus
        # host admission-path wall per prefill. Bounded deques: telemetry
        # covers a sliding window so long-lived engines stay bounded.
        self.on_step = on_step
        self.step_times = deque(maxlen=4096)
        self.step_token_counts = deque(maxlen=4096)
        # queue backlog per step() call, and per-retire commit accounting
        # (emitted tokens vs active slot-steps — the speculative
        # acceptance telemetry; exactly 1.0 on a non-spec engine except
        # for EOS-at-prefill slots)
        self.queue_depths = deque(maxlen=4096)
        self.retired_emits = deque(maxlen=4096)
        self.retired_active = deque(maxlen=4096)
        # per step() call: seconds blocked reading step records (the
        # loop's one host↔device sync), in Scheduler.admit, and in the
        # serve step's dispatch; the same phases as the serve.* spans
        self.record_wait_times = deque(maxlen=4096)
        self.admit_times = deque(maxlen=4096)
        self.dispatch_times = deque(maxlen=4096)
        self._record_wait = 0.0
        self._step_num = 0  # serve.step's step_num; never reset
        self._collectives: Optional[Dict[str, Dict[str, float]]] = None

    # ------------------------- queue / slot views -------------------------
    @property
    def queue(self) -> List[Request]:
        return self.scheduler.queue

    @property
    def active(self) -> Dict[int, Optional[Request]]:
        return self.scheduler.active

    @property
    def prefill_times(self):
        return self.scheduler.prefill_times

    @property
    def prefill_prompt_lens(self):
        return self.scheduler.prefill_prompt_lens

    def submit(self, req: Request):
        self.scheduler.submit(req)

    def unfinished(self) -> List[int]:
        """rids still queued or decoding (including unretired records)."""
        rids = [r.rid for r in self.queue]
        rids += [r.rid for r in self.active.values() if r is not None]
        return rids

    # ---------------------------- decode loop ----------------------------
    def step(self):
        """One serving-loop iteration: retire the record(s) that fell out
        of the lookahead window, admit into the freed slots, dispatch the
        next fused decode step. Each phase runs under its ``serve.*``
        profiler span (``serving/spans.py``) and its seconds go to the
        per-step counters beside ``step_times``."""
        t0 = time.perf_counter()
        self._record_wait = 0.0
        with StepTraceAnnotation(SP.STEP, step_num=self._step_num):
            self.queue_depths.append(len(self.queue))
            emitted = 0
            with TraceAnnotation(SP.RETIRE):
                while len(self._pending) > self.lookahead:
                    emitted += self._retire_one()
                # opportunistic early retire: a record whose device work
                # already completed costs nothing to read now, and freeing
                # its finished slots one step earlier avoids idle-slot
                # decode steps under churn. Records still inside the
                # lookahead window are only ever read when ready — the
                # loop never blocks here.
                while self._pending and _record_ready(self._pending[0]):
                    emitted += self._retire_one()
            t1 = time.perf_counter()
            with TraceAnnotation(SP.ADMIT):
                self.caches, self.state = self.scheduler.admit(
                    self.params, self.caches, self.state)
            t2 = time.perf_counter()
            with TraceAnnotation(SP.DISPATCH):
                state, caches, record = self._serve_step(
                    self.params, self.caches, self.state)
            t3 = time.perf_counter()
            self.state, self.caches = state, caches
            self._pending.append(record)
            if self.lookahead == 0:
                with TraceAnnotation(SP.RETIRE):
                    while self._pending:
                        emitted += self._retire_one()
        self._step_num += 1
        wall = time.perf_counter() - t0
        self.step_times.append(wall)
        self.step_token_counts.append(emitted)
        self.record_wait_times.append(self._record_wait)
        self.admit_times.append(t2 - t1)
        self.dispatch_times.append(t3 - t2)
        if self.on_step is not None:
            self.on_step({"step": len(self.step_times) - 1,
                          "wall_s": wall, "tokens": emitted,
                          "record_wait_s": self._record_wait,
                          "admit_s": t2 - t1, "dispatch_s": t3 - t2})

    def _retire_one(self) -> int:
        """Read one step record back (the only host↔device sync in the
        loop) and apply it: append emitted tokens, free finished slots.
        The blocking read runs under ``serve.record_wait`` and its seconds
        add to the step's ``record_wait`` counter; the read's end stamps
        a request's ``first_token_at`` and ``finished_at``.

        Speculative steps return 2-D ``token``/``emit`` ([slots, k+1] —
        up to ``k+1`` commits per slot per step); the plain step's 1-D
        record is handled as the single-column case."""
        rec = self._pending.popleft()
        t = time.perf_counter()
        with TraceAnnotation(SP.RECORD_WAIT):
            token = np.asarray(rec["token"])
            emit = np.asarray(rec["emit"])
            finished = np.asarray(rec["finished"])
        now = time.perf_counter()
        self._record_wait += now - t
        if token.ndim == 1:
            token = token[:, None]
            emit = emit[:, None]
        # emit.any(1) | finished == active-at-dispatch (an active slot
        # either emits or finishes without emitting: EOS at prefill)
        self.retired_emits.append(int(emit.sum()))
        self.retired_active.append(int((emit.any(axis=1) | finished).sum()))
        count = 0
        for slot, req in self.active.items():
            if req is None:
                continue
            for j in range(token.shape[1]):
                if emit[slot, j]:
                    if not req.out_tokens:
                        req.first_token_at = now
                    req.out_tokens.append(int(token[slot, j]))
                    count += 1
            if finished[slot]:
                req.finished_at = now
                self.completed.append(req)
                self.active[slot] = None
                if self.paged:
                    self.scheduler.release_slot(slot)
        return count

    def collective_stats(self) -> Dict[str, Dict[str, float]]:
        """The fused serve step's collectives per execution, by kind:
        ``{"all-reduce": {"count": n, "wire_bytes": b, "max_bytes": m},
        ...}``, the layer scan's trip count applied
        (``launch.hlo_analysis.collective_stats``).

        Compiled on the first call from the engine's own jitted step at
        the live arguments' shapes and shardings, then cached (``migrate``
        drops the cache); neither ``step()`` nor construction calls it.
        ``{}`` on one device."""
        if self._collectives is None:
            if self.mesh is None or self.mesh.devices.size == 1:
                self._collectives = {}
            else:
                from repro.launch.hlo_analysis import collective_stats
                args = jax.tree.map(
                    lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype,
                                                   sharding=x.sharding),
                    (self.params, self.caches, self.state))
                self._collectives = collective_stats(
                    self._serve_step.lower(*args).compile())
        return {k: dict(v) for k, v in self._collectives.items()}

    def _flush(self) -> int:
        count = 0
        while self._pending:
            count += self._retire_one()
        return count

    # ----------------------- elastic live migration -----------------------
    def migrate(self, new_plan: ExecutionPlan, *,
                verify: bool = True) -> MigrationReport:
        """Live plan→plan migration: move this deployment onto
        ``new_plan``'s mesh without dropping streams.

        The resharded transfer is *derived* from the two plans'
        ``NamedSharding``\\ s (``core.execution_plan.reshard_transfer``):
        params, the KV cache grid and the in-flight :class:`DecodeState`
        are ``device_put`` onto the destination placements — a leaf whose
        placement is equivalent on both plans does not physically move,
        so only the slots whose pages/rows must move are drained through
        the transfer. Host bookkeeping (queue, active slot map, page
        pool, prefix registry, per-request PRNG seeding) is
        mesh-independent and carries over untouched; the fused step and
        the scheduler's prefill/splice/admit jits are rebuilt lazily on
        the new mesh. Greedy token streams are bit-exact across the move
        (the plan-invariance property ``serving_equiv --replan``
        certifies).

        ``verify`` reconciles the analytic destination shard bytes
        against the committed arrays within the disagg transfer band
        (``serving.disagg.XFER_LOWER_TOL`` / ``XFER_UPPER_FACTOR``) and
        raises on a mismatch. Returns the :class:`MigrationReport`
        (also appended to ``self.migrations``).
        """
        import dataclasses as _dc
        from jax.sharding import NamedSharding, PartitionSpec
        from repro.core.execution_plan import reshard_transfer
        from repro.core.xfer import tree_shardings
        from repro.serving.state import active_slots as _active_slots

        if self.plan is None:
            raise ValueError(
                "migrate() needs a plan-constructed engine (build with "
                "repro.plan(...).compile().serve(...)); the deprecated "
                "ServingEngine(arch, ...) construction has no source plan")
        if self.scheduler.worker is not None:
            raise NotImplementedError(
                "migrating a disaggregated deployment would re-split the "
                "prefill/decode role slices; migrate the fused engine")
        if new_plan.arch != self.arch:
            raise ValueError(
                f"migrate() cannot change the architecture: engine serves "
                f"{self.arch.name}, new plan is {new_plan.arch.name}")
        t0 = time.perf_counter()
        # read back every dispatched-but-unread record first: host
        # bookkeeping must be current before rows move, and old-mesh
        # record buffers must not be read after their grid is donated on
        # the new mesh
        flushed = len(self._pending)
        self._flush()
        exe = new_plan.compile()
        new_mesh = exe.mesh
        ctx = exe.ctx
        in_flight = _active_slots(self.state)
        is_encdec = self.arch.family == "encdec"
        draft_dims = (REG.cache_dims(self.spec.draft)
                      if self.spec is not None else None)
        repl = lambda tree: jax.tree.map(
            lambda _: NamedSharding(new_mesh, PartitionSpec()), tree)

        # --- params: destination shardings from the new plan. int8
        # weights dequantize first (symmetric per-channel int8
        # round-trips exactly: the max-magnitude channel maps back to
        # ±127, so requantizing on the new mesh reproduces the same
        # ints), are placed as fp, and requantize under the new mesh —
        # the construction order, so int8 leaves inherit the placement.
        params = self.params
        requant = self.quant.quant_weights
        if requant:
            if self.spec is not None:
                params = dict(params, target=mesh_jit(
                    self.mesh, dequantize_params)(params["target"]))
            else:
                params = mesh_jit(self.mesh, dequantize_params)(params)
        if self.spec is not None:
            params_dst = {
                "target": new_plan.param_shardings(params["target"], new_mesh),
                "draft": repl(params["draft"])}
        else:
            params_dst = new_plan.param_shardings(params, new_mesh)
        # --- caches: dense grids take the plan's cache shardings; paged
        # pools have no slot axis (the jitted step lets the compiler
        # place them), so they cross replicated
        caches_dst = (repl(self.caches) if self.paged
                      else new_plan.cache_shardings(self.caches, new_mesh))
        state_dst = tree_shardings(
            new_plan.ctx(new_mesh), self.state,
            decode_state_dims(enc=is_encdec, paged=self.paged,
                              draft_dims=draft_dims))

        xp = reshard_transfer(params, params_dst)
        xc = reshard_transfer(self.caches, caches_dst)
        xs = reshard_transfer(self.state, state_dst)

        params = jax.device_put(params, params_dst)
        if requant:
            if self.spec is not None:
                params = dict(params, target=mesh_jit(
                    new_mesh, quantize_params)(params["target"]))
            else:
                params = mesh_jit(new_mesh, quantize_params)(params)
        self.params = params
        self.caches = jax.device_put(self.caches, caches_dst)
        self.state = jax.device_put(self.state, state_dst)
        jax.block_until_ready((self.params, self.caches, self.state))

        # --- reconcile: bytes actually committed across the new mesh vs
        # the analytic per-device shard bytes the placements imply (the
        # disagg verify_xfer band; shard-exact modulo padding)
        n_dev = int(np.prod(list(new_mesh.shape.values())))
        analytic = (xp.dst_shard_bytes + xc.dst_shard_bytes
                    + xs.dst_shard_bytes) * n_dev
        actual = sum(
            sum(s.data.nbytes for s in leaf.addressable_shards)
            for leaf in jax.tree.leaves(
                (self.params, self.caches, self.state))
            if hasattr(leaf, "addressable_shards"))
        from repro.serving.disagg import XFER_LOWER_TOL, XFER_UPPER_FACTOR
        verified = ((1.0 - XFER_LOWER_TOL) * analytic <= actual
                    <= XFER_UPPER_FACTOR * analytic)
        if verify and not verified:
            raise RuntimeError(
                f"migrate(): committed bytes {actual} outside the "
                f"[{1.0 - XFER_LOWER_TOL:.2f}x, {XFER_UPPER_FACTOR:.1f}x] "
                f"band of analytic {analytic} "
                f"({dict(self.plan.mesh_axes)} -> {dict(new_plan.mesh_axes)})")

        # --- resume the fused step on the new mesh; scheduler host state
        # survives, its jits rebuild lazily under the new mesh context
        step_fn = REG.build_serve_step(self.arch, ctx, sampling=self.sampling,
                                       eos_id=self.eos_id, paged=self.paged,
                                       spec=self.spec)
        if requant:
            inner_step = step_fn
            if self.spec is not None:
                step_fn = (lambda params, caches, state:
                           inner_step({"target":
                                       dequantize_params(params["target"]),
                                       "draft": params["draft"]},
                                      caches, state))
            else:
                step_fn = (lambda params, caches, state:
                           inner_step(dequantize_params(params), caches,
                                      state))
        self._serve_step = mesh_jit(new_mesh, step_fn, donate_argnums=(1, 2))
        self._collectives = None
        self.scheduler.rebind_mesh(new_mesh)
        from_axes = tuple(self.plan.mesh_axes)
        self.plan = new_plan
        self.mesh = new_mesh
        report = MigrationReport(
            from_axes=from_axes, to_axes=tuple(new_plan.mesh_axes),
            stall_s=time.perf_counter() - t0,
            flushed_records=flushed,
            active_slots=len(in_flight),
            drained_slots=(len(in_flight)
                           if (xc.moved_leaves or xs.moved_leaves) else 0),
            params_moved_bytes=xp.moved_bytes,
            caches_moved_bytes=xc.moved_bytes,
            state_moved_bytes=xs.moved_bytes,
            logical_bytes=xp.logical_bytes + xc.logical_bytes
            + xs.logical_bytes,
            moved_bytes=xp.moved_bytes + xc.moved_bytes + xs.moved_bytes,
            dst_shard_bytes=analytic, actual_shard_bytes=actual,
            verified=verified)
        self.migrations.append(report)
        return report

    def maybe_resize(self):
        """One elastic-controller tick (no-op without
        ``ServeConfig(elastic=...)``): lets the attached
        ``runtime.elastic.LoadController`` act on the current telemetry.
        Returns the :class:`MigrationReport` when a resize happened."""
        if self.elastic is None:
            return None
        return self.elastic.observe()

    def migration_stats(self) -> Dict[str, float]:
        """Resize telemetry: count, stall percentiles, bytes moved."""
        from repro.core.stats import percentile
        stalls = [m.stall_s * 1e3 for m in self.migrations]
        return {
            "migrations": float(len(self.migrations)),
            "migration_stall_p50_ms": percentile(stalls, 50),
            "migration_stall_max_ms": max(stalls) if stalls else 0.0,
            "migration_moved_bytes": float(sum(m.moved_bytes
                                               for m in self.migrations)),
            "migration_logical_bytes": float(sum(m.logical_bytes
                                                 for m in self.migrations)),
        }

    def run_until_drained(self, max_steps: int = 10_000, *,
                          on_incomplete: str = "raise") -> int:
        """Step until every submitted request completed; returns the step
        count. Hitting ``max_steps`` with requests still in flight raises
        :class:`IncompleteDrainError` naming the unfinished rids (pass
        ``on_incomplete="warn"`` to degrade to a warning) — a hang must
        surface in tests and benches, not truncate silently.

        Step/prefill telemetry is reset on entry: ``step_stats()`` /
        ``prefill_stats()`` after a drain describe exactly that drain,
        however many drains the engine already ran."""
        if on_incomplete not in ("raise", "warn"):
            raise ValueError(f"on_incomplete must be 'raise' or 'warn', "
                             f"got {on_incomplete!r}")
        self.reset_step_stats()
        steps = 0
        while (self.queue or self.scheduler.has_active()) and steps < max_steps:
            self.step()
            steps += 1
            if not self.queue and not self.scheduler.has_active():
                self._flush()  # retire the trailing lookahead records
        if self.queue or self.scheduler.has_active():
            self._flush()
        if self.queue or self.scheduler.has_active():
            rids = self.unfinished()
            msg = (f"run_until_drained: {len(rids)} request(s) still in "
                   f"flight after {steps} steps (max_steps={max_steps}): "
                   f"rids={rids}")
            if on_incomplete == "raise":
                raise IncompleteDrainError(msg, rids)
            warnings.warn(msg, RuntimeWarning, stacklevel=2)
        return steps

    # ------------------------- step-timing hooks -------------------------
    def reset_step_stats(self):
        """Drop recorded step/prefill timings (e.g. after a jit warmup pass)."""
        self.step_times.clear()
        self.step_token_counts.clear()
        self.queue_depths.clear()
        self.retired_emits.clear()
        self.retired_active.clear()
        self.record_wait_times.clear()
        self.admit_times.clear()
        self.dispatch_times.clear()
        self.scheduler.reset_stats()

    def step_stats(self) -> Dict[str, float]:
        """p50/p95 decode-step wall time and aggregate token throughput.

        ``record_wait_p50_ms`` / ``record_wait_max_ms`` are the time a
        ``step()`` blocked reading step records (the host waiting on the
        device); ``host_p50_ms`` is the median of each step's wall minus
        that wait, the host's own work per step. ``queue_depth`` is the
        mean backlog observed at step dispatch; ``accepted_tokens_mean``
        is committed tokens per active slot-step (1.0 for plain decoding,
        up to ``k+1`` under speculation — the speedup lever). Speculative
        engines additionally report ``draft_acceptance``: accepted /
        proposed draft tokens over the currently-resident requests
        (device counters, zeroed at admission)."""
        from repro.core.stats import percentile
        ms = [t * 1e3 for t in self.step_times]
        total_s = sum(self.step_times)
        toks = sum(self.step_token_counts)
        qd = list(self.queue_depths)
        emits = sum(self.retired_emits)
        actives = sum(self.retired_active)
        wait_ms = [t * 1e3 for t in self.record_wait_times]
        stats = {
            "steps": float(len(ms)),
            "step_p50_ms": percentile(ms, 50),
            "step_p95_ms": percentile(ms, 95),
            "step_mean_ms": (sum(ms) / len(ms)) if ms else 0.0,
            "record_wait_p50_ms": percentile(wait_ms, 50),
            "record_wait_max_ms": max(wait_ms, default=0.0),
            "host_p50_ms": percentile([a - b for a, b in zip(ms, wait_ms)],
                                      50),
            "tokens": float(toks),
            "tokens_per_s": toks / total_s if total_s > 0 else 0.0,
            "queue_depth": (sum(qd) / len(qd)) if qd else 0.0,
            "accepted_tokens_mean": (emits / actives) if actives else 0.0,
        }
        if self.spec is not None and self.state.accepted is not None:
            acc = float(np.asarray(self.state.accepted).sum())
            prop = float(np.asarray(self.state.proposed).sum())
            stats["draft_acceptance"] = acc / prop if prop else 0.0
        return stats

    def prefill_stats(self) -> Dict[str, float]:
        """p50/p95 per-request admission wall time (host critical path:
        bucketed prefill dispatch + cache splice + state update; the
        prefill compute itself overlaps the in-flight decode step).

        Batched admission telemetry rides along: ``prefill_dispatches``
        counts device dispatch groups since the last reset (a same-bucket
        burst of N requests is **one** dispatch), ``admit_p50_ms`` /
        ``admit_p95_ms`` are per-dispatch wall percentiles, and
        ``prefill_batch_mean`` is the mean requests-per-dispatch.
        ``prefix_hit_rate`` is the fraction of prefix-registry lookups
        that aliased shared pages (0.0 on non-paged engines)."""
        from repro.core.stats import percentile
        sched = self.scheduler
        ms = [t * 1e3 for t in self.prefill_times]
        lens = list(self.prefill_prompt_lens)
        disp_ms = [t * 1e3 for t in sched.prefill_dispatch_times]
        sizes = list(sched.prefill_batch_sizes)
        reg = sched.registry
        looked = (reg.hits + reg.misses) if reg is not None else 0
        return {
            "prefix_hit_rate": (reg.hits / looked) if looked else 0.0,
            "prefills": float(len(ms)),
            "prefill_p50_ms": percentile(ms, 50),
            "prefill_p95_ms": percentile(ms, 95),
            "prefill_mean_ms": (sum(ms) / len(ms)) if ms else 0.0,
            "prompt_tokens": float(sum(lens)),
            "prefill_tokens_per_s": (sum(lens) / (sum(self.prefill_times) or 1.0)
                                     if ms else 0.0),
            "prefill_dispatches": float(len(disp_ms)),
            "admit_p50_ms": percentile(disp_ms, 50),
            "admit_p95_ms": percentile(disp_ms, 95),
            "prefill_batch_mean": (sum(sizes) / len(sizes)) if sizes else 0.0,
        }
