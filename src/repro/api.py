"""Unified deployment API: plan → compile → execute.

The paper's workflow is a pipeline — run the analytic model over the
design space (Eq. 15), pick a partition, then *deploy exactly that
partition* (§5E). This module makes that pipeline first-class::

    import repro

    # stage 1 — DSE: pick the best ShardingPlan + per-layer tiling/ports
    plan = repro.plan("qwen1.5-0.5b", "train_4k")          # auto mesh
    plan = repro.plan(arch_cfg, shape_cfg, mesh)           # explicit mesh

    # stage 2 — compile: build mesh, derive NamedShardings, jit steps
    exe = plan.compile()

    # stage 3 — execute: plan-aware engines
    engine = exe.serve(config=ServeConfig(slots=4, max_len=128))
    driver = exe.train(steps=50, ckpt_dir="/tmp/ckpt")     # TrainDriver

    # or in one call when the defaults are right:
    exe = repro.deploy("qwen1.5-0.5b", "train_4k")

Every arch/shape argument accepts either a registered id string or a
config object; ``mesh`` accepts a live ``jax.sharding.Mesh``, a tuple of
``(axis_name, size)`` pairs, or ``None`` (fit the live device set).
"""
from __future__ import annotations

import functools
from typing import Any, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp

from repro.configs import SHAPES, get_arch
from repro.configs.base import ArchConfig, ShapeConfig
from repro.core.execution_plan import ExecutionPlan
from repro.core.planner import plan_cell
from repro.core.xfer import ShardingCtx
from repro.optim import adamw as OPT

PyTree = Any
MeshLike = Union[None, "jax.sharding.Mesh", Sequence[Tuple[str, int]]]


def _coerce_arch(arch: Union[str, ArchConfig], reduced: bool = False) -> ArchConfig:
    if isinstance(arch, str):
        arch = get_arch(arch)
    return arch.reduced() if reduced else arch


def _coerce_shape(shape: Union[str, ShapeConfig]) -> ShapeConfig:
    if isinstance(shape, str):
        if shape not in SHAPES:
            raise KeyError(f"unknown shape {shape!r}; known: {sorted(SHAPES)}")
        return SHAPES[shape]
    return shape


def _coerce_mesh(mesh: MeshLike, arch: Optional[ArchConfig] = None):
    """-> (mesh_axes, devices, live_mesh). ``arch`` (when known) keeps the
    auto-fitted model axis divisible into the arch's heads."""
    if mesh is None:
        from repro.runtime.elastic import _best_grid
        devices = jax.devices()
        data, model = _best_grid(len(devices), arch)
        return ((("data", data), ("model", model)),
                list(devices[: data * model]), None)
    if isinstance(mesh, jax.sharding.Mesh):
        from repro.launch.mesh import mesh_axes
        return mesh_axes(mesh), list(mesh.devices.flat), mesh
    axes = tuple((str(n), int(s)) for n, s in mesh)
    bad = [(n, s) for n, s in axes if s <= 0]
    if bad:
        raise ValueError(f"mesh axis sizes must be positive, got {bad} in {axes}")
    names = [n for n, _ in axes]
    if len(set(names)) != len(names):
        raise ValueError(f"duplicate mesh axis name in {axes}")
    return axes, None, None


def plan(arch: Union[str, ArchConfig], shape: Union[str, ShapeConfig],
         mesh: MeshLike = None, *, reduced: bool = False,
         force_xfer: Optional[bool] = None, quant=None,
         draft: Union[None, str, ArchConfig] = None) -> ExecutionPlan:
    """Stage 1: run the paper's DSE for one cell and wrap the winner.

    The returned :class:`ExecutionPlan` carries the chosen ``ShardingPlan``,
    per-layer ``Tiling``/``Ports``, and the capacity report, and derives the
    ``NamedSharding`` specs that ``compile()`` places tensors with.

    ``quant`` (a :class:`repro.quant.QuantConfig`) informs the capacity
    model when the cell will serve quantised: int8 weights / KV shrink
    per-device HBM residency, which can flip a capacity-infeasible plan
    to feasible (match it to the ``ServeConfig.quant`` you deploy with).

    ``draft`` co-places a speculative-decoding draft model with the
    target (serving shapes only): the capacity report charges both
    models' params + KV footprints to the same devices, and
    ``exe.serve(config=ServeConfig(spec=SpecConfig()))`` resolves its
    draft arch from the plan.
    """
    arch = _coerce_arch(arch, reduced)
    shape = _coerce_shape(shape)
    draft = _coerce_arch(draft, reduced) if draft is not None else None
    axes, devices, live_mesh = _coerce_mesh(mesh, arch)
    report = plan_cell(arch, shape, axes, force_xfer=force_xfer, quant=quant,
                       draft=draft)
    return ExecutionPlan(arch=arch, shape=shape, report=report,
                         mesh_axes=axes, devices=devices, _mesh=live_mesh,
                         draft=draft)


def deploy(arch: Union[str, ArchConfig], shape: Union[str, ShapeConfig],
           mesh: MeshLike = None, *, reduced: bool = False,
           force_xfer: Optional[bool] = None, **compile_kwargs) -> "Executable":
    """plan → compile in one call."""
    return plan(arch, shape, mesh, reduced=reduced,
                force_xfer=force_xfer).compile(**compile_kwargs)


class Executable:
    """Stage 2 output: a plan bound to a live mesh with jitted steps.

    Construction is cheap (mesh + ShardingCtx); jitting happens lazily the
    first time a step builder is asked for, and actual XLA compilation on
    first call as usual.
    """

    def __init__(self, plan: ExecutionPlan, *, dtype=None):
        self.plan = plan
        self.mesh = plan.build_mesh()
        self.ctx: ShardingCtx = plan.ctx(self.mesh)
        if dtype is None:
            dtype = jnp.float32 if jax.default_backend() == "cpu" else jnp.bfloat16
        self.dtype = dtype

    @property
    def arch(self) -> ArchConfig:
        return self.plan.arch

    @property
    def shape(self) -> ShapeConfig:
        return self.plan.shape

    def describe(self) -> str:
        return self.plan.describe()

    # -------------------------- parameters ---------------------------
    def init_params(self, key=None, dtype=None) -> PyTree:
        """Initialise params directly in the plan's shardings: each
        device materialises only its own shards, so a model larger than
        one device's memory never lands whole on device 0."""
        from repro.models import registry as REG
        if key is None or isinstance(key, int):
            key = jax.random.PRNGKey(key or 0)
        init = functools.partial(REG.init_params, self.arch,
                                 dtype=dtype or self.dtype)
        shardings = self.plan.param_shardings(jax.eval_shape(init, key),
                                              self.mesh)
        with self.mesh:
            return jax.jit(init, out_shardings=shardings)(key)

    def shard_params(self, params: PyTree) -> PyTree:
        """device_put with NamedShardings derived from the ShardingPlan."""
        return jax.device_put(params, self.plan.param_shardings(params, self.mesh))

    def shard_opt_state(self, opt_state: PyTree, quantize: bool = False) -> PyTree:
        return jax.device_put(
            opt_state, self.plan.opt_shardings(opt_state, self.mesh, quantize))

    # -------------------------- step builders -------------------------
    def train_step(self, cfg: Optional[OPT.AdamWConfig] = None,
                   lr_schedule=None, accum_steps: int = 1):
        """Jitted plan-aware train step (params, opt, batch) -> (params, opt, metrics)."""
        from repro.models import registry as REG
        cfg = cfg or OPT.AdamWConfig()
        fn = REG.build_train_step(self.arch, cfg, self.ctx, lr_schedule,
                                  accum_steps=accum_steps)
        with self.mesh:
            return jax.jit(fn, donate_argnums=(0, 1))

    def serve_step(self):
        from repro.models import registry as REG
        with self.mesh:
            return jax.jit(REG.build_serve_step(self.arch, self.ctx))

    def prefill_step(self, shape: Optional[ShapeConfig] = None):
        from repro.models import registry as REG
        with self.mesh:
            return jax.jit(REG.build_prefill_step(self.arch, shape or self.shape,
                                                  self.ctx, cache_dtype=self.dtype))

    # -------------------------- stage 3: execute ----------------------
    def serve(self, params: Optional[PyTree] = None, *,
              config: Optional["Any"] = None, on_step=None,
              **legacy_kwargs) -> "Any":
        """Plan-aware :class:`repro.serving.engine.ServingEngine`.

        The serve surface is one typed value — pass a
        :class:`repro.serving.config.ServeConfig`::

            from repro.serving import ServeConfig, PagingConfig, DisaggConfig
            engine = exe.serve(config=ServeConfig(
                slots=4, max_len=128,
                paging=PagingConfig(paged=True),
                disagg=DisaggConfig(prefill_data=2)))

        ``slots``/``max_len`` default to the planned shape's batch/seq;
        the engine exposes the fully-resolved values as ``engine.config``.
        Params are initialised (or re-placed, if given) with the plan's
        NamedShardings before the engine jits its decode step.

        ``config.sampling`` selects on-device token choice (default
        greedy), ``config.lookahead`` the dispatch depth (1 = double-
        buffered, 0 = synchronous), ``config.max_src_len`` bounds enc-dec
        source frames (requests carry ``src_frames`` / vlm
        ``patch_embeds``). ``config.paging`` swaps the dense slot grid
        for the page-pool KV cache (``repro.serving.pages``);
        ``config.disagg`` splits the planned mesh into prefill/decode
        role slices and returns a
        :class:`repro.serving.disagg.DisaggServingEngine` that streams
        admission KV across (``ExecutionPlan.disaggregate``).

        ``on_step`` is the engine's step-timing hook: called after every
        decode step with ``{"step", "wall_s", "tokens"}`` — the probe
        ``repro.bench`` uses to put measured step time next to the plan's
        ``predicted_seconds`` (the paper's model-validation loop).

        The pre-``ServeConfig`` flat kwargs (``slots=, max_len=, paged=,
        ...``) are still accepted — funneled through
        :meth:`ServeConfig.from_kwargs` with a ``DeprecationWarning``.
        """
        import warnings

        from repro.serving.config import ServeConfig
        from repro.serving.engine import ServingEngine
        if config is None:
            if legacy_kwargs:
                warnings.warn(
                    "Executable.serve(slots=..., max_len=..., ...) flat "
                    "kwargs are deprecated; pass "
                    "serve(config=ServeConfig(...))",
                    DeprecationWarning, stacklevel=2)
            config = ServeConfig.from_kwargs(**legacy_kwargs)
        elif legacy_kwargs:
            raise TypeError(
                f"serve() got both config= and flat kwargs "
                f"{sorted(legacy_kwargs)}; put everything in the config")
        config = config.resolve(self.shape)
        if config.spec is not None:
            import dataclasses as _dc

            from repro.models import registry as REG
            from repro.serving.config import SpecConfig  # noqa: F401
            if config.disagg is not None:
                raise NotImplementedError(
                    "speculative decoding does not compose with "
                    "disaggregated serving yet")
            spec = config.spec
            if spec.draft is None:
                if self.plan.draft is None:
                    raise ValueError(
                        "ServeConfig.spec set but no draft arch: pass "
                        "SpecConfig(draft=...) or plan the cell with "
                        "repro.plan(..., draft=...)")
                spec = _dc.replace(spec, draft=self.plan.draft)
                config = _dc.replace(config, spec=spec)
            if params is None:
                params = REG.init_params(
                    self.arch, jax.random.PRNGKey(config.seed), self.dtype)
            if not (isinstance(params, dict)
                    and set(params) == {"target", "draft"}):
                dkey = jax.random.fold_in(
                    jax.random.PRNGKey(config.seed), 1)
                params = {"target": params,
                          "draft": REG.init_params(spec.draft, dkey,
                                                   self.dtype)}
            from repro.serving.engine import ServingEngine
            return self._attach_elastic(
                ServingEngine(self.plan, params, config=config,
                              dtype=self.dtype, on_step=on_step), config)
        if config.disagg is not None:
            # role slices place params on their own meshes; skip the
            # fused-mesh placement and hand the raw tree over
            if config.elastic is not None:
                raise NotImplementedError(
                    "elastic resize does not compose with disaggregated "
                    "serving yet: migrating would re-split the "
                    "prefill/decode role slices")
            from repro.serving.disagg import DisaggServingEngine
            if params is None:
                from repro.models import registry as REG
                params = REG.init_params(
                    self.arch, jax.random.PRNGKey(config.seed), self.dtype)
            return DisaggServingEngine(self.plan, params, config=config,
                                       dtype=self.dtype, on_step=on_step)
        if params is None:
            params = self.init_params(jax.random.PRNGKey(config.seed))
        else:
            params = self.shard_params(params)
        return self._attach_elastic(
            ServingEngine(self.plan, params, config=config,
                          dtype=self.dtype, on_step=on_step), config)

    def _attach_elastic(self, engine, config):
        """Attach the load controller when ``ServeConfig.elastic`` is set:
        the serving loop then drives resizes via ``engine.maybe_resize()``
        (or directly through ``engine.elastic.observe()``)."""
        if config.elastic is not None:
            from repro.runtime.elastic import LoadController
            engine.elastic = LoadController(engine, config.elastic)
        return engine

    def train(self, params: Optional[PyTree] = None,
              opt_state: Optional[PyTree] = None, *,
              steps: int = 20, ckpt_dir: str = "/tmp/repro_ckpt",
              ckpt_every: int = 10, keep: int = 3,
              opt_cfg: Optional[OPT.AdamWConfig] = None,
              lr_schedule=None, accum_steps: int = 1, seed: int = 0,
              pipeline=None, ckpt=None, cfg=None,
              on_failure_rebuild=None) -> "Any":
        """Plan-aware :class:`repro.runtime.driver.TrainDriver`.

        Builds the data pipeline, checkpointer, sharded state and jitted
        step from the plan; call ``.run()`` on the result. ``ckpt`` /
        ``cfg`` override the ``ckpt_dir``/``keep`` and
        ``steps``/``ckpt_every`` conveniences with explicit objects.
        """
        from repro.checkpoint.checkpointer import Checkpointer
        from repro.data.pipeline import TokenPipeline
        from repro.runtime.driver import DriverConfig, TrainDriver
        if opt_cfg is None:
            # honor the capacity side of the DSE: a plan that only fits HBM
            # with int8 Adam states (planner note) must deploy them that way
            from repro.core.planner import INT8_NOTE
            opt_cfg = OPT.AdamWConfig(quantize=INT8_NOTE in self.plan.report.note)
        cfg = cfg or DriverConfig(total_steps=steps, checkpoint_every=ckpt_every)
        if params is None:
            params = self.init_params(jax.random.PRNGKey(seed))
        else:
            params = self.shard_params(params)
        if opt_state is None:
            opt_state = OPT.adamw_init(params, opt_cfg)
        opt_state = self.shard_opt_state(opt_state, opt_cfg.quantize)
        if lr_schedule is None:
            lr_schedule = OPT.cosine_schedule(opt_cfg.lr,
                                              warmup=max(cfg.total_steps // 20, 2),
                                              total=cfg.total_steps)
        step_fn = self.train_step(opt_cfg, lr_schedule, accum_steps)
        pipeline = pipeline or TokenPipeline(self.arch, self.shape, seed=seed)
        ckpt = ckpt or Checkpointer(ckpt_dir, keep=keep)
        return TrainDriver(
            step_fn, params, opt_state, pipeline, ckpt, cfg,
            on_failure_rebuild=on_failure_rebuild, plan=self.plan)
