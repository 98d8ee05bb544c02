"""One run of one cell: set up, drive the open-loop client for the window,
check the served tokens against the reference, reduce to metrics.

Everything that belongs to one configuration, traffic mix or metric is
found by name: ``configs/<config>.json``, ``traffic/<mix>.json`` and
``metrics/<metric>.py``. The program under test is driven only through
its public serving path: ``repro.plan(...).compile()`` ->
``Executable.serve(params, config=ServeConfig(...))`` ->
``ServingEngine.submit`` / ``step``, with weights that this benchmark
makes from the seed.
"""
from __future__ import annotations

import dataclasses
import gc
import importlib.util
import json
import os
import pathlib
import shutil
import sys
import tempfile
import time
from collections import deque
from contextlib import nullcontext
from typing import Dict, List, Optional

import numpy as np

from chipbench import mix as MIX

HERE = pathlib.Path(__file__).resolve().parent
ROOT = HERE.parent
WARM_RID = 1 << 30
# Admission groups are warmed up to this many same-bucket requests. The
# scheduler bounds a group only by the slot count, and warming every size
# up to the slots would compile 32 signatures per bucket. A larger group
# would compile inside the window, which ``run_cell`` refuses.
WARM_GROUP_MAX = 4
# A backlog runs this many decode steps after its slots fill and before
# the window opens, so the window starts in the steady state it measures.
STEADY_STEPS = 32
# A traced run traces the window's last seconds (at most the window).
TRACE_SECONDS = 4.0
# The reference compares at least this many served tokens per run.
REFERENCE_MIN_TOKENS = 300


def log(msg: str) -> None:
    print(f"[chipbench] {msg}", file=sys.stderr, flush=True)


class RunError(Exception):
    """The run cannot produce a result (no chip, missing program, ...)."""


# ----------------------------------------------------------------- loading
def load_benchmark() -> dict:
    with open(ROOT / "BENCHMARK.json") as f:
        return json.load(f)


def load_config(name: str) -> dict:
    with open(HERE / "configs" / f"{name}.json") as f:
        return json.load(f)


def load_peaks(device_kind: str) -> dict:
    with open(HERE / "peaks.json") as f:
        table = json.load(f)
    if device_kind not in table or device_kind == "source":
        raise RunError(f"device kind {device_kind!r} is not in peaks.json")
    return table[device_kind]


def load_reader(metric: str):
    path = HERE / "metrics" / f"{metric}.py"
    spec = importlib.util.spec_from_file_location(
        f"chipbench_metric_{metric.replace('.', '_').replace('-', '_')}", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.read


class CompileCounter:
    """XLA compilations (persistent-cache hits included) and their
    seconds, through JAX's monitoring events."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax
        self.count, self.seconds, self.cache_hits = 0, 0.0, 0
        jax.monitoring.register_event_duration_secs_listener(self._on_duration)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_duration(self, event, duration, **_):
        if event == self.EVENT:
            self.count += 1
            self.seconds += duration

    def _on_event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def snapshot(self):
        return self.count, self.seconds, self.cache_hits


def enable_compile_cache() -> str:
    """``JAX_COMPILATION_CACHE_DIR`` if set, else ``<checkout>/.jax_cache``;
    every program is cached, also those that compile in under a second."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(ROOT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def arch_from(cfg: dict):
    from repro.configs.base import ArchConfig
    if cfg.get("architecture") != "llama" or cfg.get("hidden_act") != "silu":
        raise RunError(f"config {cfg['name']}: only llama/silu is wired")
    return ArchConfig(
        name=cfg["name"], family="dense",
        num_layers=cfg["num_hidden_layers"], d_model=cfg["hidden_size"],
        num_heads=cfg["num_attention_heads"],
        num_kv_heads=cfg["num_key_value_heads"],
        d_ff=cfg["intermediate_size"], vocab_size=cfg["vocab_size"],
        head_dim=cfg["head_dim"], mlp="swiglu",
        rope_theta=float(cfg["rope_theta"]),
        tie_embeddings=bool(cfg["tie_word_embeddings"]))


# ------------------------------------------------------------ observation
@dataclasses.dataclass
class Track:
    """What the client saw of one request (host clock, seconds)."""
    req: MIX.Req
    handle: object            # the program's Request
    due: float
    submitted: float
    admitted: Optional[float] = None
    tokens: List[float] = dataclasses.field(default_factory=list)
    finished: Optional[float] = None
    refused: bool = False
    withdrawn: bool = False   # a backlog's queue left at the close
    cut: bool = False         # a backlog's request still decoding at the end


@dataclasses.dataclass
class Step:
    t: float                  # when step() returned
    wall: float               # its wall seconds
    active: int               # slots holding a request afterwards
    contexts: List[int]       # live positions of each active request
    admitted: int = 0         # requests first seen in a slot afterwards


@dataclasses.dataclass
class Obs:
    """Everything a metric reader may read."""
    cfg: dict
    chips: int
    slots: int
    peaks: Optional[dict]
    t0: float
    t_end: float
    setup_s: float
    tracks: List[Track]
    steps: List[Step]
    trace: object = None      # chipbench.trace.Trace in a traced run
    trace_window: tuple = ()  # (start, end) host seconds of the trace

    @property
    def seconds(self) -> float:
        return self.t_end - self.t0

    def due_in_window(self) -> List[Track]:
        return [t for t in self.tracks
                if self.t0 <= t.due < self.t_end and not t.withdrawn]

    def served_in_window(self) -> List[Track]:
        """Requests that held a slot while the window was open, or were
        refused in it: a backlog's work (its requests are queued ahead of
        their turn, so few are both due and admitted in the window)."""
        return [t for t in self.tracks
                if (t.refused and self.t0 <= t.submitted < self.t_end)
                or (t.admitted is not None and t.admitted < self.t_end
                    and (t.finished is None or t.finished >= self.t0))]

    def steps_in(self, lo: float, hi: float) -> List[Step]:
        return [s for s in self.steps if lo <= s.t < hi]


# ------------------------------------------------------------------ engine
def build_engine(cfg: dict, seed: int, chips: int):
    """plan -> compile -> serve, with this benchmark's seeded weights made
    in one jitted call in the plan's shardings."""
    import jax
    import jax.numpy as jnp

    import repro
    from repro.configs.base import ShapeConfig
    from repro.models import registry as REG
    from repro.serving import ServeConfig

    from chipbench import weights as W
    arch = arch_from(cfg)
    slots, max_len = cfg["serve"]["slots"], cfg["serve"]["max_len"]
    mesh = (("data", 1), ("model", 1)) if chips == 1 else None
    plan = repro.plan(arch, ShapeConfig(cfg["name"], max_len, slots, "decode"),
                      mesh=mesh)
    exe = plan.compile(dtype=jnp.bfloat16)
    if exe.mesh.devices.size != chips:
        raise RunError(f"plan took {exe.mesh.devices.size} devices, "
                       f"the cell asks for {chips}")
    key = W.base_key(seed)
    make = lambda k: W.program_tree(k, cfg)  # noqa: E731
    shapes = jax.eval_shape(make, key)
    want = jax.eval_shape(lambda k: REG.init_params(arch, k, jnp.bfloat16),
                          key)
    if (jax.tree.structure(shapes) != jax.tree.structure(want)
            or any(a.shape != b.shape for a, b in
                   zip(jax.tree.leaves(shapes), jax.tree.leaves(want)))):
        raise RunError("the program's parameter layout changed: "
                       f"{jax.tree.map(lambda a: a.shape, want)}")
    with exe.mesh:
        params = jax.jit(make, out_shardings=plan.param_shardings(
            shapes, exe.mesh))(key)
    engine = exe.serve(params, config=ServeConfig(
        slots=slots, max_len=max_len, seed=seed & 0x7FFFFFFF))
    return plan, exe, engine


def warm(engine, mix: dict, cfg: dict, rng) -> list:
    """Run every (bucket, group size) admission the mix can produce, so
    the window compiles nothing: prefill, splice and admit per signature,
    and the serve step. Returns the warm-up requests."""
    from repro.serving.engine import Request
    max_len = cfg["serve"]["max_len"]
    rid = WARM_RID
    hi = mix["prompt"]["max"]
    handles = []
    for b in MIX.buckets(mix, max_len, engine.scheduler.min_bucket):
        length = min(b, hi)
        for n in range(1, WARM_GROUP_MAX + 1):
            for _ in range(n):
                handles.append(Request(rid=rid, prompt=rng.integers(
                    1, cfg["vocab_size"], size=length, dtype=np.int32),
                    max_new_tokens=2))
                engine.submit(handles[-1])
                rid += 1
            # two tokens each: a sound engine drains in a few steps; a
            # broken one is left for the window's checks to catch
            engine.run_until_drained(max_steps=64, on_incomplete="warn")
    return handles


# ------------------------------------------------------------------ client
class Client:
    """Open-loop client: submits each request at its due time, steps the
    engine, and stamps every new token when ``step()`` hands it back."""

    def __init__(self, engine, mix: dict, reqs: List[MIX.Req], slots: int,
                 traced: bool):
        import jax
        self.engine = engine
        self.mix = mix
        self.pending = deque(reqs)
        self.slots = slots
        self.tracks: Dict[int, Track] = {}
        self.live: Dict[int, Track] = {}
        self.steps: List[Step] = []
        self.span = ((lambda name: jax.profiler.TraceAnnotation(name))
                     if traced else (lambda name: nullcontext()))

    def submit(self, r: MIX.Req, due: float, now: float) -> None:
        from repro.serving.engine import Request
        from repro.serving.scheduler import RequestValidationError
        h = Request(rid=r.rid, prompt=r.prompt, max_new_tokens=r.max_new)
        tr = Track(req=r, handle=h, due=due, submitted=now)
        self.tracks[r.rid] = tr
        try:
            self.engine.submit(h)
        except RequestValidationError as e:
            tr.refused = True
            log(f"request {r.rid} refused: {e}")
            return
        self.live[r.rid] = tr

    def submit_due(self, t0: float) -> None:
        now = time.perf_counter()
        if self.mix["kind"] == "backlog":
            want = self.slots * self.mix["backlog_per_slot"]
            while len(self.engine.queue) < want:
                if not self.pending:
                    raise RunError("the backlog ran dry")
                self.submit(self.pending.popleft(), now, now)
            return
        while self.pending and t0 + self.pending[0].due <= now:
            r = self.pending.popleft()
            self.submit(r, t0 + r.due, now)

    def step(self) -> None:
        t = time.perf_counter()
        with self.span("engine.step"):
            self.engine.step()
        now = time.perf_counter()
        with self.span("client.observe"):
            self.observe(now, now - t)

    def observe(self, now: float, wall: float) -> None:
        active = [r for r in self.engine.active.values() if r is not None]
        held = {id(h) for h in active}
        admitted = 0
        for h in active:
            tr = self.live.get(h.rid)
            if tr is not None and tr.admitted is None:
                tr.admitted = now
                admitted += 1
        done = []
        for rid, tr in self.live.items():
            n = len(tr.handle.out_tokens)
            if n > len(tr.tokens):
                tr.tokens.extend([now] * (n - len(tr.tokens)))
            if tr.admitted is not None and id(tr.handle) not in held:
                tr.finished = now
                done.append(rid)
        for rid in done:
            del self.live[rid]
        self.steps.append(Step(t=now, wall=wall, active=len(active),
                               contexts=[len(h.prompt) + len(h.out_tokens)
                                         for h in active],
                               admitted=admitted))

    def busy(self) -> bool:
        return bool(self.engine.queue) or self.engine.scheduler.has_active()

    def window(self, t0: float, t_end: float, on_tick=None) -> None:
        while True:
            now = time.perf_counter()
            if now >= t_end:
                return
            if on_tick is not None:
                on_tick(now)
            with self.span("client.submit"):
                self.submit_due(t0)
            if self.busy():
                self.step()
            else:
                nxt = (t0 + self.pending[0].due) if self.pending else t_end
                with self.span("client.wait"):
                    time.sleep(max(0.0, min(nxt, t_end) - time.perf_counter()))

    def drain(self, deadline: float) -> None:
        """After the window: no new requests. Open loop: every request
        runs to its end (one that has not by ``deadline`` never came). A
        backlog's queue is withdrawn and its slots run until
        ``deadline``; a request that got no token meanwhile has stalled."""
        if self.mix["kind"] == "backlog":
            queued = {h.rid for h in self.engine.queue}
            del self.engine.queue[:]
            for rid in queued & set(self.live):
                self.live.pop(rid).withdrawn = True
        seen = {rid: len(tr.tokens) for rid, tr in self.live.items()}
        while self.live and time.perf_counter() < deadline:
            if self.busy():
                self.step()
            else:
                time.sleep(0.001)
        if self.mix["kind"] == "backlog":
            for rid, tr in list(self.live.items()):
                if len(tr.tokens) > seen[rid]:
                    tr.cut = True
                    del self.live[rid]


# ------------------------------------------------------------- correctness
def sample_rows(tracks: List[Track], rows: int, rng) -> List[Track]:
    """The finished request with most served tokens, and others drawn
    from the seed."""
    done = sorted((t for t in tracks if t.finished is not None),
                  key=lambda t: (-len(t.handle.out_tokens), t.req.rid))
    if not done:
        return []
    rest = list(done[1:])
    pick = [done[0]] + [rest[i] for i in rng.permutation(len(rest))[:rows - 1]]
    return pick


def check(cfg: dict, seed: int, obs: "Obs", warmed: list, limit: float,
          control: bool = False) -> Dict[str, dict]:
    """The numbers compared, each with its limit. With ``control`` the
    int8 reference is put in the program's place: ``served_gap`` is the
    gap of the tokens it puts first on the same rows, which the limit has
    to fail, and ``program_gap`` keeps the program's own reading. The
    benchmark's own runs never ask for it."""
    from chipbench import reference as R
    tracks = [t for t in obs.tracks if not t.refused]
    unfinished = sum(1 for t in tracks if t.finished is None
                     and not (t.withdrawn or t.cut))
    unfinished += sum(1 for h in warmed if len(h.out_tokens) != h.max_new_tokens)
    wrong_len = sum(1 for t in tracks if t.finished is not None
                    and len(t.handle.out_tokens) != t.req.max_new)
    wrong_len += sum(1 for t in tracks
                     if len(t.handle.out_tokens) > t.req.max_new)
    vocab = cfg["vocab_size"]
    outside = sum(1 for t in tracks for x in t.handle.out_tokens
                  if not 0 <= int(x) < vocab)
    checks = {"unfinished": {"value": unfinished, "limit": 0},
              "wrong_length": {"value": wrong_len, "limit": 0},
              "outside_vocab": {"value": outside, "limit": 0}}
    pick = sample_rows(tracks, cfg["reference_rows"],
                       MIX.rng_for(seed, 5)) if not outside else []
    served = sum(len(t.handle.out_tokens) for t in pick)
    checks["sampled_tokens"] = {"value": served,
                                "limit": REFERENCE_MIN_TOKENS}
    gap = None
    if pick:
        t0 = time.perf_counter()
        res = R.score(cfg, seed, [(t.req.prompt, list(t.handle.out_tokens))
                                  for t in pick], control=control)
        gap = float(max(g.max() for g in res["served"]))
        if control:
            checks["program_gap"] = {"value": gap, "limit": limit}
            gap = float(max(g.max() for g in res["control"]))
        log(f"reference over {len(pick)} requests, {served} served tokens, "
            f"{time.perf_counter() - t0:.3f}s; served tokens below the "
            f"reference's best: "
            f"{sum(int((g > 0).sum()) for g in res['served'])}")
    checks["served_gap"] = {"value": gap, "limit": limit}
    return checks


def is_correct(checks: Dict[str, dict]) -> bool:
    c = checks
    return (c["unfinished"]["value"] == 0 and c["wrong_length"]["value"] == 0
            and c["outside_vocab"]["value"] == 0
            and c["sampled_tokens"]["value"] >= c["sampled_tokens"]["limit"]
            and c["served_gap"]["value"] is not None
            and c["served_gap"]["value"] <= c["served_gap"]["limit"])


# --------------------------------------------------------------------- run
def log_steps(steps: List[Step], t0: float, t_end: float) -> None:
    """Where the window's time went on the host: steps that admitted
    against the rest, and the longest steps with their offsets."""
    win = [s for s in steps if t0 <= s.t < t_end]
    if not win:
        return
    adm = [s for s in win if s.admitted]
    rest = [s for s in win if not s.admitted]
    mean = lambda xs: 1e3 * sum(s.wall for s in xs) / max(1, len(xs))  # noqa: E731
    top = sorted(win, key=lambda s: -s.wall)[:5]
    log(f"window steps: {len(win)}, {len(adm)} admitting (mean "
        f"{mean(adm):.3f} ms, {sum(s.wall for s in adm):.3f}s in all), the "
        f"rest mean {mean(rest):.3f} ms; longest: "
        + ", ".join(f"{s.wall * 1e3:.1f} ms at {s.t - t0:.2f}s"
                    for s in top))


def device_info(chips: int, require_tpu: bool):
    import jax
    devs = jax.devices()
    if require_tpu and devs[0].platform != "tpu":
        raise RunError(f"no TPU: JAX found {len(devs)} {devs[0].platform} "
                       f"device(s); this benchmark runs only on the chip")
    if len(devs) < chips:
        raise RunError(f"the cell asks for {chips} chips, JAX found "
                       f"{len(devs)}")
    return devs[:chips]


def run_cell(cell: dict, cfg: dict, mix: dict, metrics: List[dict],
             seed: int, seconds: float, trace: bool, *,
             require_tpu: bool = True, t_start: Optional[float] = None,
             limit: Optional[float] = None, control: bool = False) -> dict:
    """One run; returns the result object the command prints last."""
    t_start = time.perf_counter() if t_start is None else t_start
    devs = device_info(cell["chips"], require_tpu)
    import jax
    dev = devs[0]
    log(f"device: platform={dev.platform} kind={dev.device_kind} "
        f"count={len(devs)}")
    peaks = load_peaks(dev.device_kind) if require_tpu else None
    sys.path.insert(0, str(ROOT / "src"))
    try:
        import repro  # noqa: F401
    except ImportError as e:
        raise RunError(f"cannot import the program from {ROOT / 'src'}: {e}")
    log(f"compile cache: {enable_compile_cache()}")
    counter = CompileCounter()
    slots = cfg["serve"]["slots"]
    plan, exe, engine = build_engine(cfg, seed, cell["chips"])
    log(f"plan: {plan.describe()}")
    warmed = warm(engine, mix, cfg, MIX.rng_for(seed, 6))
    reqs = MIX.requests(mix, seed, seconds, slots, cfg["vocab_size"])
    client = Client(engine, mix, reqs, slots, traced=trace)
    if mix["kind"] == "backlog":
        # fill the slots one admission at a time: a steady grid of
        # decoding requests before the window opens
        now = time.perf_counter()
        for _ in range(slots):
            client.submit(client.pending.popleft(), now, now)
            client.step()
        for _ in range(STEADY_STEPS):
            client.submit_due(now)
            client.step()
    engine.reset_step_stats()
    jax.block_until_ready(engine.state)
    c_set = counter.snapshot()
    t0 = time.perf_counter()
    setup_s = t0 - t_start
    log(f"set-up {setup_s:.3f}s: {len(warmed)} warm-up requests, {c_set[0]} "
        f"compiles taking {c_set[1]:.3f}s ({c_set[2]} persistent-cache hits)")
    t_end = t0 + seconds
    tdir, twin = None, ()
    on_tick = None
    if trace:
        # the last TRACE_SECONDS of the window: stopping the profiler
        # (which collects the trace) then stalls the loop after the close
        tdir = tempfile.mkdtemp(prefix="chipbench-trace-")
        t_on = t_end - min(TRACE_SECONDS, seconds)
        state = {"on": None}

        def on_tick(now):
            if state["on"] is None and now >= t_on:
                # host annotations are kept; the Python call tracer
                # (every builtin call) is left off
                opts = jax.profiler.ProfileOptions()
                opts.python_tracer_level = 0
                jax.profiler.start_trace(tdir, profiler_options=opts)
                state["on"] = time.perf_counter()
    client.window(t0, t_end, on_tick)
    if trace:
        if state["on"] is None:
            raise RunError("no step ran in the traced part of the window")
        twin = (state["on"], time.perf_counter())
        jax.profiler.stop_trace()
    c_win = counter.snapshot()
    log(f"compiles inside the window: {c_win[0] - c_set[0]} taking "
        f"{c_win[1] - c_set[1]:.3f}s")
    log_steps(client.steps, t0, t_end)
    lag = [tr.submitted - tr.due for tr in client.tracks.values()
           if t0 <= tr.due < t_end]
    if lag:
        log(f"client lag: p50 {np.percentile(lag, 50) * 1e3:.3f} ms, "
            f"max {max(lag) * 1e3:.3f} ms over {len(lag)} submits")
    client.drain(time.perf_counter() + mix["drain_seconds"])
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in devs)
    obs = Obs(cfg=cfg, chips=cell["chips"], slots=slots,
              peaks=peaks, t0=t0, t_end=t_end, setup_s=setup_s,
              tracks=list(client.tracks.values()), steps=client.steps)
    if trace:
        from chipbench import trace as T
        obs.trace = T.load(T.find_xplane(tdir))
        obs.trace_window = twin
        shutil.rmtree(tdir, ignore_errors=True)
    if c_win[0] != c_set[0]:
        raise RunError(f"{c_win[0] - c_set[0]} programs compiled inside the "
                       f"window: the warm-up missed a signature (a same-bucket "
                       f"admission group above {WARM_GROUP_MAX}?)")
    predicted_ms = plan.predicted_seconds * 1e3
    del engine, exe, plan, client
    gc.collect()
    if limit is None:
        limit = cfg["limits"]["served_gap"]
    checks = check(cfg, seed, obs, warmed, limit, control)
    values = {}
    for m in metrics:
        v = load_reader(m["name"])(obs)
        if v is not None:
            values[m["name"]] = {"value": v, "unit": m["unit"]}
    if "decode_step_device_ms" in values:
        meas = values["decode_step_device_ms"]["value"]
        log(f"perf_model predicted decode step {predicted_ms:.3f} ms, "
            f"measured {meas:.3f} ms on the device (measured/predicted "
            f"{meas / predicted_ms:.3f})")
    due = (obs.served_in_window() if mix["kind"] == "backlog"
           else obs.due_in_window())
    result = {
        "correct": is_correct(checks),
        "attempted": len(due),
        "failed": sum(1 for t in due if t.refused
                      or (t.finished is None and not t.cut)),
        "metrics": values,
        "device": {"platform": dev.platform, "kind": dev.device_kind,
                   "count": len(devs), "memory_peak_bytes": int(peak)},
    }
    if trace:
        from chipbench import trace as T
        lo, hi = obs.trace.window()
        result["device"]["busy_s"] = T.busy_seconds(obs.trace)
        result["device"]["window_s"] = (hi - lo) * 1e-9
        result["breakdown"] = {"device_ops": T.top_ops(obs.trace),
                               "idle_gaps": T.idle_gaps(obs.trace)}
    result["checks"] = checks
    for name, c in checks.items():
        log(f"check {name}: {c['value']} (limit {c['limit']})")
    return result
