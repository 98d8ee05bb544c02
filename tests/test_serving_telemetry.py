"""The serving loop's own telemetry: the ``serve.*`` profiler spans, the
per-step phase counters, the request stamps, and the jitted program names
that device-trace readers match."""
import dataclasses
import glob
import importlib.util
import pathlib
import re
import time

import jax
import numpy as np
import pytest
from jax.profiler import ProfileData

import repro
from repro.configs.base import ShapeConfig
from repro.serving import ServeConfig
from repro.serving import spans as SP
from repro.serving.engine import Request

ARCH = repro.get_arch("qwen1.5-0.5b").reduced()
ROOT = pathlib.Path(__file__).resolve().parents[1]


def _engine(lookahead=1, slots=2):
    plan = repro.plan(ARCH, ShapeConfig("telemetry", 32, slots, "decode"))
    return plan.compile().serve(config=ServeConfig(slots=slots, max_len=32,
                                                   lookahead=lookahead))


def _submit(eng, rids, lens=(4, 6, 5, 9), new=3):
    for rid, n in zip(rids, lens):
        eng.submit(Request(rid=rid, prompt=np.arange(1, n + 1, dtype=np.int32),
                           max_new_tokens=new))


def _steps_until_idle(eng, limit=60):
    """step() until no request is queued or active, with no trailing
    flush: every record read happens inside a step()."""
    for _ in range(limit):
        if not (eng.queue or eng.scheduler.has_active()):
            return
        eng.step()
    raise AssertionError("engine did not go idle")


def _host_spans(path):
    """(name, start_ns, end_ns, stats) of every serve.* span."""
    out = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name in SP.ALL:
                    out.append((e.name, e.start_ns, e.start_ns + e.duration_ns,
                                dict(e.stats)))
    return out


def _inside(child, parents):
    return any(p[1] <= child[1] and child[2] <= p[2] for p in parents)


def test_profiler_trace_holds_nested_serve_spans(tmp_path):
    eng = _engine()
    _submit(eng, range(100, 104))
    _steps_until_idle(eng)  # compile outside the trace
    eng.reset_step_stats()
    _submit(eng, range(4))
    jax.profiler.start_trace(str(tmp_path))
    _steps_until_idle(eng)
    jax.profiler.stop_trace()
    spans = _host_spans(glob.glob(str(tmp_path / "**" / "*.xplane.pb"),
                                  recursive=True)[0])
    by = {n: [s for s in spans if s[0] == n] for n in SP.ALL}
    steps = by[SP.STEP]
    assert len(steps) == len(eng.step_times) > 0
    assert sorted(s[3]["step_num"] for s in steps) == list(
        range(steps[0][3]["step_num"], steps[0][3]["step_num"] + len(steps)))
    for name in (SP.RETIRE, SP.ADMIT, SP.DISPATCH):
        assert len(by[name]) == len(steps)
        assert all(_inside(s, steps) for s in by[name])
    assert by[SP.RECORD_WAIT]
    assert all(_inside(s, by[SP.RETIRE]) for s in by[SP.RECORD_WAIT])
    assert all(_inside(s, by[SP.ADMIT]) for s in by[SP.PREFILL])
    rids = [int(r) for s in by[SP.PREFILL]
            for r in re.findall(r"\d+", s[3]["rids"])]
    assert sorted(rids) == [0, 1, 2, 3]
    assert sum(s[3]["size"] for s in by[SP.PREFILL]) == 4
    assert all(s[3]["bucket"] >= 8 for s in by[SP.PREFILL])
    # the spans time the same steps as the counters
    span_s = sum(s[2] - s[1] for s in steps) * 1e-9
    assert span_s == pytest.approx(sum(eng.step_times), rel=0.02)


class _HeldRecord:
    """A step-record leaf whose read blocks for ``delay`` seconds, as a
    record whose device work is late does."""

    def __init__(self, leaf, delay):
        self.leaf, self.delay = leaf, delay

    def is_ready(self):
        return False

    def __array__(self, dtype=None, copy=None):
        time.sleep(self.delay)
        return np.asarray(self.leaf, dtype=dtype)


def test_record_wait_takes_a_held_back_record():
    delay = 0.3
    seen = []
    eng = _engine()
    _submit(eng, range(2), new=8)
    eng.step()
    eng.step()
    eng.reset_step_stats()
    eng.on_step = seen.append
    rec = eng._pending[0]
    eng._pending[0] = dict(rec, token=_HeldRecord(rec["token"], delay))
    # never ready, so read once the lookahead window is full
    for _ in range(3):
        eng.step()
    held = [i for i, w in enumerate(eng.record_wait_times) if w >= delay]
    assert len(held) == 1
    i = held[0]
    wait, wall = eng.record_wait_times[i], eng.step_times[i]
    assert wall - wait < delay / 2
    assert seen[i]["record_wait_s"] == wait and seen[i]["wall_s"] == wall
    assert seen[i]["admit_s"] >= 0 and seen[i]["dispatch_s"] > 0
    assert eng.step_stats()["record_wait_max_ms"] == pytest.approx(wait * 1e3)


@pytest.mark.parametrize("lookahead", [0, 1])
def test_request_stamps_are_ordered(lookahead):
    eng = _engine(lookahead=lookahead)
    t0 = time.perf_counter()
    _submit(eng, range(4))
    eng.run_until_drained(max_steps=60)
    t1 = time.perf_counter()
    assert len(eng.completed) == 4
    for r in eng.completed:
        assert (t0 <= r.submitted_at <= r.admitted_at <= r.first_token_at
                <= r.finished_at <= t1), r


def test_step_stats_report_record_wait_and_host_time():
    eng = _engine()
    _submit(eng, range(4))
    eng.run_until_drained(max_steps=60)
    stats = eng.step_stats()
    n = len(eng.step_times)
    assert n == len(eng.record_wait_times) == len(eng.admit_times) \
        == len(eng.dispatch_times)
    assert 0 <= stats["record_wait_p50_ms"] <= stats["record_wait_max_ms"]
    assert stats["record_wait_max_ms"] == pytest.approx(
        1e3 * max(eng.record_wait_times))
    host = sorted(1e3 * (w - r) for w, r in zip(eng.step_times,
                                               eng.record_wait_times))
    assert 0 < stats["host_p50_ms"] <= stats["step_p50_ms"] + 1e-9
    assert host[0] <= stats["host_p50_ms"] <= host[-1]
    eng.reset_step_stats()
    assert not (eng.record_wait_times or eng.admit_times or eng.dispatch_times)
    assert eng.step_stats()["record_wait_max_ms"] == 0.0


def _program_patterns():
    path = ROOT / "chipbench" / "metrics" / "_programs.py"
    spec = importlib.util.spec_from_file_location("chipbench_programs", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_program_names_match_the_device_trace_readers():
    """The device trace names each executed program after its HLO module;
    the benchmark's readers find the serve step and prefill by these
    names, so a rename has to fail here rather than silence them."""
    pats = _program_patterns()
    eng = _engine()
    serve = eng._serve_step.lower(eng.params, eng.caches, eng.state).compile()
    prefill = eng.scheduler._get_prefill("lm", 8, 2).lower(
        eng.params, np.ones((2, 8), np.int32),
        np.full((2,), 8, np.int32)).compile()
    name = lambda c: re.match(r"HloModule (\S+?),", c.as_text()).group(1)  # noqa: E731
    assert name(serve) == "jit_serve_step"
    assert name(prefill).startswith("jit_prefill")
    assert re.search(pats.SERVE_STEP, name(serve))
    assert re.search(pats.PREFILL, name(prefill))
    assert not re.search(pats.PREFILL, name(serve))


def test_engine_step_timing_hooks(key):
    arch = repro.get_arch("qwen1.5-0.5b").reduced()
    seen = []
    plan = repro.plan(arch, ShapeConfig("hooks", 32, 2, "decode"))
    engine = plan.compile().serve(config=ServeConfig(slots=2, max_len=32),
                                  on_step=seen.append)
    engine.submit(Request(rid=0, prompt=np.arange(1, 5, dtype=np.int32),
                          max_new_tokens=3))
    engine.run_until_drained(max_steps=10)
    stats = engine.step_stats()
    assert stats["steps"] == len(engine.step_times) > 0
    assert stats["tokens"] == 3.0
    assert stats["tokens_per_s"] > 0
    assert stats["step_p95_ms"] >= stats["step_p50_ms"] > 0
    assert [s["step"] for s in seen] == list(range(len(engine.step_times)))
    assert all(s["wall_s"] > 0 for s in seen)
    # lookahead dispatch: every emitted token is accounted exactly once
    assert sum(s["tokens"] for s in seen) == 3
    engine.reset_step_stats()
    assert len(engine.step_times) == 0 and engine.step_stats()["steps"] == 0


def test_engine_prefill_timing_hooks(key):
    """The admission path records per-request wall time (bucketed prefill
    dispatch + splice)."""
    arch = repro.get_arch("qwen1.5-0.5b").reduced()
    plan = repro.plan(arch, ShapeConfig("hooks_p", 32, 2, "decode"))
    engine = plan.compile().serve(config=ServeConfig(slots=2, max_len=32))
    for i, n in enumerate((4, 6, 5)):
        engine.submit(Request(rid=i, prompt=np.arange(1, n + 1, dtype=np.int32),
                              max_new_tokens=1))
    engine.run_until_drained(max_steps=20)
    stats = engine.prefill_stats()
    assert stats["prefills"] == 3.0
    assert stats["prompt_tokens"] == 15.0
    assert stats["prefill_p95_ms"] >= stats["prefill_p50_ms"] > 0
    assert stats["prefill_tokens_per_s"] > 0
    engine.reset_step_stats()
    assert engine.prefill_stats()["prefills"] == 0.0


def test_kv_read_share_counts_the_ragged_kernel_blocks(monkeypatch):
    """``step_stats()["kv_read_share"]``: on a one-device plan built for a
    TPU (the ragged kernel interpreted), every short request lives in the
    first 128-position block of a 384-position grid, inert slots too, so
    the kernel reads a third of it. Without the kernel (this CPU's own
    build) the record has no block count and the share is absent."""
    from repro.kernels import ops
    from repro.kernels import ragged_decode_attention as RK

    arch = dataclasses.replace(ARCH, head_dim=128)  # the kernel's least

    def engine():
        plan = repro.plan(arch, ShapeConfig("telemetry", 384, 2, "decode"))
        return plan.compile().serve(config=ServeConfig(slots=2, max_len=384))

    eng = engine()
    _submit(eng, [0, 1, 2])
    _steps_until_idle(eng)
    assert "kv_read_share" not in eng.step_stats()

    monkeypatch.setattr(ops, "_on_tpu", lambda: True)
    monkeypatch.setattr(
        ops, "ragged_decode_attention",
        lambda *a, interpret, **kw: RK.ragged_decode_attention(
            *a, interpret=True, **kw))
    eng = engine()
    _submit(eng, [0, 1, 2])
    _steps_until_idle(eng)
    assert eng.step_stats()["kv_read_share"] == pytest.approx(1 / 3)
