"""runtime/elastic.py: grid selection, load controller, live migration.

The property-based block uses hypothesis. The cross-mesh migration cells
live in test_conformance.py (slow, subprocess, 8 fake devices); here the
migration machinery is exercised end-to-end on the in-process device so
tier-1 covers it.
"""
import types

import jax
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

import repro
from repro.configs import get_arch
from repro.configs.base import ShapeConfig
from repro.runtime.elastic import (LoadController, _best_grid, replan,
                                   replan_execution)
from repro.serving import ServeConfig
from repro.serving.config import ElasticConfig
from repro.serving.scheduler import Request
from repro.testing.mesh_fixtures import run_in_subprocess

ARCH = get_arch("qwen1.5-0.5b").reduced()
SHAPE = ShapeConfig("elastic_t", 32, 4, "decode")


# ------------------------- _best_grid properties -------------------------
@settings(max_examples=60, deadline=None)
@given(st.integers(min_value=1, max_value=256))
def test_best_grid_uses_at_most_n_and_factors(n):
    data, model = _best_grid(n)
    assert data >= 1 and model in (1, 2, 4, 8, 16, 32)
    assert data * model <= n


@settings(max_examples=60, deadline=None)
@given(st.integers(min_value=1, max_value=255))
def test_best_grid_utilization_monotone(n):
    """More devices never means fewer used (the grid can always keep the
    smaller count's factorisation)."""
    used = lambda k: _best_grid(k)[0] * _best_grid(k)[1]
    assert used(n + 1) >= used(n)


@settings(max_examples=40, deadline=None)
@given(st.integers(min_value=1, max_value=256))
def test_best_grid_model_axis_divides_heads(n):
    data, model = _best_grid(n, ARCH)
    assert ARCH.num_heads % model == 0
    assert data * model <= n


def test_best_grid_arch_regression_nondividing_model():
    """Regression: with 64 devices the unconstrained grid is (8, 8), but
    a 4-head arch cannot split attention over model=8 — the arch-aware
    grid must fall back to a head-dividing model axis."""
    assert _best_grid(64) == (8, 8)
    assert ARCH.num_heads == 4
    data, model = _best_grid(64, ARCH)
    assert ARCH.num_heads % model == 0
    assert data * model == 64  # divisibility costs no devices here
    # replan threads the arch through (the auto-mesh api path does too)
    mesh, ctx, rep = replan(ARCH, SHAPE)
    assert ARCH.num_heads % mesh.shape["model"] == 0


# ---------------------------- load controller ----------------------------
def _fake_engine(depth, p50=1.0, ndev=4):
    eng = types.SimpleNamespace()
    eng.plan = types.SimpleNamespace(num_devices=ndev)
    eng.step_stats = lambda: {"steps": 100.0, "queue_depth": float(depth),
                              "step_p50_ms": float(p50)}
    eng.prefill_stats = lambda: {"prefills": 0.0}
    return eng


def test_load_controller_grow_shrink_hold():
    cfg = ElasticConfig(grow_queue_depth=4.0, shrink_queue_depth=0.5)
    devices = list(range(8))
    ladder = [2, 4, 8]
    grow = LoadController(_fake_engine(depth=10.0), cfg, devices=devices,
                          device_ladder=ladder)
    assert grow.decide() == ("grow", 8)
    shrink = LoadController(_fake_engine(depth=0.0), cfg, devices=devices,
                            device_ladder=ladder)
    assert shrink.decide() == ("shrink", 2)
    hold = LoadController(_fake_engine(depth=2.0), cfg, devices=devices,
                          device_ladder=ladder)
    assert hold.decide() == ("hold", None)
    # at the top rung there is nothing to grow into
    top = LoadController(_fake_engine(depth=10.0, ndev=8), cfg,
                         devices=devices, device_ladder=ladder)
    assert top.decide() == ("hold", None)


def test_load_controller_shrink_needs_latency_headroom():
    cfg = ElasticConfig(shrink_queue_depth=0.5, shrink_step_p50_ms=2.0)
    ctl = LoadController(_fake_engine(depth=0.0, p50=50.0), cfg,
                         devices=list(range(8)), device_ladder=[2, 4, 8])
    assert ctl.decide() == ("hold", None)


def test_load_controller_cooldown_blocks_resize():
    cfg = ElasticConfig(grow_queue_depth=1.0, cooldown_steps=1000)
    ctl = LoadController(_fake_engine(depth=10.0), cfg,
                         devices=list(range(8)), device_ladder=[2, 4, 8])
    assert ctl.decide()[0] == "grow"
    assert ctl.observe() is None  # 100 steps seen < 1000 cooldown


def test_elastic_config_validation_and_kwargs():
    with pytest.raises(ValueError):
        ElasticConfig(grow_queue_depth=1.0, shrink_queue_depth=2.0)
    cfg = ServeConfig.from_kwargs(slots=2, max_len=32,
                                  elastic=ElasticConfig())
    assert cfg.elastic is not None
    with pytest.raises(TypeError):
        ServeConfig.from_kwargs(elastic_mode=True)


# ------------------------- live migration (tier-1) ------------------------
def _drain(eng, plan_b=None, migrate_at=None):
    steps = 0
    report = None
    while eng.queue or eng.scheduler.has_active():
        if migrate_at is not None and steps == migrate_at:
            report = eng.migrate(plan_b)
        eng.step()
        steps += 1
        assert steps < 400
    eng._flush()
    return {r.rid: list(r.out_tokens) for r in eng.completed}, report


def test_migrate_mid_stream_bit_exact_single_device():
    """plan→plan migration on the in-process device: streams served
    across the move are bit-identical to the never-migrated run, no
    request is lost, and the transfer accounting verifies."""
    mesh = (("data", 1), ("model", 1))
    plan_a = repro.plan(ARCH, SHAPE, mesh)
    plan_b = repro.plan(ARCH, SHAPE, mesh)
    cfg = ServeConfig(slots=2, max_len=32)

    def engine():
        eng = plan_a.compile().serve(config=cfg)
        for rid in range(4):  # oversubscribed: queue crosses the move too
            eng.submit(Request(rid=rid, prompt=[2 + rid, 3, 5],
                               max_new_tokens=4))
        return eng

    want, _ = _drain(engine())
    got, report = _drain(engine(), plan_b, migrate_at=2)
    assert got == want
    assert report is not None and report.verified
    assert report.active_slots > 0
    assert sum(len(t) for t in got.values()) == 4 * 4  # zero tokens lost
    # same axes + same devices -> nothing physically moves
    assert report.moved_bytes == 0 and report.drained_slots == 0


def test_migrate_rejects_arch_change():
    plan_a = repro.plan(ARCH, SHAPE, (("data", 1), ("model", 1)))
    other = get_arch("minitron-8b").reduced()
    plan_b = repro.plan(other, SHAPE, (("data", 1), ("model", 1)))
    eng = plan_a.compile().serve(config=ServeConfig(slots=2, max_len=32))
    with pytest.raises(ValueError):
        eng.migrate(plan_b)


def test_serve_config_elastic_attaches_controller():
    plan = repro.plan(ARCH, SHAPE, (("data", 1), ("model", 1)))
    eng = plan.compile().serve(config=ServeConfig(
        slots=2, max_len=32, elastic=ElasticConfig(cooldown_steps=10**6)))
    assert isinstance(eng.elastic, LoadController)
    assert eng.maybe_resize() is None  # empty telemetry + cooldown: hold


# ------------------------ shrink replan (8 -> 6) -------------------------
_SHRINK_SCRIPT = """
import jax
from repro.configs import get_arch
from repro.configs.base import ShapeConfig
from repro.runtime.elastic import replan_execution
from repro.serving import ServeConfig
from repro.serving.scheduler import Request

arch = get_arch("qwen1.5-0.5b").reduced()
shape = ShapeConfig("shrink", 32, 4, "decode")
devices = jax.devices()[:6]  # two of eight devices just went away
plan = replan_execution(arch, shape, devices)
assert plan.num_devices <= 6, plan.mesh_axes
assert arch.num_heads % dict(plan.mesh_axes)["model"] == 0
assert plan.feasible, plan.describe()
eng = plan.compile().serve(config=ServeConfig(slots=2, max_len=32))
for rid in range(3):
    eng.submit(Request(rid=rid, prompt=[2, 3, 5], max_new_tokens=4))
eng.run_until_drained(max_steps=500)
assert len(eng.completed) == 3
print("ELASTIC_SHRINK_OK", dict(plan.mesh_axes))
"""


@pytest.mark.slow
def test_replan_after_shrink_8_to_6_is_servable():
    """Losing 2 of 8 devices: replan must pick a feasible sub-grid of the
    6 survivors and the resulting plan must actually serve."""
    run_in_subprocess(_SHRINK_SCRIPT, devices=8, timeout=900,
                      marker="ELASTIC_SHRINK_OK")
