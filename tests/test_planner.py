"""Planner (Eq. 15 DSE) behaviour across cells and meshes.

The property-based block at the bottom uses hypothesis.
"""
import dataclasses

import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.configs import ARCH_IDS, SHAPES, cell_is_runnable, get_arch
from repro.core.planner import candidate_plans, capacity_bytes, plan_cell

MESH1 = (("data", 16), ("model", 16))
MESH2 = (("pod", 2), ("data", 16), ("model", 16))


@pytest.mark.parametrize("arch_id", ARCH_IDS)
@pytest.mark.parametrize("shape_id", list(SHAPES))
def test_plan_every_cell(arch_id, shape_id):
    arch, shape = get_arch(arch_id), SHAPES[shape_id]
    if not cell_is_runnable(arch, shape)[0]:
        pytest.skip("cell skipped by design")
    rep = plan_cell(arch, shape, MESH1)
    assert rep.predicted_seconds > 0
    f = rep.plan.factors
    assert f.Pb * f.Pr <= 256 and f.Pm <= 256
    # batch factor divides global batch
    assert shape.global_batch % max(f.Pb, 1) == 0


def test_multipod_speedup_over_single_pod():
    arch, shape = get_arch("minitron-8b"), SHAPES["train_4k"]
    t1 = plan_cell(arch, shape, MESH1).predicted_seconds
    t2 = plan_cell(arch, shape, MESH2).predicted_seconds
    assert t2 < t1  # 512 chips beat 256
    assert t2 < 0.75 * t1  # and by a sane margin


def test_xfer_wins_capacity_for_training():
    """Paper's core claim, capacity side: distributing weights over the
    sharing group divides per-device HBM residency."""
    arch, shape = get_arch("phi3-medium-14b"), SHAPES["train_4k"]
    plans = candidate_plans(arch, shape, MESH1)
    on = [p for p in plans if p.xfer and p.factors.Pb == 16]
    off = [p for p in plans if not p.xfer and p.factors.Pb == 16]
    assert on and off
    cap_on = capacity_bytes(arch, shape, on[0])
    cap_off = capacity_bytes(arch, shape, off[0])
    # params shard 16x further; opt states (ZeRO-1) shard either way, so the
    # total drops by the param+grad share (~2x here), not the full 16x.
    assert cap_on < 0.6 * cap_off


def test_planner_prefers_tp_for_low_batch_decode():
    arch, shape = get_arch("minitron-8b"), SHAPES["decode_32k"]
    rep = plan_cell(arch, shape, MESH1)
    assert rep.plan.factors.Pm >= 16  # model parallelism engaged


def test_force_xfer_flag():
    arch, shape = get_arch("yi-9b"), SHAPES["train_4k"]
    on = plan_cell(arch, shape, MESH1, force_xfer=True)
    off = plan_cell(arch, shape, MESH1, force_xfer=False)
    assert on.plan.xfer and not off.plan.xfer
    # time-domain prediction: gathers overlap, so xfer is never much slower
    assert on.predicted_seconds <= off.predicted_seconds * 1.2


def test_llama4_train_needs_multipod_or_int8():
    arch, shape = get_arch("llama4-maverick-400b-a17b"), SHAPES["train_4k"]
    r1 = plan_cell(arch, shape, MESH1)
    r2 = plan_cell(arch, shape, MESH2)
    assert not r1.fits_hbm  # 784B params cannot fit 256 x 16GB
    assert r2.fits_hbm and "int8" in r2.note


# ---------------------------------------------------------------------------
# property-based: dedupe-key stability, determinism, monotonicity
# ---------------------------------------------------------------------------

_RUNNABLE = [(a, s) for a in ARCH_IDS for s in SHAPES
             if cell_is_runnable(get_arch(a), SHAPES[s])[0]]


def _dedupe_key(p):
    # the identity candidate_plans dedupes on — ep_axes included: MoE plans
    # differing only in expert-parallel assignment are distinct candidates
    return (p.batch_axes, p.seq_axes, p.tp_axes, p.xfer, p.ep_axes)


@settings(max_examples=15, deadline=None)
@given(st.sampled_from(_RUNNABLE), st.sampled_from([2, 4, 16]),
       st.sampled_from([1, 2, 8, 16]))
def test_candidate_dedupe_keys_unique_and_stable(cell, data, model):
    arch, shape = get_arch(cell[0]), SHAPES[cell[1]]
    mesh = (("data", data), ("model", model))
    plans = candidate_plans(arch, shape, mesh)
    keys = [_dedupe_key(p) for p in plans]
    assert len(set(keys)) == len(keys), f"duplicate candidates for {cell}"
    # stable across calls (same candidates, same order)
    assert [_dedupe_key(p) for p in candidate_plans(arch, shape, mesh)] == keys
    # ep_axes is load-bearing in the key: erasing it must change identity
    for p in plans:
        if p.ep_axes:
            assert _dedupe_key(dataclasses.replace(p, ep_axes=())) != _dedupe_key(p)


@settings(max_examples=12, deadline=None)
@given(st.sampled_from(_RUNNABLE), st.sampled_from([1, 2, 4, 16]),
       st.sampled_from([1, 2, 8, 16]))
def test_plan_cell_deterministic(cell, data, model):
    """Same cell in, same PlanReport out — the DSE has no hidden state."""
    arch, shape = get_arch(cell[0]), SHAPES[cell[1]]
    mesh = (("data", data), ("model", model))
    r1, r2 = plan_cell(arch, shape, mesh), plan_cell(arch, shape, mesh)
    assert r1.plan == r2.plan
    assert r1.predicted_seconds == r2.predicted_seconds
    assert r1.per_layer == r2.per_layer
    assert r1.layer_choices == r2.layer_choices
    assert (r1.hbm_bytes_per_device, r1.fits_hbm, r1.note) == \
           (r2.hbm_bytes_per_device, r2.fits_hbm, r2.note)


@settings(max_examples=12, deadline=None)
@given(st.sampled_from(_RUNNABLE), st.sampled_from([1, 2, 4, 8]),
       st.sampled_from([1, 4, 16]))
def test_more_data_devices_never_slower(cell, data, model):
    """Monotonicity: doubling the data axis never increases predicted
    latency — as long as the batch still divides, so the new devices can
    actually absorb work (Pb/Pr). Deliberately NOT asserted for the tp
    axis or for indivisible batches (long_500k has batch 1): there, extra
    devices buy only collectives, and the model honestly predicts the
    slowdown — that prediction is the planner's reason to not use them.
    """
    arch, shape = get_arch(cell[0]), SHAPES[cell[1]]
    if shape.global_batch % (2 * data) != 0:
        return
    t1 = plan_cell(arch, shape, (("data", data), ("model", model))).predicted_seconds
    t2 = plan_cell(arch, shape, (("data", 2 * data), ("model", model))).predicted_seconds
    assert t2 <= t1 * (1 + 1e-9), (cell, data, model, t1, t2)
