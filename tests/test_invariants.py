"""Fast structural invariants + the testing harness's own machinery.

The 1-device cases exercise the checks' structure (tree coverage, axis
validity, capacity reproducibility); the real sharded variants run in the
slow suite (tests/test_conformance.py) on 8 fake devices.
"""
import dataclasses
import warnings

import pytest

import repro
from repro.configs.base import ShapeConfig
from repro.testing import invariants as I
from repro.testing import mesh_fixtures as MF
from repro.testing.differential import (Tolerance, compare_trees, kind_shape,
                                        make_batch, proposed_plans)

ARCH = repro.get_arch("qwen1.5-0.5b").reduced()
DEGENERATE = (("data", 1), ("model", 1))


# ------------------------- sharding coverage ---------------------------

def test_sharding_coverage_every_candidate_plan():
    shape = ShapeConfig("inv", 32, 8, "decode")
    plans = proposed_plans(ARCH, shape, DEGENERATE)
    assert plans
    for eplan in plans:
        assert I.check_sharding_coverage(eplan) > 0


def test_sharding_coverage_counts_all_leaves():
    import jax
    shape = ShapeConfig("inv", 32, 8, "decode")
    eplan = proposed_plans(ARCH, shape, DEGENERATE)[0]
    from repro.models import registry as REG
    params = jax.eval_shape(lambda k: REG.init_params(ARCH, k),
                            jax.random.PRNGKey(0))
    assert I.check_sharding_coverage(eplan) == len(jax.tree.leaves(params))


# ------------------------- capacity report -----------------------------

def test_capacity_report_reproducible_full_size():
    # hypothetical 256-chip mesh: pure planning, no devices needed
    eplan = repro.plan("minitron-8b", "train_4k", (("data", 16), ("model", 16)))
    I.check_capacity_report(eplan)


def test_capacity_report_int8_note_handled():
    # llama4 train fits MESH2 only with int8 Adam states (planner note)
    eplan = repro.plan("llama4-maverick-400b-a17b", "train_4k",
                       (("pod", 2), ("data", 16), ("model", 16)))
    assert "int8" in eplan.report.note
    I.check_capacity_report(eplan)


def test_capacity_report_detects_corruption():
    eplan = repro.plan("minitron-8b", "train_4k", (("data", 16), ("model", 16)))
    bad = dataclasses.replace(
        eplan, report=dataclasses.replace(eplan.report,
                                          hbm_bytes_per_device=123.0))
    with pytest.raises(I.InvariantViolation, match="recomputes"):
        I.check_capacity_report(bad)


# ------------------------- xfer accounting -----------------------------

def test_expected_xfer_bytes_zero_without_xfer():
    shape = ShapeConfig("inv", 32, 8, "decode")
    off = [p for p in proposed_plans(ARCH, shape, DEGENERATE)
           if not p.sharding_plan.xfer]
    assert off and I.expected_xfer_gather_bytes(off[0]) == 0.0
    # and the band check degrades to report-only for non-XFER plans
    out = I.check_xfer_accounting(off[0], "HloModule empty")
    assert out["expected_xfer_bytes"] == 0.0


def test_measured_collective_bytes_parses_hlo():
    hlo = ("HloModule m\n"
           "ENTRY %main () -> f32[16] {\n"
           "  %p = f32[4]{0} parameter(0)\n"
           "  ROOT %ag = f32[16]{0} all-gather(%p), replica_groups={{0,1,2,3}}, "
           "dimensions={0}\n"
           "}\n")
    got = I.measured_collective_bytes(hlo)
    assert got.get("all-gather", 0.0) > 0


# ------------------------- differential helpers ------------------------

def test_compare_trees_tolerance_and_exactness():
    import numpy as np
    a = {"x": np.array([1.0, 2.0], np.float32), "i": np.array([1, 2])}
    b = {"x": np.array([1.0, 2.0 + 1e-5], np.float32), "i": np.array([1, 2])}
    diffs = compare_trees(a, b, Tolerance(max_abs=1e-4))
    assert all(d.ok for d in diffs)
    diffs = compare_trees(a, b, Tolerance(max_abs=1e-7, max_ulp=1.0))
    assert not all(d.ok for d in diffs)
    # integer leaves must match exactly
    c = {"x": b["x"], "i": np.array([1, 3])}
    diffs = compare_trees(c, b, Tolerance(max_abs=1e-4))
    assert not all(d.ok for d in diffs)


def test_compare_trees_rejects_nonfinite_divergence():
    """An overflowing sharded run (inf/NaN where golden is finite) must
    fail, not slip through the ulp escape hatch (spacing(inf) is NaN)."""
    import numpy as np
    want = {"x": np.array([1.0, 2.0], np.float32)}
    inf_got = {"x": np.array([np.inf, 2.0], np.float32)}
    assert not all(d.ok for d in compare_trees(inf_got, want, Tolerance()))
    nan_got = {"x": np.array([np.nan, 2.0], np.float32)}
    assert not all(d.ok for d in compare_trees(nan_got, want, Tolerance()))
    # matching non-finite values are equal, not divergent
    both = {"x": np.array([np.inf, np.nan], np.float32)}
    diffs = compare_trees(both, {"x": both["x"].copy()}, Tolerance())
    assert all(d.ok for d in diffs) and diffs[0].max_abs_err == 0.0
    # mismatched infinity signs diverge
    neg = {"x": np.array([-np.inf, np.nan], np.float32)}
    assert not all(d.ok for d in compare_trees(neg, both, Tolerance()))


def test_make_batch_is_deterministic_and_spec_complete():
    import numpy as np
    for kind in ("forward", "decode", "train_step"):
        shape = kind_shape(ShapeConfig("mb", 16, 2, "decode"), kind)
        a = make_batch(ARCH, shape, seed=3)
        b = make_batch(ARCH, shape, seed=3)
        for k in a:
            np.testing.assert_array_equal(np.asarray(a[k]), np.asarray(b[k]))
        from repro.models import registry as REG
        assert set(a) == set(REG.input_specs(ARCH, shape))


def test_proposed_plans_cover_xfer_both_ways():
    shape = ShapeConfig("pp", 32, 8, "train")
    plans = proposed_plans(ARCH, shape, (("data", 4), ("model", 2)))
    flags = {p.sharding_plan.xfer for p in plans}
    assert flags == {True, False}


# ------------------------- mesh fixtures -------------------------------

def test_merged_flags_appends_and_replaces():
    merged = MF._merged_flags("--xla_foo=1 --xla_force_host_platform_device_count=4", 8)
    assert merged.split() == ["--xla_foo=1",
                              "--xla_force_host_platform_device_count=8"]
    assert MF._merged_flags("", 2) == "--xla_force_host_platform_device_count=2"


def test_force_host_device_count_env_dict():
    env = {"XLA_FLAGS": "--xla_bar=7"}
    assert MF.force_host_device_count(8, env=env)
    assert "--xla_bar=7" in env["XLA_FLAGS"]
    assert "--xla_force_host_platform_device_count=8" in env["XLA_FLAGS"]
    assert env["JAX_PLATFORMS"] == "cpu"  # simulated devices live on the host
    with pytest.raises(ValueError):
        MF.force_host_device_count(0, env=env)


def test_force_host_device_count_noops_after_backend_init():
    import os

    import jax
    jax.devices()  # ensure the backend exists
    assert MF.backend_initialized()
    before = os.environ.get("XLA_FLAGS")
    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        assert not MF.force_host_device_count(8)
        # context-manager form: applied=False, env untouched
        with MF.fake_devices(8) as applied:
            assert not applied
            assert os.environ.get("XLA_FLAGS") == before
    assert any("already initialised" in str(x.message) for x in w)
    assert os.environ.get("XLA_FLAGS") == before


def test_mesh_shape_registry():
    # dp2_tp2 is the 4-device grid the replan cells grow/shrink through;
    # everything else fills all 8 fake devices
    assert set(MF.mesh_shape_names(8)) == set(MF.MESH_SHAPES) - {"dp2_tp2"}
    assert MF.mesh_shape_names(4) == ["dp2_tp2"]
    assert set(MF.mesh_shape_names(None)) == set(MF.MESH_SHAPES)
    for name in MF.MESH_SHAPES:
        n = 1
        for _, s in MF.mesh_shape(name):
            n *= s
        assert n == (4 if name == "dp2_tp2" else 8), name
    with pytest.raises(KeyError, match="unknown mesh shape"):
        MF.mesh_shape("nope")


def test_build_mesh_from_registered_axes():
    mesh = MF.build_mesh(DEGENERATE)
    assert dict(mesh.shape) == {"data": 1, "model": 1}
    # more devices than this 1-CPU process has: refuse with the
    # run_in_subprocess pointer instead of a bare XLA error
    with pytest.raises(RuntimeError, match="run_in_subprocess"):
        MF.build_mesh(MF.mesh_shape("dp8"))


def test_run_in_subprocess_forces_device_count():
    r = MF.run_in_subprocess(
        "import jax; print('DEVCOUNT', jax.device_count())",
        devices=2, timeout=300, marker="DEVCOUNT 2")
    assert r.returncode == 0


def test_run_in_subprocess_pins_cpu_platform():
    """The child simulates devices on the host: it must never reach for
    the accelerator its parent already holds."""
    r = MF.run_in_subprocess(
        "import os, jax; print('PLATFORM', os.environ['JAX_PLATFORMS'], "
        "jax.default_backend())",
        devices=2, timeout=300, marker="PLATFORM cpu cpu",
        extra_env={"JAX_PLATFORMS": "tpu"})
    assert r.returncode == 0


# ------------------------- compile cache -------------------------------

@pytest.fixture()
def cache_config():
    import jax
    before = jax.config.jax_compilation_cache_dir
    yield jax.config
    jax.config.update("jax_compilation_cache_dir", before)


def test_compile_cache_env_var_wins(cache_config, monkeypatch, tmp_path):
    from repro.launch import compile_cache as CC
    before = cache_config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert CC.enable_compile_cache() == str(tmp_path)
    # JAX read the variable itself; the helper sets no other directory
    assert cache_config.jax_compilation_cache_dir == before


def test_compile_cache_defaults_to_checkout(cache_config, monkeypatch):
    import pathlib

    from repro.launch import compile_cache as CC
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    checkout = pathlib.Path(__file__).resolve().parents[1]
    expected = str(checkout / ".jax_cache")
    assert CC.compile_cache_dir() == expected
    assert CC.enable_compile_cache() == expected
    assert cache_config.jax_compilation_cache_dir == expected
