"""Fake-device meshes: XLA_FLAGS handling + subprocess runner + mesh shapes.

XLA's host platform can simulate N devices on one CPU via
``--xla_force_host_platform_device_count=N`` — but only if the flag is in
``XLA_FLAGS`` *before* the first backend initialisation, and only in a
process whose backend has not already been created. Everything here deals
with those two constraints:

* :func:`force_host_device_count` edits ``XLA_FLAGS`` by **appending**
  (user-set flags survive; a previous force flag is replaced) and refuses
  to touch the environment once the backend is initialised — the bug the
  old ``launch/dryrun.py`` / ``bench/hillclimb.py`` import-time
  ``os.environ["XLA_FLAGS"] = ...`` overwrite had. It also pins the CPU
  platform (:func:`pin_cpu_platform`): simulated devices live on the host,
  and a process that initialised the accelerator instead would hold the
  chip another process needs (one process per chip).
* :func:`fake_devices` is the context-managed form for launcher
  entry points (set, run, restore).
* :func:`run_in_subprocess` runs a script under a fresh XLA client with a
  forced device count — the only reliable way to get an N-device mesh
  from inside an already-initialised pytest process.
* :data:`MESH_SHAPES` is the registry of small mesh shapes the
  conformance suite parametrizes over (named by parallelism role:
  ``dp`` maps to the ``data`` axis, ``tp``/``ep`` to ``model``, the
  3-axis entry adds a data-like ``pod`` axis — planner axis-role
  conventions, see ``core/planner.candidate_plans``).

This module imports no JAX at module scope on purpose: launcher code must
be able to call :func:`force_host_device_count` before its own first
``import jax``.
"""
from __future__ import annotations

import contextlib
import os
import subprocess
import sys
import warnings
from typing import Dict, List, Optional, Tuple

FORCE_FLAG = "--xla_force_host_platform_device_count"

MeshAxes = Tuple[Tuple[str, int], ...]

# Small meshes for 8 fake devices, keyed by parallelism role. The planner
# treats every non-"model" axis as data-like (batch/seq roles) and "model"
# as the TP/EP axis, so role names map onto the repo's axis names.
MESH_SHAPES: Dict[str, MeshAxes] = {
    "dp8": (("data", 8), ("model", 1)),
    "tp8": (("data", 1), ("model", 8)),
    "dp4_tp2": (("data", 4), ("model", 2)),
    "dp2_tp4": (("data", 2), ("model", 4)),
    # 4-device grid: the replan conformance cells migrate between this
    # and an 8-device shape in one process (grow/shrink the device set)
    "dp2_tp2": (("data", 2), ("model", 2)),
    "pod2_dp2_tp2": (("pod", 2), ("data", 2), ("model", 2)),
}


def mesh_shape(name: str) -> MeshAxes:
    if name not in MESH_SHAPES:
        raise KeyError(f"unknown mesh shape {name!r}; known: {sorted(MESH_SHAPES)}")
    return MESH_SHAPES[name]


def mesh_shape_names(num_devices: Optional[int] = 8) -> List[str]:
    """Registered mesh-shape names, optionally filtered to a device count."""
    out = []
    for name, axes in MESH_SHAPES.items():
        n = 1
        for _, s in axes:
            n *= s
        if num_devices is None or n == num_devices:
            out.append(name)
    return out


def backend_initialized() -> bool:
    """True once this process has created an XLA backend (device count is
    locked from then on; XLA_FLAGS edits no longer take effect)."""
    xla_bridge = sys.modules.get("jax._src.xla_bridge")
    if xla_bridge is None:
        return False  # jax internals not even imported yet
    return bool(getattr(xla_bridge, "_backends", None))


def _merged_flags(existing: str, n: int) -> str:
    """Append the force flag to an XLA_FLAGS string, replacing any previous
    force flag but preserving every other user-set flag."""
    kept = [f for f in existing.split()
            if not f.startswith(FORCE_FLAG + "=") and f != FORCE_FLAG]
    kept.append(f"{FORCE_FLAG}={n}")
    return " ".join(kept)


def pin_cpu_platform(env: Optional[Dict[str, str]] = None) -> None:
    """Keep a process on the CPU backend: ``JAX_PLATFORMS=cpu``.

    ``env`` (a child's environment) is edited as given. Without it the
    edit targets this process; JAX reads ``JAX_PLATFORMS`` when it is
    imported, so an already-imported JAX is pinned through its config as
    well (effective until the backend is created).
    """
    if env is not None:
        env["JAX_PLATFORMS"] = "cpu"
        return
    os.environ["JAX_PLATFORMS"] = "cpu"
    jax = sys.modules.get("jax")
    if jax is not None:
        jax.config.update("jax_platforms", "cpu")


def force_host_device_count(n: int, env: Optional[Dict[str, str]] = None) -> bool:
    """Request ``n`` fake host devices by editing ``XLA_FLAGS`` in place,
    and pin the CPU platform they live on.

    Appends to the existing value instead of overwriting it. When ``env``
    is None the edit targets ``os.environ`` and is refused (returns False,
    with a warning) if the XLA backend already exists in this process —
    the flag could no longer take effect and clobbering the environment
    would only mislead child processes that inherit it deliberately.

    Pass an explicit ``env`` dict (e.g. a copy for ``subprocess.run``) to
    edit unconditionally — a fresh child process always honours the flag.
    """
    if n <= 0:
        raise ValueError(f"device count must be positive, got {n}")
    if env is None:
        if backend_initialized():
            warnings.warn(
                f"force_host_device_count({n}): XLA backend already "
                "initialised — flag would be ignored; leaving XLA_FLAGS "
                "untouched (use run_in_subprocess for a fresh client)",
                RuntimeWarning, stacklevel=2)
            return False
        pin_cpu_platform()
        env = os.environ
    else:
        pin_cpu_platform(env)
    env["XLA_FLAGS"] = _merged_flags(env.get("XLA_FLAGS", ""), n)
    return True


@contextlib.contextmanager
def fake_devices(n: int):
    """Context manager: ``n`` fake host devices for code run inside.

    Must enter before the first backend initialisation (launcher
    entry points, subprocess scripts). The previous ``XLA_FLAGS`` and
    ``JAX_PLATFORMS`` values are restored on exit — the *backend*,
    however, keeps whatever device count and platform it first initialised
    with; the restore only protects later child processes from inheriting
    the forced settings.

    Yields True when the flag was applied, False when the backend was
    already up (in which case the environment is untouched).
    """
    before = {k: os.environ.get(k) for k in ("XLA_FLAGS", "JAX_PLATFORMS")}
    applied = force_host_device_count(n)
    try:
        yield applied
    finally:
        if applied:
            for k, v in before.items():
                if v is None:
                    os.environ.pop(k, None)
                else:
                    os.environ[k] = v


def run_in_subprocess(script: str, *, devices: int = 8, timeout: int = 600,
                      marker: Optional[str] = None,
                      extra_env: Optional[Dict[str, str]] = None,
                      ) -> subprocess.CompletedProcess:
    """Run ``script`` in a fresh python with ``devices`` fake host devices.

    A fresh process gets its own XLA client, so the forced device count
    applies no matter what this process's backend looks like — the pattern
    every multi-device CPU test uses. ``PYTHONPATH`` and the rest of the
    environment are inherited; the force flag is appended to (not
    overwriting) any inherited ``XLA_FLAGS``. The child runs with
    ``JAX_PLATFORMS=cpu``: its devices are simulated on the host, and a
    parent on an accelerator host already holds the chip.

    When ``marker`` is given, asserts it appears on the child's stdout and
    raises AssertionError carrying the stderr tail otherwise — the
    standard "print sentinel on success" subprocess-test contract.
    """
    env = dict(os.environ)
    if extra_env:
        env.update(extra_env)
    force_host_device_count(devices, env=env)
    r = subprocess.run([sys.executable, "-c", script], capture_output=True,
                       text=True, timeout=timeout, env=env)
    if marker is not None:
        assert marker in r.stdout, (
            f"subprocess did not print {marker!r} (rc={r.returncode})\n"
            f"--- stdout tail ---\n{r.stdout[-1000:]}\n"
            f"--- stderr tail ---\n{r.stderr[-2000:]}")
    return r


def build_mesh(axes: MeshAxes):
    """Materialise a registered mesh shape over the live device set.

    Requires the live process to already have enough devices (i.e. you are
    inside a :func:`run_in_subprocess` child or a forced-count launcher).
    """
    import jax

    from repro.launch.mesh import make_mesh
    n = 1
    for _, s in axes:
        n *= s
    avail = jax.devices()
    if n > len(avail):
        raise RuntimeError(
            f"mesh {dict(axes)} needs {n} devices but only {len(avail)} "
            f"exist — run under run_in_subprocess(devices={n}) or force "
            "the host device count before jax initialises")
    return make_mesh(tuple(s for _, s in axes), tuple(a for a, _ in axes),
                     devices=avail[:n])
