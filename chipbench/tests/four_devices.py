"""Run the tiny backlog cell over four virtual CPU devices, optionally
with the chips' exchange in decode attention left out; prints the result's
``correct`` and checks. Started by ``test_faults`` in a child process,
because the device count is fixed when JAX starts."""
import json
import os
import pathlib
import sys

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
os.environ["JAX_PLATFORMS"] = "cpu"
HERE = pathlib.Path(__file__).resolve().parent
sys.path[:0] = [str(HERE.parents[1]), str(HERE.parents[1] / "src"), str(HERE)]

import jax  # noqa: E402

import tiny  # noqa: E402
from chipbench import harness as H  # noqa: E402

if sys.argv[1] == "no_exchange":
    calls = []

    def local_only(x, axis_name, **kw):
        calls.append(axis_name)
        return x
    jax.lax.psum = local_only
H.REFERENCE_MIN_TOKENS = 8   # as conftest.tiny_sizes sets it in-process
cfg, mix = tiny.fresh(tiny.CONFIG), tiny.fresh(tiny.DECODE)
mix["drain_seconds"] = 5
cell = dict(tiny.cell(mix), chips=4)
res = H.run_cell(cell, cfg, mix, [], 2**31 + 5, 1.5, False, require_tpu=False)
print(json.dumps({"correct": res["correct"], "checks": res["checks"],
                  "devices": res["device"]["count"],
                  "psum_calls": len(calls) if sys.argv[1] == "no_exchange"
                  else None}))
