"""§Perf measurement harness: one (arch × shape) cell, baseline vs optimized.

    PYTHONPATH=src:. python -m benchmarks.hillclimb <arch> <shape> [baseline|optimized]

`baseline` sets REPRO_EXPLICIT_SPMD=0 (pure-GSPMD paths: no shard_map
attention locality, no explicit EP all-to-all, no flash-decoding, original
head-sharded cache layout) — the paper-faithful GSPMD implementation.
`optimized` (default) is the beyond-paper explicit-SPMD path.

Must run as its own process: the 512-device host platform and the env
toggle are locked at jax import.
"""
import os
import sys


def main():
    arch, shape = sys.argv[1], sys.argv[2]
    mode = sys.argv[3] if len(sys.argv) > 3 else "optimized"
    if mode == "baseline":
        os.environ["REPRO_EXPLICIT_SPMD"] = "0"
    # importing dryrun pins the CPU platform and forces 512 host devices
    # (via testing.mesh_fixtures: appends to XLA_FLAGS, never overwrites)
    from repro.launch.dryrun import lower_cell
    from repro.launch import hlo_analysis as H

    rep, mesh, lowered = lower_cell(arch, shape, False)
    c = H.analyze(lowered.compile().as_text())
    scale, u = (1e3, "ms") if shape.startswith(("decode", "long")) else (1.0, "s")
    print(f"RESULT {arch} {shape} {mode}: "
          f"compute {c.flops * scale / 197e12:.3f}{u} "
          f"memory {c.hbm_bytes * scale / 819e9:.3f}{u} "
          f"collective {c.collective_wire_bytes * scale / 100e9:.3f}{u} "
          f"plan=[{rep.plan.describe()}]")


if __name__ == "__main__":
    main()
