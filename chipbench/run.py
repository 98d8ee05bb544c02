#!/usr/bin/env python3
"""Run one benchmark cell once on the chip and print its result.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

From the root of a checkout. The cell, its configuration, traffic mix and
metrics are read from ``BENCHMARK.json``. The run sets up (plan, weights
from the seed, warm-up of every shape the mix uses), measures for
``--seconds`` on the host clock with the profiler off (``--trace 0``: the
cell's end-to-end metrics) or with a few seconds of it traced
(``--trace 1``: its per-layer metrics), checks the served tokens against
the float32 reference, and prints one JSON object as the last line of
standard output. Progress and the compared numbers go to standard error.
Without a TPU, or with fewer chips than the cell asks for, it exits
non-zero and prints no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parent.parent
sys.path.insert(0, str(ROOT))


def cell_metrics(bench: dict, cell: str, trace: bool) -> list:
    key = "per_layer" if trace else "end_to_end"
    return [m for m in bench[key]
            if "workloads" not in m or cell in m["workloads"]]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if args.seed < 0:
        ap.error("--seed must be non-negative")

    from chipbench import harness as H
    from chipbench import mix as MIX
    try:
        bench = H.load_benchmark()
        cells = {w["name"]: w for w in bench["workloads"]}
        if args.workload not in cells:
            raise H.RunError(f"unknown workload {args.workload!r}; "
                             f"known: {sorted(cells)}")
        cell = cells[args.workload]
        result = H.run_cell(cell, H.load_config(cell["config"]),
                            MIX.load_mix(cell["traffic"]),
                            cell_metrics(bench, cell["name"], bool(args.trace)),
                            args.seed, args.seconds, bool(args.trace),
                            t_start=T_START)
    except H.RunError as e:
        print(f"[chipbench] FAIL: {e}", file=sys.stderr, flush=True)
        return 1
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
