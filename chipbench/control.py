#!/usr/bin/env python3
"""Readings for the limit on ``served_gap``, at the cell's own size.

    python3 chipbench/control.py --workload yi9b-l16.chat --seconds 20 \
        --seeds 11 12 13

For each seed, one run of the cell as ``run.py`` makes it (a shorter
window at the cell's own load), then the float32 reference scores the
sampled requests twice: as the program served them (``program_gap``, a
lower reading) and with the control, the same reference with every
linear layer in int8, put in the program's place (``served_gap``, an
upper reading). ``correct`` is the harness's verdict on the control,
which has to read false. One JSON line per seed. The benchmark's own
runs never run the control.
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


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)

    from chipbench import harness as H
    from chipbench import mix as MIX
    bench = H.load_benchmark()
    cell = {w["name"]: w for w in bench["workloads"]}[args.workload]
    t = T_START
    for seed in args.seeds:
        res = H.run_cell(cell, H.load_config(cell["config"]),
                         MIX.load_mix(cell["traffic"]), [], seed,
                         args.seconds, False, t_start=t, control=True)
        c = res["checks"]
        print(json.dumps({"seed": seed, "correct": res["correct"],
                          "program_gap": c.get("program_gap", {}).get("value"),
                          "served_gap": c["served_gap"]["value"],
                          "sampled_tokens": c["sampled_tokens"]["value"],
                          "limit": c["served_gap"]["limit"]}), flush=True)
        t = time.perf_counter()
    return 0


if __name__ == "__main__":
    sys.exit(main())
