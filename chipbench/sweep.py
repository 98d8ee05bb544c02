#!/usr/bin/env python3
"""Find the knee of an open-loop cell: the highest arrival rate the system
sustains. One process sets the cell up once and runs one window per rate.

    python3 chipbench/sweep.py --workload yi9b-l16.chat --seed 7 --seconds 30 \
        --rates 3 4 5 6 7 8

A rate is sustained when the queue does not grow over the window: the
requests due in its last third wait no longer for their first token than
twice those due in its first third, and the window ends with no more
requests queued than were due in one second. Prints one JSON line per
rate; the cell's mix file records the knee and the rate chosen from it.
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
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    args = ap.parse_args(argv)

    import numpy as np

    from chipbench import harness as H
    from chipbench import mix as MIX
    from chipbench.stats import pct
    bench = H.load_benchmark()
    cell = {w["name"]: w for w in bench["workloads"]}[args.workload]
    cfg, mix = H.load_config(cell["config"]), MIX.load_mix(cell["traffic"])
    if mix["kind"] != "open_loop":
        raise SystemExit("a sweep needs an open-loop mix")
    H.device_info(cell["chips"], True)
    sys.path.insert(0, str(ROOT / "src"))
    H.enable_compile_cache()
    plan, exe, engine = H.build_engine(cfg, args.seed, cell["chips"])
    H.warm(engine, mix, cfg, MIX.rng_for(args.seed, 6))
    H.log(f"set-up {time.perf_counter() - T_START:.3f}s")
    for k, rate in enumerate(args.rates):
        m = dict(mix, rate_per_s=rate)
        reqs = MIX.requests(m, args.seed + k, args.seconds,
                            cfg["serve"]["slots"], cfg["vocab_size"])
        for r in reqs:
            r.rid += (k + 1) * 1_000_000
        client = H.Client(engine, m, reqs, cfg["serve"]["slots"], False)
        t0 = time.perf_counter()
        t_end = t0 + args.seconds
        client.window(t0, t_end)
        queued = len(engine.queue)
        client.drain(time.perf_counter() + 120)
        obs = H.Obs(cfg=cfg, chips=cell["chips"],
                    slots=cfg["serve"]["slots"], peaks=None, t0=t0,
                    t_end=t_end, setup_s=0.0,
                    tracks=list(client.tracks.values()), steps=client.steps)
        due = obs.due_in_window()
        third = args.seconds / 3

        def ttft(ts):
            return [((t.tokens[0] if t.tokens else t_end) - t.due) * 1e3
                    for t in ts]
        first = ttft([t for t in due if t.due < t0 + third])
        last = ttft([t for t in due if t.due >= t_end - third])
        toks = sum(1 for t in obs.tracks for s in t.tokens if t0 <= s < t_end)
        row = {"rate_per_s": rate, "requests": len(due),
               "output_tokens_per_s": toks / args.seconds,
               "ttft_p50_ms": pct(ttft(due), 50),
               "ttft_p95_ms": pct(ttft(due), 95),
               "ttft_p95_first_third_ms": pct(first, 95),
               "ttft_p95_last_third_ms": pct(last, 95),
               "queued_at_close": queued,
               "itl_p95_ms": pct([(b - a) * 1e3 for t in obs.tracks
                                  for a, b in zip(t.tokens, t.tokens[1:])
                                  if t0 <= a and b < t_end], 95),
               "occupancy": float(np.mean([s.active for s in client.steps
                                           if t0 <= s.t < t_end]))
               / cfg["serve"]["slots"]}
        row["sustained"] = bool(
            row["ttft_p95_last_third_ms"] <= 2 * row["ttft_p95_first_third_ms"]
            and queued <= rate)
        print(json.dumps(row), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
