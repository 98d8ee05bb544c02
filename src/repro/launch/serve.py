"""Serving launcher: plan → compile → continuous-batching inference.

    PYTHONPATH=src python -m repro.launch.serve --arch qwen1.5-0.5b \
        --requests 12 --slots 4 --max-len 128

The launcher is a thin shell over the three-stage API: the planner picks
the ShardingPlan for a decode cell on the live mesh, ``compile()`` places
params/caches with the plan's NamedShardings, and the returned engine runs
the plan-aware jitted decode step.
"""
from __future__ import annotations

import argparse
import time

import numpy as np

from repro.api import plan
from repro.configs import ARCH_IDS
from repro.configs.base import ShapeConfig
from repro.launch.compile_cache import enable_compile_cache
from repro.serving import ServeConfig
from repro.serving.engine import Request
from repro.serving.sampler import SamplingParams


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=list(ARCH_IDS), default="qwen1.5-0.5b")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--max-len", type=int, default=64)
    ap.add_argument("--new-tokens", type=int, default=8)
    ap.add_argument("--reduced", action="store_true", default=True)
    ap.add_argument("--full", dest="reduced", action="store_false")
    ap.add_argument("--xfer", choices=("on", "off", "auto"), default="auto")
    # on-device sampling knobs (greedy when --temperature is unset)
    ap.add_argument("--temperature", type=float, default=None,
                    help="sample instead of greedy decode (default 1.0 "
                         "when only --top-k is given)")
    ap.add_argument("--top-k", type=int, default=0,
                    help="restrict sampling to the k largest logits")
    ap.add_argument("--lookahead", type=int, default=1,
                    help="dispatch depth (1 = double-buffered, 0 = sync)")
    args = ap.parse_args()

    sampling = None
    if args.temperature is not None or args.top_k:
        sampling = SamplingParams(
            method="top_k" if args.top_k else "temperature",
            temperature=1.0 if args.temperature is None else args.temperature,
            top_k=args.top_k)

    enable_compile_cache()
    shape = ShapeConfig("serve_cli", args.max_len, args.slots, "decode")
    force_xfer = {"on": True, "off": False, "auto": None}[args.xfer]
    xplan = plan(args.arch, shape, reduced=args.reduced, force_xfer=force_xfer)
    print(f"[serve] {xplan.describe()}")
    engine = xplan.compile().serve(config=ServeConfig(
        slots=args.slots, max_len=args.max_len,
        sampling=sampling, lookahead=args.lookahead))

    rng = np.random.RandomState(0)
    arch = xplan.arch
    for i in range(args.requests):
        prompt = rng.randint(1, arch.vocab_size, size=rng.randint(4, 17)).astype(np.int32)
        engine.submit(Request(rid=i, prompt=prompt, max_new_tokens=args.new_tokens))

    t0 = time.time()
    steps = engine.run_until_drained()
    dt = time.time() - t0
    lat = [r.finished_at - r.submitted_at for r in engine.completed]
    stats = engine.step_stats()
    print(f"[serve] {len(engine.completed)}/{args.requests} requests in {steps} steps, "
          f"{dt:.2f}s wall; mean latency {np.mean(lat)*1e3:.1f}ms, "
          f"p99 {np.percentile(lat, 99)*1e3:.1f}ms; "
          f"step p50 {stats['step_p50_ms']:.2f}ms, "
          f"{stats['tokens_per_s']:.0f} tok/s")
    for r in engine.completed[:3]:
        print(f"  rid={r.rid} out={r.out_tokens[:8]}")
    assert len(engine.completed) == args.requests
    return engine


if __name__ == "__main__":
    main()
