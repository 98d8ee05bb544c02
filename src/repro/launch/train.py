"""Training launcher: plan → compile → fault-tolerant loop.

    PYTHONPATH=src python -m repro.launch.train --arch qwen1.5-0.5b \
        --steps 50 --batch 8 --seq 256 --ckpt /tmp/ckpt [--xfer on|off]

On this CPU container it runs reduced configs end-to-end; on a pod the
same entrypoint runs the full config (the mesh comes from jax.devices()).
The whole flow is the three-stage API: the chosen ShardingPlan drives the
NamedShardings the params/optimizer are placed with and the jitted step.
"""
from __future__ import annotations

import argparse
import time

import numpy as np

from repro.api import plan
from repro.configs import ARCH_IDS
from repro.configs.base import ShapeConfig
from repro.launch.compile_cache import enable_compile_cache
from repro.optim import adamw as OPT


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=list(ARCH_IDS), default="qwen1.5-0.5b")
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--reduced", action="store_true", default=True)
    ap.add_argument("--full", dest="reduced", action="store_false")
    ap.add_argument("--ckpt", default="/tmp/repro_ckpt")
    ap.add_argument("--ckpt-every", type=int, default=10)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--xfer", choices=("on", "off", "auto"), default="auto")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    enable_compile_cache()
    shape = ShapeConfig("train_cli", args.seq, args.batch, "train")
    force_xfer = {"on": True, "off": False, "auto": None}[args.xfer]
    xplan = plan(args.arch, shape, reduced=args.reduced, force_xfer=force_xfer)
    print(f"[train] {xplan.describe()}")

    driver = xplan.compile().train(
        steps=args.steps, ckpt_dir=args.ckpt, ckpt_every=args.ckpt_every,
        opt_cfg=OPT.AdamWConfig(lr=args.lr), seed=args.seed)
    t0 = time.time()
    result = driver.run()
    dt = time.time() - t0
    losses = [m["loss"] for m in result["log"]]
    print(f"[train] {len(losses)} steps in {dt:.1f}s "
          f"({dt/max(len(losses),1)*1e3:.1f} ms/step) "
          f"loss {losses[0]:.4f} -> {losses[-1]:.4f} "
          f"restarts={result['restarts']} stragglers={result['straggler_events']}")
    assert np.isfinite(losses[-1])
    return result


if __name__ == "__main__":
    main()
