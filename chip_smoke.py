#!/usr/bin/env python3
"""Chip smoke test: the serving path, end to end, on TPU.

    python chip_smoke.py              # one chip
    python chip_smoke.py --chips 4    # four chips (one host, 2x2)

One chip: qwen1.5-0.5b at its published widths (24 x 1024, vocabulary
151936) in bf16 with seeded random weights goes through the public path,
``repro.plan(...) -> .compile() -> .serve(config=ServeConfig(...))``,
serves 16 requests (prompts from three length buckets, 32 new tokens
each) with 8 slots x 2048 positions, and checks the tokens. The served
prefill logits of one prompt are compared with a plain float32 forward of
the same weights at the highest matmul precision.

Four chips: yi-9b at its published widths (48 x 4096; about 17.7 GB of
bf16 weights, more than one chip holds) is served on the auto-fitted
4-chip mesh, and no device may hold the whole model. The same widths cut
to 4 layers are then served greedily on the 4-chip mesh and on one chip
in this process; their prefill logits must agree within the bf16
tolerance, and whether the greedy streams agree is reported.

Everything runs in this one process (a chip belongs to one process).
Without a TPU the script exits non-zero before printing any result. The
last line of a passing run is exactly
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
Rates printed on the way are smoke output, not benchmark metrics.
"""
from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import pathlib
import sys
import time

# bf16 stores 8 significant bits: one rounding moves a value by at most
# 2^-9 of itself. The served prefill rounds activations to bf16 a handful
# of times per layer (projections, attention, MLP, residual adds) across
# 24 layers, while the reference keeps everything in float32 at the
# highest matmul precision. Independent roundings add up like a random
# walk, about sqrt(24 * 8) * 2^-9 ~ 3% of the largest activation, so the
# largest logit error must stay within 5% of the reference's largest
# |logit|. A broken mask, cache or sharding moves logits by O(100%).
LOGIT_TOL = 0.05

ROOT = pathlib.Path(__file__).resolve().parent
PROMPT_BUCKETS = ((33, 64), (129, 256), (513, 1024))


def log(msg: str) -> None:
    print(f"[chip_smoke] {msg}", flush=True)


def fail(msg: str) -> "None":
    print(f"[chip_smoke] FAIL: {msg}", file=sys.stderr, flush=True)
    sys.exit(1)


class CompileCounter:
    """Counts XLA compilations (persistent-cache hits included) and their
    seconds, through JAX's monitoring events."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax
        self.count, self.seconds, self.cache_hits = 0, 0.0, 0
        jax.monitoring.register_event_duration_secs_listener(self._on_duration)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_duration(self, event, duration, **_):
        if event == self.EVENT:
            self.count += 1
            self.seconds += duration

    def _on_event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def snapshot(self):
        return self.count, self.seconds, self.cache_hits


def make_requests(vocab: int, n: int, new_tokens: int, seed: int,
                  buckets=PROMPT_BUCKETS, slots: int = 8):
    """Seeded prompts; request i draws its length from bucket
    ``pattern[i % slots]`` so every admission wave of ``slots`` requests
    splits over the buckets the same way (a bounded set of compiles)."""
    import numpy as np

    from repro.serving.engine import Request
    rng = np.random.RandomState(seed)
    pattern = [min(j * len(buckets) // slots, len(buckets) - 1)
               for j in range(slots)]
    reqs = []
    for i in range(n):
        lo, hi = buckets[pattern[i % slots]]
        prompt = rng.randint(1, vocab, size=rng.randint(lo, hi + 1))
        reqs.append(Request(rid=i, prompt=prompt.astype(np.int32),
                            max_new_tokens=new_tokens))
    return reqs


def drain(engine, requests):
    from repro.serving.engine import Request
    for r in requests:  # fresh objects: the engine fills in out_tokens
        engine.submit(Request(rid=r.rid, prompt=r.prompt,
                              max_new_tokens=r.max_new_tokens))
    t0 = time.perf_counter()
    steps = engine.run_until_drained()
    return steps, time.perf_counter() - t0


def check_streams(engine, requests, vocab: int) -> dict:
    done = {r.rid: r for r in engine.completed}
    if sorted(done) != sorted(r.rid for r in requests):
        fail(f"completed {sorted(done)}, submitted "
             f"{sorted(r.rid for r in requests)}")
    for r in requests:
        out = done[r.rid].out_tokens
        if len(out) != r.max_new_tokens:
            fail(f"request {r.rid}: {len(out)} tokens, budget "
                 f"{r.max_new_tokens}")
        if not all(0 <= int(t) < vocab for t in out):
            fail(f"request {r.rid}: token outside the vocabulary {vocab}")
    return {rid: list(map(int, r.out_tokens)) for rid, r in done.items()}


def served_prefill_logits(engine, prompt):
    """Last-position logits [V] of ``prompt`` from the engine's own
    bucketed prefill program (the one admission dispatches)."""
    import jax.numpy as jnp
    import numpy as np

    from repro.serving.scheduler import bucket_len
    sched = engine.scheduler
    bucket = bucket_len(len(prompt), engine.max_len,
                        min_bucket=sched.min_bucket)
    toks = np.zeros((1, bucket), np.int32)
    toks[0, :len(prompt)] = prompt
    fn = sched.prefill_factory.get("lm", bucket, 1)
    _, logits = fn(engine.params, jnp.asarray(toks),
                   jnp.asarray([len(prompt)], jnp.int32))
    return np.asarray(logits, np.float32).reshape(-1)


def reference_logits(arch, params, prompt):
    """Plain float32 forward of the same weights, highest precision."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.models import lm as LM
    p32 = jax.tree.map(lambda x: x.astype(jnp.float32), params)

    def fwd(p, toks):
        hidden, _ = LM.forward(arch, p, toks)
        return LM.logits_fn(arch, p, hidden[:, -1:])

    with jax.default_matmul_precision("highest"):
        out = jax.jit(fwd)(p32, jnp.asarray(prompt[None]))
    del p32
    return np.asarray(out, np.float32).reshape(-1)


def logit_error(got, ref, what: str) -> float:
    import numpy as np
    err = float(np.abs(got - ref).max())
    rel = err / float(np.abs(ref).max())
    agree = int(np.argmax(got)) == int(np.argmax(ref))
    log(f"logits {what}: max_abs_err={err!r} max_rel_err={rel!r} "
        f"(tolerance {LOGIT_TOL}) argmax_agrees={agree}")
    if not np.all(np.isfinite(got)):
        fail(f"logits {what}: non-finite values")
    if rel > LOGIT_TOL:
        fail(f"logits {what}: max_rel_err {rel} above {LOGIT_TOL}")
    return rel


def param_bytes_per_device(params) -> dict:
    import jax
    per = {}
    for leaf in jax.tree.leaves(params):
        for shard in leaf.addressable_shards:
            per[shard.device.id] = per.get(shard.device.id, 0) + shard.data.nbytes
    return per


def mem_stat(device, key: str):
    return (device.memory_stats() or {}).get(key)


def describe_arch(arch) -> str:
    return (f"arch={arch.name} layers={arch.num_layers} d_model={arch.d_model} "
            f"heads={arch.num_heads}/{arch.num_kv_heads} head_dim={arch.head_dim} "
            f"d_ff={arch.d_ff} vocab={arch.vocab_size}")


def build_engine(arch, slots: int, max_len: int, seed: int, mesh=None,
                 params=None):
    import jax.numpy as jnp

    import repro
    from repro.configs.base import ShapeConfig
    from repro.serving import ServeConfig
    plan = repro.plan(arch, ShapeConfig("chip_smoke", max_len, slots, "decode"),
                      mesh=mesh)
    log(f"plan: {plan.describe()}")
    exe = plan.compile(dtype=jnp.bfloat16)
    return exe.serve(params, config=ServeConfig(slots=slots, max_len=max_len,
                                                seed=seed))


def one_chip(args, counter) -> None:
    import jax

    import repro
    arch = repro.get_arch("qwen1.5-0.5b")
    log(describe_arch(arch))
    c0 = counter.snapshot()
    t0 = time.perf_counter()
    engine = build_engine(arch, slots=8, max_len=2048, seed=args.seed)
    setup_s = time.perf_counter() - t0
    pbytes = sum(param_bytes_per_device(engine.params).values())
    log(f"params: {arch.param_count()} parameters, {pbytes} bytes (bf16); "
        f"engine set-up {setup_s:.3f}s")
    reqs = make_requests(arch.vocab_size, 16, 32, args.seed)
    log(f"requests: {len(reqs)}, prompt lengths "
        f"{[len(r.prompt) for r in reqs]}, 32 new tokens each")
    steps, wall = drain(engine, reqs)
    c1 = counter.snapshot()
    streams = check_streams(engine, reqs, arch.vocab_size)
    tokens = sum(len(s) for s in streams.values())
    log(f"served {len(streams)} requests, {tokens} tokens, {steps} steps, "
        f"serve wall {wall:.3f}s")
    log(f"compiles during serve: {c1[0] - c0[0]} taking {c1[1] - c0[1]:.3f}s "
        f"(persistent-cache hits {c1[2] - c0[2]})")
    log(f"smoke rate, not a benchmark metric: {tokens / wall:.1f} tokens/s "
        f"over the serve wall (compiles included)")
    prompt = reqs[0].prompt
    got = served_prefill_logits(engine, prompt)
    ref = reference_logits(arch, engine.params, prompt)
    logit_error(got, ref, f"served bf16 vs float32 reference "
                          f"(prompt of {len(prompt)} tokens)")
    dev = jax.devices()[0]
    log(f"peak_bytes_in_use={mem_stat(dev, 'peak_bytes_in_use')}")
    c2 = counter.snapshot()
    log(f"compiles in all: {c2[0]} taking {c2[1]:.3f}s "
        f"(persistent-cache hits {c2[2]})")


def four_chips(args, counter) -> None:
    import jax

    import repro
    devices = jax.devices()
    if len(devices) < 4:
        fail(f"--chips 4 needs 4 devices, found {len(devices)}")
    yi = repro.get_arch("yi-9b")
    log(describe_arch(yi))
    t0 = time.perf_counter()
    engine = build_engine(yi, slots=4, max_len=512, seed=args.seed)
    total = sum(leaf.nbytes for leaf in jax.tree.leaves(engine.params))
    per = param_bytes_per_device(engine.params)
    log(f"yi-9b: {yi.param_count()} parameters, {total} bytes (bf16); "
        f"param bytes per device {per}; set-up {time.perf_counter() - t0:.3f}s")
    for d in engine.mesh.devices.flat:
        log(f"device {d.id}: bytes_in_use={mem_stat(d, 'bytes_in_use')}")
    if len(per) < 4 or max(per.values()) >= total:
        fail(f"a device holds the whole model ({total} bytes): {per}")
    reqs = make_requests(yi.vocab_size, 4, 8, args.seed,
                         buckets=((17, 64),), slots=4)
    steps, wall = drain(engine, reqs)
    streams = check_streams(engine, reqs, yi.vocab_size)
    log(f"yi-9b served {len(streams)} requests, "
        f"{sum(len(s) for s in streams.values())} tokens, {steps} steps, "
        f"wall {wall:.3f}s")
    del engine
    gc.collect()

    cut = dataclasses.replace(yi, name="yi-9b-4layers", num_layers=4)
    log(f"compare: {describe_arch(cut)} on 4 chips vs 1 chip, greedy")
    single = build_engine(cut, slots=4, max_len=512, seed=args.seed,
                          mesh=(("data", 1), ("model", 1)))
    multi = build_engine(cut, slots=4, max_len=512, seed=args.seed,
                         params=single.params)
    if len(multi.mesh.devices.flat) != 4:
        fail(f"the cut model's plan took {multi.mesh.devices.size} devices")
    reqs = make_requests(cut.vocab_size, 4, 16, args.seed,
                         buckets=((17, 64),), slots=4)
    prompt = reqs[0].prompt
    logit_error(served_prefill_logits(multi, prompt),
                served_prefill_logits(single, prompt),
                "4 chips vs 1 chip")
    outs = {}
    for name, eng in (("1chip", single), ("4chips", multi)):
        drain(eng, reqs)
        outs[name] = check_streams(eng, reqs, cut.vocab_size)
    same = sum(outs["1chip"][r.rid] == outs["4chips"][r.rid] for r in reqs)
    first = sum(outs["1chip"][r.rid][0] == outs["4chips"][r.rid][0]
                for r in reqs)
    log(f"greedy streams identical: {same}/{len(reqs)}; first tokens "
        f"identical: {first}/{len(reqs)}")
    for d in devices[:4]:
        log(f"device {d.id}: peak_bytes_in_use="
            f"{mem_stat(d, 'peak_bytes_in_use')}")
    c = counter.snapshot()
    log(f"compiles in all: {c[0]} taking {c[1]:.3f}s (persistent-cache hits "
        f"{c[2]})")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    import jax
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        fail(f"no TPU: JAX found {len(devices)} {dev.platform} device(s); "
             f"this smoke test runs only on the chip")
    log(f"device: platform={dev.platform} kind={dev.device_kind} "
        f"count={len(devices)}")
    sys.path.insert(0, str(ROOT / "src"))
    try:
        import repro  # noqa: F401
    except ImportError as e:
        fail(f"cannot import the repository's package from {ROOT / 'src'}: {e}")
    from repro.launch.compile_cache import enable_compile_cache
    log(f"compile cache: {enable_compile_cache()}")
    counter = CompileCounter()
    t0 = time.perf_counter()
    (four_chips if args.chips == 4 else one_chip)(args, counter)
    log(f"total wall {time.perf_counter() - t0:.3f}s")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
