from repro.testing.mesh_fixtures import force_host_device_count

force_host_device_count(512)
# ^ MUST precede the first XLA backend creation (the device count locks
# then — merely importing jax, as the repro import chain above does, is
# fine as long as nothing touches jax.devices() at module scope). Appends
# to (never overwrites) user-set XLA_FLAGS, pins the CPU platform (the
# simulated devices live on the host; the chip stays free), and no-ops
# with a warning when a backend already exists in this process. This is
# dry-run-only; tests/benches see the real device count.

import argparse  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import subprocess  # noqa: E402
import sys  # noqa: E402
import time  # noqa: E402
import traceback  # noqa: E402

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.sharding import NamedSharding, PartitionSpec as P  # noqa: E402

from repro.configs import ARCH_IDS, SHAPES, cell_is_runnable, get_arch, get_shape  # noqa: E402
from repro.core.planner import plan_cell  # noqa: E402
from repro.core.xfer import ShardingCtx, tree_shardings  # noqa: E402
from repro.launch.collectives import parse_collectives  # noqa: E402
from repro.launch.hlo_analysis import analyze  # noqa: E402
from repro.launch.mesh import make_production_mesh, mesh_axes  # noqa: E402
from repro.models import registry as REG  # noqa: E402
from repro.optim import adamw as OPT  # noqa: E402

OUT_DEFAULT = pathlib.Path(__file__).resolve().parents[3] / "experiments" / "dryrun"


def _mem_dict(mem) -> dict:
    if mem is None:
        return {}
    keys = ("generated_code_size_in_bytes", "argument_size_in_bytes",
            "output_size_in_bytes", "temp_size_in_bytes", "alias_size_in_bytes",
            "host_generated_code_size_in_bytes", "host_argument_size_in_bytes",
            "host_output_size_in_bytes", "host_temp_size_in_bytes",
            "peak_memory_in_bytes")
    out = {}
    for k in keys:
        v = getattr(mem, k, None)
        if v is not None:
            out[k] = int(v)
    return out


def lower_cell(arch_id: str, shape_id: str, multi_pod: bool,
               force_xfer=None, pp: bool = False):
    """Build plan + shardings, lower and compile one (arch × shape × mesh)."""
    arch = get_arch(arch_id)
    shape = get_shape(shape_id)
    mesh = make_production_mesh(multi_pod=multi_pod)
    axes = mesh_axes(mesh)

    rep = plan_cell(arch, shape, axes, force_xfer=force_xfer)
    plan = rep.plan
    ctx = ShardingCtx(mesh, plan)
    dtype = jnp.bfloat16
    quantize = "int8" in rep.note

    params_sds = jax.eval_shape(lambda k: REG.init_params(arch, k, dtype),
                                jax.random.PRNGKey(0))
    p_dims = REG.param_dims(arch)
    p_sh = tree_shardings(ctx, params_sds, p_dims)
    batch_sds = REG.input_specs(arch, shape, dtype)
    b_sh = tree_shardings(ctx, batch_sds, REG.input_dims(arch, shape))
    scalar_sh = NamedSharding(mesh, P())

    with mesh:
        if shape.kind == "train":
            cfg = OPT.AdamWConfig(quantize=quantize)
            opt_sds = jax.eval_shape(lambda p: OPT.adamw_init(p, cfg), params_sds)
            o_sh = tree_shardings(ctx, opt_sds, OPT.opt_state_dims(p_dims, quantize))
            fn = REG.build_train_step(arch, cfg, ctx)
            m_sh = {"loss": scalar_sh, "lr": scalar_sh, "grad_norm": scalar_sh,
                    "clip_scale": scalar_sh}
            jfn = jax.jit(fn, in_shardings=(p_sh, o_sh, b_sh),
                          out_shardings=(p_sh, o_sh, m_sh),
                          donate_argnums=(0, 1))
            lowered = jfn.lower(params_sds, opt_sds, batch_sds)
        elif shape.kind == "prefill":
            fn = REG.build_prefill_step(arch, shape, ctx, cache_dtype=dtype)
            jfn = jax.jit(fn, in_shardings=(p_sh, b_sh))
            lowered = jfn.lower(params_sds, batch_sds)
        else:  # decode
            caches_sds = jax.eval_shape(
                lambda: REG.make_caches(arch, shape.global_batch, shape.seq_len, dtype))
            c_sh = tree_shardings(ctx, caches_sds, REG.cache_dims(arch))
            tok_sh = NamedSharding(mesh, ctx.spec((shape.global_batch,), ("batch",)))
            fn = REG.build_serve_step(arch, ctx)
            jfn = jax.jit(fn, in_shardings=(p_sh, c_sh, b_sh),
                          out_shardings=(tok_sh, c_sh), donate_argnums=(1,))
            lowered = jfn.lower(params_sds, caches_sds, batch_sds)
    return rep, mesh, lowered


def run_cell(arch_id: str, shape_id: str, multi_pod: bool, outdir: pathlib.Path,
             force_xfer=None, tag: str = "") -> dict:
    mesh_name = "pod2x16x16" if multi_pod else "pod16x16"
    cellname = f"{arch_id}__{shape_id}{('__' + tag) if tag else ''}"
    outpath = outdir / mesh_name / f"{cellname}.json"
    outpath.parent.mkdir(parents=True, exist_ok=True)

    arch = get_arch(arch_id)
    shape = get_shape(shape_id)
    runnable, why = cell_is_runnable(arch, shape)
    rec = {"arch": arch_id, "shape": shape_id, "mesh": mesh_name, "tag": tag}
    if not runnable:
        rec.update({"skipped": why})
        outpath.write_text(json.dumps(rec, indent=1))
        print(f"[dryrun] SKIP {cellname}: {why}")
        return rec

    t0 = time.time()
    rep, mesh, lowered = lower_cell(arch_id, shape_id, multi_pod, force_xfer)
    t_lower = time.time() - t0
    t0 = time.time()
    compiled = lowered.compile()
    t_compile = time.time() - t0

    mem = _mem_dict(compiled.memory_analysis())
    cost = dict(compiled.cost_analysis() or {})
    hlo_text = compiled.as_text()
    coll = parse_collectives(hlo_text)
    t0 = time.time()
    deep = analyze(hlo_text)  # trip-count-aware FLOPs / bytes / collectives
    t_analyze = time.time() - t0
    ndev = mesh.devices.size
    rec.update({
        "plan": rep.plan.describe(),
        "plan_note": rep.note,
        "predicted_seconds": rep.predicted_seconds,
        "plan_hbm_bytes": rep.hbm_bytes_per_device,
        "num_devices": int(ndev),
        "lower_s": round(t_lower, 1),
        "compile_s": round(t_compile, 1),
        "analyze_s": round(t_analyze, 1),
        # raw XLA numbers (while bodies counted once — kept for reference)
        "xla_flops_per_device": cost.get("flops", 0.0),
        "xla_bytes_accessed_per_device": cost.get("bytes accessed", 0.0),
        # trip-count-aware per-device numbers (launch/hlo_analysis.py)
        "flops_per_device": deep.flops,
        "hbm_bytes_per_device": deep.hbm_bytes,
        "collective_wire_bytes_per_device": deep.collective_wire_bytes,
        "collectives_by_type": {k: dict(v) for k, v in deep.coll.items()},
        "memory_analysis": mem,
        "collectives_raw": coll,
    })
    outpath.write_text(json.dumps(rec, indent=1))
    print(f"[dryrun] OK {mesh_name}/{cellname}: plan=[{rep.plan.describe()}] "
          f"lower={t_lower:.0f}s compile={t_compile:.0f}s "
          f"flops/dev={deep.flops:.3e} hbm/dev={deep.hbm_bytes:.3e} "
          f"wire/dev={deep.collective_wire_bytes:.3e}")
    print(f"[dryrun] memory_analysis: {mem}")
    return rec


def run_all(multi_pod: bool, outdir: pathlib.Path, timeout: int = 3000,
            skip_existing: bool = True, force_xfer=None, tag: str = ""):
    mesh_name = "pod2x16x16" if multi_pod else "pod16x16"
    failures = []
    for arch_id in ARCH_IDS:
        for shape_id in SHAPES:
            cellname = f"{arch_id}__{shape_id}{('__' + tag) if tag else ''}"
            outpath = outdir / mesh_name / f"{cellname}.json"
            if skip_existing and outpath.exists():
                data = json.loads(outpath.read_text())
                if "error" not in data:
                    print(f"[dryrun] cached {mesh_name}/{cellname}")
                    continue
            cmd = [sys.executable, "-m", "repro.launch.dryrun",
                   "--arch", arch_id, "--shape", shape_id, "--out", str(outdir)]
            if multi_pod:
                cmd.append("--multi-pod")
            if force_xfer is not None:
                cmd += ["--xfer", "on" if force_xfer else "off"]
            if tag:
                cmd += ["--tag", tag]
            t0 = time.time()
            try:
                r = subprocess.run(cmd, timeout=timeout, capture_output=True, text=True)
                sys.stdout.write(r.stdout)
                if r.returncode != 0:
                    err = r.stderr.strip().splitlines()[-15:]
                    outpath.parent.mkdir(parents=True, exist_ok=True)
                    outpath.write_text(json.dumps(
                        {"arch": arch_id, "shape": shape_id, "mesh": mesh_name,
                         "tag": tag, "error": "\n".join(err)}, indent=1))
                    failures.append(cellname)
                    print(f"[dryrun] FAIL {cellname} rc={r.returncode}: {err[-1] if err else '?'}")
            except subprocess.TimeoutExpired:
                outpath.write_text(json.dumps(
                    {"arch": arch_id, "shape": shape_id, "mesh": mesh_name,
                     "tag": tag, "error": f"timeout {timeout}s"}, indent=1))
                failures.append(cellname)
                print(f"[dryrun] TIMEOUT {cellname} after {time.time()-t0:.0f}s")
    print(f"[dryrun] done mesh={mesh_name}; {len(failures)} failures: {failures}")
    return failures


def main():
    ap = argparse.ArgumentParser(description="Multi-pod dry-run (lower+compile)")
    ap.add_argument("--arch", choices=list(ARCH_IDS))
    ap.add_argument("--shape", choices=list(SHAPES))
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--xfer", choices=("on", "off", "auto"), default="auto")
    ap.add_argument("--tag", default="", help="suffix for output filenames")
    ap.add_argument("--out", default=str(OUT_DEFAULT))
    ap.add_argument("--timeout", type=int, default=3000)
    args = ap.parse_args()
    outdir = pathlib.Path(args.out)
    force_xfer = {"on": True, "off": False, "auto": None}[args.xfer]
    if args.all:
        run_all(args.multi_pod, outdir, timeout=args.timeout,
                force_xfer=force_xfer, tag=args.tag)
        return
    assert args.arch and args.shape, "--arch/--shape or --all required"
    try:
        run_cell(args.arch, args.shape, args.multi_pod, outdir,
                 force_xfer=force_xfer, tag=args.tag)
    except Exception:
        traceback.print_exc()
        sys.exit(1)


if __name__ == "__main__":
    main()
