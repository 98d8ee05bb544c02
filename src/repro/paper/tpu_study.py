"""The paper's Table 2/3 comparison transplanted to a TPU pod — analytic
time-domain model, baseline (replicate) vs XFER (distribute+exchange) vs
the pipelined multi-device baseline (ISLPED16).
"""
from __future__ import annotations

from typing import List

from repro.configs import SHAPES, get_arch
from repro.core.planner import plan_cell
from repro.paper.tables import Row

MESH = (("data", 16), ("model", 16))


def xfer_vs_baseline() -> List[Row]:
    out = []
    for arch_id, shape_id in (("minitron-8b", "train_4k"),
                              ("phi3-medium-14b", "train_4k"),
                              ("minitron-8b", "decode_32k")):
        arch, shape = get_arch(arch_id), SHAPES[shape_id]
        on = plan_cell(arch, shape, MESH, force_xfer=True)
        off = plan_cell(arch, shape, MESH, force_xfer=False)
        out.append(Row(f"tpu_xfer_{arch_id}_{shape_id}",
                       f"xfer={on.predicted_seconds*1e3:.1f}ms "
                       f"baseline={off.predicted_seconds*1e3:.1f}ms "
                       f"hbm {on.hbm_bytes_per_device/2**30:.1f}GB vs "
                       f"{off.hbm_bytes_per_device/2**30:.1f}GB "
                       f"(XFER trades {off.hbm_bytes_per_device/max(on.hbm_bytes_per_device,1):.1f}x "
                       f"capacity for ICI exchange)",
                       {"xfer_s": on.predicted_seconds,
                        "baseline_s": off.predicted_seconds,
                        "xfer_hbm_bytes": on.hbm_bytes_per_device,
                        "baseline_hbm_bytes": off.hbm_bytes_per_device}))
    return out


def pipeline_baseline() -> List[Row]:
    """ISLPED16-style layer pipelining across 2 pods vs Super-LIP partitioning:
    pipelining preserves throughput but not latency (paper §1/§6)."""
    arch, shape = get_arch("yi-9b"), SHAPES["prefill_32k"]
    # Super-LIP: all chips cooperate on one request
    sl = plan_cell(arch, shape, (("pod", 2),) + MESH).predicted_seconds
    # pipelined: 2 stages of 256; latency = sum of stage latencies (fill),
    # throughput = 1/stage_time
    stage = plan_cell(arch, shape, MESH).predicted_seconds
    pipe_latency = 2 * (stage / 2)  # half the model per stage, two stages
    pipe_throughput = 1 / (stage / 2)
    sl_throughput = 1 / sl
    return [Row("pipeline_vs_superlip",
                f"latency superlip={sl*1e3:.0f}ms pipeline={pipe_latency*1e3:.0f}ms "
                f"thpt superlip={sl_throughput:.2f}req/s pipeline={pipe_throughput:.2f}req/s "
                f"(pipelining matches throughput, loses latency: paper §6)",
                {"superlip_s": sl, "pipeline_s": pipe_latency,
                 "superlip_req_per_s": sl_throughput,
                 "pipeline_req_per_s": pipe_throughput})]
