"""The paper's tables and figures — one function per Super-LIP result.

All run the paper's own vehicle (AlexNet et al.) through the *cycle-domain*
analytic model (Eqs. 8–22 verbatim, ZCU102 resource constraints), so the
paper's headline numbers are reproducible on any host. Each function
returns :class:`Row` s: the row's numbers beside its formatted text.

  Table 1 — layer-specific vs uniform cross-layer designs
  Table 3 — 1-FPGA baseline vs 2-FPGA Super-LIP (32b and 16b)
  Table 4 — bottleneck detection + XFER alleviation (designs A-D)
  Fig. 3  — XFER pipeline beat improvement
  Fig. 14 — our model vs the FPGA'15 roofline model (deviation structure)
  Fig. 15 — scaling 1→16 devices, four CNNs, super-linear check
"""
from __future__ import annotations

from typing import List, NamedTuple

from repro.core.bottleneck import diagnose
from repro.core.layer_model import alexnet_layers
from repro.core.partition import PartitionFactors, enumerate_partitions
from repro.core.perf_model import Ports, TilePipelineModel, Tiling
from repro.paper import designs as C

MODEL = TilePipelineModel()


class Row(NamedTuple):
    """One result: its name, its text, and the numbers the text states."""

    name: str
    derived: str
    values: dict


def table1_uniform_vs_custom() -> List[Row]:
    """Paper Table 1: per-layer-customised designs vs one uniform design."""
    layers = alexnet_layers(batch=4)  # the table uses partitions of 4
    custom_total = 0.0
    for l in layers:
        custom_total += min(
            C.best_design_cycles(l, 16, p, xfer=True)[0]
            for p in (PartitionFactors(Pb=4), PartitionFactors(Pr=2, Pb=2),
                      PartitionFactors(Pm=2, Pb=2), PartitionFactors(Pm=4),
                      PartitionFactors(Pr=4)))
    uni_cyc, _ = C.best_partition(layers, 4, 16, xfer=True)
    rel = uni_cyc / max(custom_total, 1)
    # paper: uniform within 5% of layer-customised (and avoids reconfig)
    return [Row("table1_uniform_vs_custom",
                f"uniform/custom={rel:.3f} (paper: ~1.04) custom={custom_total:.0f}cyc "
                f"uniform={uni_cyc:.0f}cyc",
                {"ratio": rel, "custom_cycles": custom_total,
                 "uniform_cycles": uni_cyc})]


def table3_xfer_speedup() -> List[Row]:
    """Paper Table 3: Super-LIP 2 devices vs single device; paper reports
    2.25x (32b float, ⟨64,7⟩) and 3.48x (16b fixed).

    Two port settings per precision: the paper's idealized §5A ports, and a
    measured-DDR setting (half effective write-side bandwidth) matching the
    paper's own observation (Fig. 2) that real memory systems run below the
    idealized model — super-linearity lives in that memory-bound regime.
    """
    layers = alexnet_layers(batch=1)
    out = []
    for bits, tile, ports, label, paper in (
            (32, Tiling(64, 7, 13, 13), C.PORTS[32], "idealized", 2.25),
            (16, Tiling(128, 10, 13, 13), C.PORTS[16], "idealized", 3.48),
            (16, Tiling(128, 10, 13, 13), Ports(4, 4, 4, b2b=8), "measured-ddr", 3.48)):
        single = sum(C.MODEL.cycles(l, tile.clamp(l, PartitionFactors()), ports).total
                     for l in layers)
        best2 = float("inf")
        bestp = None
        for p in enumerate_partitions(2, 1, 55, 55, 384, 256, allow_pn=False):
            tot = sum(C.MODEL.cycles(l, tile.clamp(l, p), ports, p, xfer=True).total
                      for l in layers)
            if tot < best2:
                best2, bestp = tot, p
        speed = single / best2
        ms_single = single / C.FREQ[bits] * 1e3
        ms_dual = best2 / C.FREQ[bits] * 1e3
        out.append(Row(f"table3_xfer_speedup_{bits}b_{label}",
                       f"speedup={speed:.2f}x (paper {paper}x) "
                       f"lat {ms_single:.2f}ms->{ms_dual:.2f}ms "
                       f"superlinear={'yes' if speed > 2 else 'no'} "
                       f"partition={bestp.as_dict()}",
                       {"speedup": speed, "single_ms": ms_single,
                        "dual_ms": ms_dual}))
    return out


def table4_bottleneck_detection() -> List[Row]:
    """Paper Table 4: detect the bound (IFM/weights), apply XFER, measure
    the alleviation (paper: 3.3x / 3.43x for designs A->B, C->D)."""
    out = []
    cases = [
        ("A->B", 32, Tiling(8, 32, 13, 13), PartitionFactors(Pm=2), 3.30),
        ("C->D", 16, Tiling(64, 20, 13, 13), PartitionFactors(Pr=2), 3.43),
    ]
    layers = alexnet_layers(batch=1)
    for name, bits, tile, part, paper in cases:
        ports = C.PORTS[bits]
        l5 = layers[4]
        single = MODEL.cycles(l5, tile.clamp(l5, PartitionFactors()), ports)
        diag = diagnose(l5, tile, ports, domain="cycles")
        dual = MODEL.cycles(l5, tile.clamp(l5, part), ports, part, xfer=True)
        diag2 = diagnose(l5, tile, ports, part, xfer=True, domain="cycles")
        speed = single.total / dual.total
        out.append(Row(f"table4_{name}",
                       f"bound={diag.bottleneck}->{diag2.bottleneck} "
                       f"speedup={speed:.2f}x (paper {paper}x) "
                       f"superlinear={'yes' if speed > part.total else 'no'}",
                       {"bound": diag.bottleneck, "bound_xfer": diag2.bottleneck,
                        "speedup": speed}))
    return out


def fig3_pipeline_beat() -> List[Row]:
    """Paper Fig. 3: XFER reduces the pipeline beat Lat2 (2953→1782 cycles,
    39.65%). We reproduce the *mechanism*: same layer/tile, XFER on/off."""
    l2 = alexnet_layers(batch=1)[1]
    tile = Tiling(64, 24, 7, 14)  # weights-bound design (the Fig. 3 regime)
    ports = Ports(2, 2, 2, b2b=2)
    p = PartitionFactors(Pb=1, Pr=2)
    base = MODEL.cycles(l2, tile.clamp(l2, p), ports, p, xfer=False)
    xf = MODEL.cycles(l2, tile.clamp(l2, p), ports, p, xfer=True)
    impr = 1 - xf.lat2 / base.lat2
    return [Row("fig3_beat_improvement",
                f"lat2 {base.lat2:.0f}->{xf.lat2:.0f}cyc improv={impr*100:.1f}% "
                f"(paper 39.65%)",
                {"lat2": base.lat2, "lat2_xfer": xf.lat2})]


def fig14_model_accuracy() -> List[Row]:
    """Paper Fig. 14: the FPGA'15 roofline model (sum/uninterrupted-BW view)
    under-predicts latency for communication-bound designs; our pipeline-of-
    maxes model does not. Compares both models' predictions per design;
    paper's measured deviations: ours 2.53% avg, FPGA'15 up to 45.47%."""
    l5 = alexnet_layers(batch=1)[4]
    ports = Ports(2, 2, 2, b2b=2)
    out = []
    for tm, tn in ((12, 16), (10, 22), (8, 32)):
        tile = Tiling(tm, tn, 13, 13)
        ours = MODEL.cycles(l5, tile.clamp(l5, PartitionFactors()), ports)
        # FPGA'15-style estimate: compute and every memory stream fully
        # overlap at peak bandwidth (no pipeline beats)
        trips = ours.trip_outer * ours.trip_inner
        comp = ours.t_comp * trips
        mem = (ours.t_ifm + ours.t_wei) * trips + ours.t_ofm * ours.trip_outer
        fpga15 = max(comp, mem)
        dev = (ours.total - fpga15) / ours.total * 100
        bound = diagnose(l5, tile, ports, domain="cycles").bottleneck
        out.append(Row(f"fig14_design_{tm}x{tn}",
                       f"ours={ours.total:.0f}cyc fpga15={fpga15:.0f}cyc "
                       f"fpga15_underpredicts_by={dev:.1f}% bound={bound}",
                       {"ours_cycles": ours.total, "fpga15_cycles": fpga15,
                        "bound": bound}))
    return out


def fig15_scaling() -> List[Row]:
    """Paper Fig. 15: 1→16 devices for AlexNet/SqueezeNet/VGG/YOLO (16b).
    Paper: consistent super-linear for AlexNet/VGG/YOLO; SqueezeNet loses
    super-linearity (compute-bound 1x1 kernels); AlexNet 126.6ms→4.53ms =
    27.93x for YOLO at 16."""
    nets = {
        "alexnet": (alexnet_layers(1), Tiling(128, 10, 13, 13)),
        "squeezenet": (C.squeezenet_layers(1), Tiling(64, 16, 13, 13)),
        "vgg": (C.vgg16_layers(1), Tiling(64, 26, 14, 14)),
        "yolo": (C.yolov1_layers(1), Tiling(64, 25, 14, 14)),
    }
    out = []
    for name, (layers, tile) in nets.items():
        base = C.net_cycles(layers, 16, tiling=tile)
        curve = []
        for n in (2, 4, 8, 16):
            cyc, _ = C.best_partition(layers, n, 16, xfer=True, tiling=tile)
            curve.append((n, base / cyc))
        pts = " ".join(f"{n}:{s:.2f}x" for n, s in curve)
        superlin = all(s > n for n, s in curve[:2])
        out.append(Row(f"fig15_{name}",
                       f"{pts} superlinear@2-4={'yes' if superlin else 'no'} "
                       f"lat1={base/C.FREQ[16]*1e3:.2f}ms "
                       f"lat16={base/curve[-1][1]/C.FREQ[16]*1e3:.2f}ms",
                       {"speedups": dict(curve)}))
    return out
