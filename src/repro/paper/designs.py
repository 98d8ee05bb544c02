"""Cycle-domain design search for the paper's tables.

The paper's testbed is a ZCU102 (XCZU9EG): 2520 DSP48, 1824 BRAM18K.
Cycle-domain searches replicate the paper's design constraints (Eqs. 1-7)
and port presets (§5A: ⟨2,2,2⟩ @100MHz for 32b float, ⟨4,8,4⟩ @200MHz for
16b fixed). The CNN descriptor sets (VGG16/YOLOv1/SqueezeNet) for
Fig. 15 live here too.
"""
from __future__ import annotations

import itertools
from typing import Iterable, List, Optional, Tuple

from repro.core.layer_model import ConvLayer
from repro.core.partition import PartitionFactors, enumerate_partitions
from repro.core.perf_model import Ports, TilePipelineModel, Tiling

ZCU102_DSP = 2520
ZCU102_BRAM18 = 1824
FREQ = {32: 100e6, 16: 200e6}
PORTS = {32: Ports(2, 2, 2, b2b=2), 16: Ports(4, 8, 4, b2b=8)}

MODEL = TilePipelineModel()


def _tiling_candidates(layer: ConvLayer, p: PartitionFactors,
                       bits: int) -> Iterable[Tiling]:
    from repro.core.perf_model import _device_dims
    _, R, C, M, N = _device_dims(layer, p)
    tms = sorted({min(t, M) for t in (8, 16, 32, 48, 64, 96, 128)})
    tns = sorted({min(t, N) for t in (3, 7, 10, 16, 20, 24, 26, 32, 48)})
    trs = sorted({min(t, R) for t in (1, 7, 13, 14, 26, 27, 55, R)})
    tcs = sorted({min(t, C) for t in (1, 7, 13, 14, 26, 27, 55, C)})
    for tm, tn, tr, tc in itertools.product(tms, tns, trs, tcs):
        yield Tiling(tm, tn, tr, tc)


def feasible(layer: ConvLayer, t: Tiling, bits: int) -> bool:
    if MODEL.dsp_usage(t, bits) > ZCU102_DSP:
        return False
    if MODEL.bram_usage(layer, t, bits) > ZCU102_BRAM18 * 1.02:
        return False  # 2% slack: the paper itself reports 92-103% figures
    return True


def best_design_cycles(layer: ConvLayer, bits: int,
                       p: PartitionFactors = PartitionFactors(),
                       xfer: bool = False,
                       tiling: Optional[Tiling] = None) -> Tuple[float, Tiling]:
    """Paper Eq. 15 for one layer in the cycle domain (ZCU102 constraints)."""
    ports = PORTS[bits]
    best = (float("inf"), None)
    cands = [tiling] if tiling is not None else _tiling_candidates(layer, p, bits)
    for t in cands:
        tc = t.clamp(layer, p)
        if not feasible(layer, tc, bits):
            continue
        lat = MODEL.cycles(layer, tc, ports, p, xfer=xfer)
        if lat.total < best[0]:
            best = (lat.total, tc)
    if best[1] is None:  # smallest fallback
        tc = Tiling(8, 3, 1, 1).clamp(layer, p)
        best = (MODEL.cycles(layer, tc, ports, p, xfer=xfer).total, tc)
    return best


def net_cycles(layers: List[ConvLayer], bits: int,
               p: PartitionFactors = PartitionFactors(), xfer: bool = False,
               tiling: Optional[Tiling] = None) -> float:
    return sum(best_design_cycles(l, bits, p, xfer, tiling)[0] * l.count
               for l in layers)


def best_partition(layers: List[ConvLayer], num_devices: int, bits: int,
                   xfer: bool = True,
                   tiling: Optional[Tiling] = None
                   ) -> Tuple[float, PartitionFactors]:
    """Uniform partition factors across layers (paper §4.5)."""
    best = (float("inf"), PartitionFactors())
    for p in enumerate_partitions(num_devices, B=max(l.B for l in layers),
                                  R=max(l.R for l in layers),
                                  C=max(l.C for l in layers),
                                  M=max(l.M for l in layers),
                                  N=max(l.N for l in layers),
                                  allow_pn=False):
        total = net_cycles(layers, bits, p, xfer, tiling)
        if total < best[0]:
            best = (total, p)
    return best


# ---------------------------------------------------------------------------
# Public CNN descriptor sets for the paper's Fig. 15 (besides AlexNet).
# Spatial dims follow the published architectures.
# ---------------------------------------------------------------------------

def vgg16_layers(batch: int = 1) -> List[ConvLayer]:
    cfg = [(64, 3, 224), (64, 64, 224), (128, 64, 112), (128, 128, 112),
           (256, 128, 56), (256, 256, 56), (256, 256, 56),
           (512, 256, 28), (512, 512, 28), (512, 512, 28),
           (512, 512, 14), (512, 512, 14), (512, 512, 14)]
    return [ConvLayer(f"conv{i}", batch, m, n, r, r, 3)
            for i, (m, n, r) in enumerate(cfg, 1)]


def yolov1_layers(batch: int = 1) -> List[ConvLayer]:
    cfg = [(64, 3, 224, 7), (192, 64, 56, 3), (128, 192, 28, 1),
           (256, 128, 28, 3), (256, 256, 28, 1), (512, 256, 28, 3),
           (256, 512, 14, 1), (512, 256, 14, 3), (256, 512, 14, 1),
           (512, 256, 14, 3), (256, 512, 14, 1), (512, 256, 14, 3),
           (256, 512, 14, 1), (512, 256, 14, 3), (512, 512, 14, 1),
           (1024, 512, 14, 3), (512, 1024, 7, 1), (1024, 512, 7, 3),
           (512, 1024, 7, 1), (1024, 512, 7, 3), (1024, 1024, 7, 3),
           (1024, 1024, 7, 3), (1024, 1024, 7, 3), (1024, 1024, 7, 3)]
    return [ConvLayer(f"conv{i}", batch, m, n, r, r, k)
            for i, (m, n, r, k) in enumerate(cfg, 1)]


def squeezenet_layers(batch: int = 1) -> List[ConvLayer]:
    out: List[ConvLayer] = [ConvLayer("conv1", batch, 96, 3, 111, 111, 7)]
    fires = [  # (squeeze, expand, in_ch, spatial)
        (16, 64, 96, 55), (16, 64, 128, 55), (32, 128, 128, 55),
        (32, 128, 256, 27), (48, 192, 256, 27), (48, 192, 384, 27),
        (64, 256, 384, 27), (64, 256, 512, 13)]
    for i, (s, e, cin, sp) in enumerate(fires, 2):
        out.append(ConvLayer(f"fire{i}.squeeze", batch, s, cin, sp, sp, 1))
        out.append(ConvLayer(f"fire{i}.e1", batch, e, s, sp, sp, 1))
        out.append(ConvLayer(f"fire{i}.e3", batch, e, s, sp, sp, 3))
    out.append(ConvLayer("conv10", batch, 1000, 512, 13, 13, 1))
    return out
