"""The paper's accurate analytic model (§3 Eqs. 8–14, §4.3 Eqs. 16–21).

Two domains are provided:

* **cycle domain** (`TilePipelineModel.cycles`): the paper's formulation
  verbatim — AXI-stream port counts ⟨Ip, Wp, Op⟩, latencies in clock
  cycles. Used by the paper reproduction, ``repro.paper`` (Tables 1/3/4,
  Figs 3/14/15).
* **time domain** (`TilePipelineModel.seconds`): the TPU v5e adaptation —
  ports become fractions of HBM bandwidth, the MAC array becomes the MXU,
  inter-FPGA links become ICI rings. Used by the planner.

The model's defining property (the paper's Challenge 1): the pipeline is a
**max over concurrent streams**, not a sum of aggregate costs — a design
under both classical roofs can still stall on its slowest stream.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Optional

from repro.core import hw
from repro.core.layer_model import ConvLayer
from repro.core.partition import PartitionFactors


@dataclasses.dataclass(frozen=True)
class Tiling:
    """Paper ②-1 loop tiling ⟨Tm, Tn, Tr, Tc⟩ (BlockSpec block shape)."""

    Tm: int
    Tn: int
    Tr: int
    Tc: int = 1

    def clamp(self, layer: ConvLayer, p: PartitionFactors) -> "Tiling":
        _, R, C, M, N = _device_dims(layer, p)
        return Tiling(
            Tm=max(1, min(self.Tm, M)),
            Tn=max(1, min(self.Tn, N)),
            Tr=max(1, min(self.Tr, R)),
            Tc=max(1, min(self.Tc, C)),
        )


@dataclasses.dataclass(frozen=True)
class Ports:
    """Paper ②-2 ⟨Ip, Wp, Op⟩ — AXI streams (cycle domain) or HBM bandwidth
    fractions (time domain, normalised to sum ≤ 1)."""

    Ip: float = 2
    Wp: float = 2
    Op: float = 2
    b2b: float = 8  # inter-device link width (elements/cycle — cycle domain)

    def normalized(self) -> "Ports":
        s = self.Ip + self.Wp + self.Op
        return Ports(self.Ip / s, self.Wp / s, self.Op / s, self.b2b)


@dataclasses.dataclass(frozen=True)
class LayerLatency:
    """All terms of Eqs. 8–14 for one layer on one device, plus bottleneck."""

    t_comp: float
    t_ifm: float
    t_wei: float
    t_ofm: float
    t_link_w: float  # Eq. 17 weight exchange over links (XFER)
    t_link_i: float  # Eq. 19 IFM exchange over links (XFER)
    t_reduce: float  # Pn>1 partial-sum reduce (TPU extension)
    lat1: float      # Eq. 12/18/21
    lat2: float      # Eq. 13
    total: float     # Eq. 14
    trip_outer: int
    trip_inner: int

    @property
    def bottleneck(self) -> str:
        # Paper Corollary 1, extended with link/reduce terms.
        if self.lat2 > self.trip_inner * self.lat1 + 1e-12:
            return "OFM"
        terms = {
            "compute": self.t_comp,
            "IFM": self.t_ifm,
            "weights": self.t_wei,
            "link": max(self.t_link_w, self.t_link_i),
            "reduce": self.t_reduce,
        }
        return max(terms, key=terms.get)


def _ceil_div(a: int, b: int) -> int:
    return -(-a // max(b, 1))


def _device_dims(layer: ConvLayer, p: PartitionFactors):
    """Per-device ⟨B,R,C,M,N⟩ after partitioning, honouring LM semantics.

    tokens_folded: batch rows fold into R (weights streamed once per token
    block); the weight-shared factors Pb·Pr·Pc jointly divide the tokens.
    pm_on_batch: Pm (TP) shards the batch·heads dim, not output channels.
    """
    if layer.tokens_folded:
        tokens = layer.B * layer.R * layer.C
        wsd = p.Pb * p.Pr * p.Pc
        B = 1
        R = _ceil_div(tokens, wsd)
        C = 1
        M = _ceil_div(layer.M, p.Pm)
        N = _ceil_div(layer.N, p.Pn)
    elif layer.pm_on_batch:
        B = _ceil_div(layer.B, p.Pb * p.Pm)
        R = _ceil_div(layer.R, p.Pr)
        C = _ceil_div(layer.C, p.Pc)
        M = layer.M
        N = _ceil_div(layer.N, p.Pn)
    else:
        B = _ceil_div(layer.B, p.Pb)
        R = _ceil_div(layer.R, p.Pr)
        C = _ceil_div(layer.C, p.Pc)
        M = _ceil_div(layer.M, p.Pm)
        N = _ceil_div(layer.N, p.Pn)
    return B, R, C, M, N


@dataclasses.dataclass
class TilePipelineModel:
    """Evaluate Eqs. 8–14 (+ XFER Eqs. 16–21) for a layer/partition/tiling."""

    hw_spec: hw.HardwareSpec = dataclasses.field(default_factory=lambda: hw.V5E)

    # ---------------- cycle domain (paper-faithful) ----------------
    def cycles(self, layer: ConvLayer, t: Tiling, ports: Ports,
               p: PartitionFactors = PartitionFactors(),
               xfer: bool = False) -> LayerLatency:
        t = t.clamp(layer, p)
        K = layer.K
        # per-device dims after partitioning (paper §4.2)
        B, R, C, M, N = _device_dims(layer, p)

        t_comp = K * K * t.Tr * t.Tc  # Eq. 11 (Tm×Tn MACs/cycle)
        t_ifm = t.Tn * t.Tr * t.Tc / ports.Ip  # Eq. 8
        t_ofm = t.Tm * t.Tr * t.Tc / ports.Op  # Eq. 10
        wsd, isd = p.weight_shared_degree, p.ifm_shared_degree
        if layer.weighted and xfer and wsd > 1:
            t_wei = t.Tm * t.Tn * K * K / (ports.Wp * wsd)      # Eq. 16
            t_link_w = t.Tm * t.Tn * K * K / (ports.b2b * wsd)  # Eq. 17
        else:
            t_wei = (t.Tm * t.Tn * K * K / ports.Wp) if layer.weighted else 0.0  # Eq. 9
            t_link_w = 0.0
        if xfer and isd > 1:
            t_ifm = t_ifm / isd                                  # Eq. 20 (corrected: IFM tile size)
            t_link_i = t.Tn * t.Tr * t.Tc / (ports.b2b * isd)    # Eq. 19 (corrected)
        else:
            t_link_i = 0.0
        t_reduce = 0.0
        if p.Pn > 1:
            # partial-sum exchange per OFM tile over links (TPU extension)
            t_reduce = 2 * t.Tm * t.Tr * t.Tc * (p.Pn - 1) / (ports.b2b * p.Pn)

        return self._assemble(layer, t, B, R, C, M, N, t_comp, t_ifm, t_wei,
                              t_ofm, t_link_w, t_link_i, t_reduce)

    # ---------------- time domain (TPU v5e) ----------------
    def seconds(self, layer: ConvLayer, t: Tiling, ports: Optional[Ports] = None,
                p: PartitionFactors = PartitionFactors(),
                xfer: bool = False, dtype: str = "bfloat16") -> LayerLatency:
        """Same pipeline algebra with physical units.

        Streams share the HBM bus: ports are fractions of `hbm_bandwidth`
        (Eq. 7 analogue: ΣBITs·ports ≤ W  →  Σφ ≤ 1). The MAC array is the
        MXU; link terms use the ICI ring bandwidth of the axis carrying the
        exchange.
        """
        ports = (ports or Ports()).normalized()
        t = t.clamp(layer, p)
        bpe = layer.bytes_per_elem
        K = layer.K
        s = self.hw_spec
        B, R, C, M, N = _device_dims(layer, p)

        flops_tile = 2.0 * K * K * t.Tr * t.Tc * t.Tm * t.Tn
        # MXU efficiency: contraction/output dims below the systolic array
        # size waste lanes (paper Eqs. 1–2 analogue).
        eff = min(t.Tm / s.mxu_dim, 1.0) * min(t.Tn / s.mxu_dim, 1.0)
        eff = max(eff, 1e-3) if (t.Tm < s.mxu_dim or t.Tn < s.mxu_dim) else 1.0
        t_comp = flops_tile / (s.matmul_flops_per_s(dtype) * eff)

        bw = s.hbm_bandwidth
        t_ifm = t.Tn * t.Tr * t.Tc * bpe / (ports.Ip * bw)
        t_ofm = t.Tm * t.Tr * t.Tc * bpe / (ports.Op * bw)
        wsd, isd = p.weight_shared_degree, p.ifm_shared_degree
        ici = s.ici_axis_bandwidth()
        if layer.weighted and xfer and wsd > 1:
            wtile = t.Tm * t.Tn * K * K * bpe
            t_wei = wtile / (ports.Wp * bw * wsd)                       # Eq. 16
            t_link_w = wtile * (wsd - 1) / wsd / ici                    # Eq. 17 (ring)
        else:
            t_wei = (t.Tm * t.Tn * K * K * bpe / (ports.Wp * bw)) if layer.weighted else 0.0
            t_link_w = 0.0
        if xfer and isd > 1:
            itile = t.Tn * t.Tr * t.Tc * bpe
            t_ifm = t_ifm / isd                                          # Eq. 20
            t_link_i = itile * (isd - 1) / isd / ici                     # Eq. 19
        else:
            t_link_i = 0.0
        t_reduce = 0.0
        if p.Pn > 1:
            otile = t.Tm * t.Tr * t.Tc * bpe
            t_reduce = 2.0 * otile * (p.Pn - 1) / p.Pn / ici

        return self._assemble(layer, t, B, R, C, M, N, t_comp, t_ifm, t_wei,
                              t_ofm, t_link_w, t_link_i, t_reduce)

    # ---------------- shared pipeline algebra (Eqs. 12–14) ----------------
    @staticmethod
    def _assemble(layer, t, B, R, C, M, N, t_comp, t_ifm, t_wei, t_ofm,
                  t_link_w, t_link_i, t_reduce) -> LayerLatency:
        trip_inner = _ceil_div(N, t.Tn)                      # loop C
        trip_outer = B * _ceil_div(R, t.Tr) * _ceil_div(C, t.Tc) * _ceil_div(M, t.Tm)
        lat1 = max(t_comp, t_ifm, t_wei, t_link_w, t_link_i)  # Eq. 12/18/21
        lat2 = max(trip_inner * lat1 + t_reduce, t_ofm)       # Eq. 13
        total = trip_outer * lat2 + (t_ofm + lat1)             # Eq. 14
        return LayerLatency(
            t_comp=t_comp, t_ifm=t_ifm, t_wei=t_wei, t_ofm=t_ofm,
            t_link_w=t_link_w, t_link_i=t_link_i, t_reduce=t_reduce,
            lat1=lat1, lat2=lat2, total=total,
            trip_outer=trip_outer, trip_inner=trip_inner,
        )

    # ---------------- resource constraints (paper Eqs. 1–7) ----------------
    def vmem_ok(self, layer: ConvLayer, t: Tiling, bpe: int = 2) -> bool:
        """Eqs. 3–6: double-buffered IFM/OFM/WEI tiles must fit on-chip."""
        k = layer.K
        need = 2 * bpe * (t.Tn * t.Tr * t.Tc + t.Tm * t.Tr * t.Tc + t.Tm * t.Tn * k * k)
        return need <= self.hw_spec.vmem_bytes

    def bram_usage(self, layer: ConvLayer, t: Tiling, bits: int = 16) -> int:
        """Paper Eqs. 3–5 (18Kb BRAM blocks) for parity benchmarks.

        Empirical note (validated against paper Table 4): the paper's Eq. 5
        carries a ×2 double-buffer factor, but its *reported* 16-bit designs
        only match with single-buffered weights (design C ⟨64,20⟩: 40+128+
        64·20·1 = 1448 = their figure exactly), while 32-bit designs match
        with the ×2 (design A ⟨8,32⟩: 64+16+2·8·32 = 592 = their figure).
        We reproduce the reported accounting.
        """
        k = layer.K
        wfac = 2 if bits == 32 else 1
        b_i = 2 * t.Tn * math.ceil(t.Tr * t.Tc * bits / 18432)
        b_o = 2 * t.Tm * math.ceil(t.Tr * t.Tc * bits / 18432)
        b_w = wfac * t.Tm * t.Tn * math.ceil(k * k * bits / 18432)
        return b_i + b_o + b_w

    def dsp_usage(self, t: Tiling, bits: int = 16) -> int:
        """Paper Eqs. 1–2: one MAC = 1 DSP (16b fixed) or 5 DSPs (32b float)."""
        per_mac = 1 if bits == 16 else 5
        return per_mac * t.Tm * t.Tn
