"""The paper's own results, reproduced through the analytic model.

:mod:`repro.paper.tables` runs Tables 1/3/4 and Figs 3/14/15 in the cycle
domain on the paper's CNNs; :mod:`repro.paper.tpu_study` transplants the
XFER-vs-baseline comparison to a TPU pod in the time domain. Every row is
a closed-form model evaluation, so the numbers are the same on any host.

    PYTHONPATH=src python -m repro.paper
"""
from __future__ import annotations

from typing import List

from repro.paper import tables as T
from repro.paper import tpu_study as X
from repro.paper.tables import Row


def rows() -> List[Row]:
    """Every row, tables then the TPU study, in print order."""
    return (T.table1_uniform_vs_custom() + T.table3_xfer_speedup()
            + T.table4_bottleneck_detection() + T.fig3_pipeline_beat()
            + T.fig14_model_accuracy() + T.fig15_scaling()
            + X.xfer_vs_baseline() + X.pipeline_baseline())


__all__ = ["Row", "rows"]
