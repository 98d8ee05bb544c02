"""The paper's tables and the TPU study (``repro.paper``): each row holds
the property its own text states, read from the numbers it returns."""
import contextlib
import io
import runpy

import pytest

from repro.paper import rows


@pytest.fixture(scope="module")
def paper_rows():
    return {row.name: row for row in rows()}


def _table1(v):
    # a uniform design stays within 5% of per-layer customised designs
    assert v["ratio"] <= 1.05
    assert v["uniform_cycles"] >= v["custom_cycles"] > 0


def _table3(v):
    # two devices answer faster than one
    assert v["dual_ms"] < v["single_ms"]
    assert v["speedup"] > 1.5


def _table4(bound):
    def check(v):
        # the detected bound is named, and XFER relieves it
        assert (v["bound"], v["bound_xfer"]) == bound
        assert v["speedup"] > 1
    return check


def _fig3(v):
    # XFER shortens the pipeline beat
    assert v["lat2_xfer"] < v["lat2"]


def _fig14(bound):
    def check(v):
        # the pipeline-of-maxes model predicts fewer cycles than the
        # FPGA'15 roofline, and names the stream that bounds the design
        assert v["ours_cycles"] < v["fpga15_cycles"]
        assert v["bound"] == bound
    return check


def _fig15(v):
    # speedup rises strictly with the device count
    s = v["speedups"]
    assert sorted(s) == [2, 4, 8, 16]
    assert s[2] < s[4] < s[8] < s[16]


def _tpu_xfer(v):
    # XFER trades HBM capacity for ICI exchange
    assert v["xfer_hbm_bytes"] < v["baseline_hbm_bytes"]


def _pipeline(v):
    # layer pipelining matches Super-LIP's throughput but not its latency
    assert v["pipeline_req_per_s"] == pytest.approx(v["superlip_req_per_s"], rel=0.01)
    assert v["pipeline_s"] > v["superlip_s"]


CHECKS = {
    "table1_uniform_vs_custom": _table1,
    "table3_xfer_speedup_32b_idealized": _table3,
    "table3_xfer_speedup_16b_idealized": _table3,
    "table3_xfer_speedup_16b_measured-ddr": _table3,
    "table4_A->B": _table4(("IFM", "compute")),
    "table4_C->D": _table4(("compute", "compute")),
    "fig3_beat_improvement": _fig3,
    "fig14_design_12x16": _fig14("compute"),
    "fig14_design_10x22": _fig14("IFM"),
    "fig14_design_8x32": _fig14("IFM"),
    "fig15_alexnet": _fig15,
    "fig15_squeezenet": _fig15,
    "fig15_vgg": _fig15,
    "fig15_yolo": _fig15,
    "tpu_xfer_minitron-8b_train_4k": _tpu_xfer,
    "tpu_xfer_phi3-medium-14b_train_4k": _tpu_xfer,
    "tpu_xfer_minitron-8b_decode_32k": _tpu_xfer,
    "pipeline_vs_superlip": _pipeline,
}


@pytest.mark.parametrize("name", list(CHECKS))
def test_row_holds_its_stated_property(paper_rows, name):
    CHECKS[name](paper_rows[name].values)


def test_main_prints_every_row(paper_rows):
    assert list(paper_rows) == list(CHECKS)
    out = io.StringIO()
    with contextlib.redirect_stdout(out):
        runpy.run_module("repro.paper", run_name="__main__")
    assert out.getvalue().splitlines() == [
        f"{r.name} | {r.derived}" for r in paper_rows.values()]
