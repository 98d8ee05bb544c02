"""The trace reduction on a hand-built trace with known answers."""
import pytest

from chipbench import trace as T

MS = 1e6  # ns


def build():
    # window 0..100 ms from the harness spans
    host = [("engine.step", 0.0, 40 * MS), ("client.observe", 40 * MS, 5 * MS),
            ("client.wait", 45 * MS, 55 * MS)]
    d0 = [("%fusion.1 = bf16[8]{0} fusion(%p)", 0.0, 10 * MS),
          ("%all-reduce.2 = f32[4]{0} all-reduce(%x)", 5 * MS, 10 * MS),
          ("%while.9 = (s32[]) while(%t)", 30 * MS, 10 * MS),
          ("%fusion.3 = bf16[8]{0} fusion(%q)", 30 * MS, 10 * MS),
          ("%all-gather.4 = f32[4]{0} all-gather(%y)", 60 * MS, 10 * MS),
          ("%fusion.1 = bf16[8]{0} fusion(%p)", 95 * MS, 10 * MS)]  # past the window
    d1 = [("fusion.1", 0.0, 50 * MS)]
    mods = [("jit_serve_step(7)", 0.0, 15 * MS), ("jit_prefill(3)", 30 * MS, 10 * MS),
            ("jit_serve_step(7)", 60 * MS, 10 * MS),
            ("jit_serve_step(7)", 120 * MS, 10 * MS)]  # after the window
    return T.Trace(ops={"/device:TPU:0": d0, "/device:TPU:1": d1},
                   modules={"/device:TPU:0": mods}, host=host)


def test_window_and_busy_union():
    tr = build()
    assert tr.window() == (0.0, 100 * MS)
    # device 0: [0,15] + [30,40] + [60,70] + [95,100] = 40 ms; device 1: 50
    assert T.busy_seconds(tr) == pytest.approx(0.045)


def test_module_seconds():
    tr = build()
    assert T.module_seconds(tr, r"^jit_serve_step") == (2, pytest.approx(0.025))
    assert T.module_seconds(tr, r"^jit_prefill") == (1, pytest.approx(0.010))


def test_collective_exposure():
    # all-reduce [5,15] overlaps fusion [0,10]: 5 ms exposed; all-gather
    # [60,70] alone: 10 ms
    assert T.collective_exposed_seconds(build()) == pytest.approx(0.015)


def test_top_ops_and_idle_gaps():
    tr = build()
    # named by the program that ran them; the loop op is left out
    top = dict((k, round(v, 9)) for k, v in T.top_ops(tr))
    assert top == {"jit_serve_step/fusion.1": 0.010,
                   "jit_serve_step/all-reduce.2": 0.010,
                   "jit_prefill/fusion.3": 0.010,
                   "jit_serve_step/all-gather.4": 0.010,
                   "?/fusion.1": 0.005}
    gaps = T.idle_gaps(tr)
    # gaps on device 0: [15,30] in engine.step, [40,60] in client.wait
    # (its middle, 50, lies in wait), [70,95] in client.wait
    assert gaps == [["client.wait", pytest.approx(0.025)],
                    ["client.wait", pytest.approx(0.020)],
                    ["engine.step", pytest.approx(0.015)]]


def test_merge_and_subtract():
    spans = T.merge([("a", 0, 5), ("b", 3, 4), ("c", 10, 1)])
    assert spans == [(0, 7), (10, 11)]
    assert T.subtract([(0, 10)], [(2, 3), (5, 20)]) == [(0, 2), (3, 5)]


def test_no_device_reads_nothing():
    tr = T.Trace(ops={}, modules={}, host=[("engine.step", 0.0, 1.0)])
    assert T.module_seconds(tr, "x") == (0, 0.0)
    assert T.top_ops(tr) == [] and T.idle_gaps(tr) == []
