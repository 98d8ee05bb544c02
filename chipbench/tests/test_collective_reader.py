"""``collective_device_ms_per_step`` on hand-built traces: collectives
inside serve-step executions count, those outside (another program, a
step that started before the window, the host's gaps) do not."""
import types

import pytest

from chipbench import harness as H
from chipbench import trace as T

MS = 1e6  # ns
READ = H.load_reader("collective_device_ms_per_step")


def obs(d0, mods, d1=()):
    # window 0..100 ms from the harness spans
    host = [("engine.step", 0.0, 60 * MS), ("client.wait", 60 * MS, 40 * MS)]
    tr = T.Trace(ops={"/device:TPU:0": list(d0), "/device:TPU:1": list(d1)},
                 modules={"/device:TPU:0": list(mods)}, host=host)
    return types.SimpleNamespace(trace=tr)


STEPS = [("jit_serve_step(7)", 0.0, 20 * MS),
         ("jit_prefill(3)", 30 * MS, 10 * MS),
         ("jit_serve_step(7)", 50 * MS, 20 * MS),
         ("jit_serve_step(7)", -30 * MS, 20 * MS)]  # started before the window


def test_collectives_inside_serve_steps_per_execution():
    d0 = [("fusion.1", 0.0, 20 * MS),
          ("all-reduce.8", 2 * MS, 3 * MS),
          ("all-gather.27", 4 * MS, 2 * MS),        # overlaps: union 2..6
          ("psum.17", 10 * MS, 1 * MS),             # shard_map's merge
          ("%pmax.9 = f32[128,1,32,1]{0} all-reduce(%x)", 18 * MS, 4 * MS),
          ("all-reduce.8", 32 * MS, 5 * MS),        # inside the prefill
          ("all-reduce-start.3", 55 * MS, 1 * MS),
          ("fusion.2", 56 * MS, 10 * MS),
          ("all-gather.35", 80 * MS, 5 * MS),       # between programs
          ("all-reduce.8", -20 * MS, 5 * MS)]       # in the early step
    # step 1: [2,6] + [10,11] + [18,20] (clipped at its end) = 7 ms;
    # step 2: [55,56] = 1 ms; two executions start in the window
    assert READ(obs(d0, STEPS)) == pytest.approx(4.0)


def test_only_the_first_device_is_read():
    d0 = [("fusion.1", 0.0, 20 * MS)]
    d1 = [("all-reduce.8", 2 * MS, 3 * MS)]
    assert READ(obs(d0, STEPS, d1)) == 0.0


def test_no_collective_reads_zero():
    d0 = [("fusion.1", 0.0, 20 * MS), ("psum_fusion.4", 5 * MS, 1 * MS),
          ("fusion.2", 50 * MS, 20 * MS)]
    assert READ(obs(d0, STEPS)) == 0.0


def test_nothing_to_read():
    assert READ(types.SimpleNamespace(trace=None)) is None
    no_step = [("jit_prefill(3)", 30 * MS, 10 * MS)]
    assert READ(obs([("all-reduce.8", 32 * MS, 5 * MS)], no_step)) is None
    assert READ(obs([], STEPS)) == 0.0
