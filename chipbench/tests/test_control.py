"""The control — the float32 reference with every linear layer in int8,
put in the program's place on the same prompts and served tokens — reads
not correct, at a size a CPU test run can hold (d_model 256, 4 layers,
vocabulary 4096, a backlog keeping every slot busy, 8 rows compared).

At this size the program read 0.020-0.045 and the control 0.118-0.225
over seeds 1-6 on the CPU; the test's limit of 0.08 lies between. The
cells' own limit is set from readings on the chip at their own size (see
PERF.md)."""
import pytest

import tiny
from chipbench import harness as H

LIMIT = 0.08


def small():
    cfg = tiny.fresh(tiny.CONFIG)
    cfg.update(hidden_size=256, intermediate_size=704, num_attention_heads=8,
               num_key_value_heads=2, head_dim=32, num_hidden_layers=4,
               vocab_size=4096, reference_rows=8)
    return cfg


@pytest.mark.parametrize("seed", [1, 2, 5])
def test_control_reads_wider_than_the_program(cache_dir, seed):
    cfg, mix = small(), tiny.fresh(tiny.DECODE)
    mix["output"] = {"dist": "uniform", "min": 32, "max": 64}
    mix["drain_seconds"] = 5
    res = H.run_cell(tiny.cell(mix), cfg, mix, [], seed, 3.0, False,
                     require_tpu=False, limit=LIMIT, control=True)
    c = res["checks"]
    assert c["program_gap"]["value"] <= LIMIT, c
    assert c["served_gap"]["value"] > LIMIT, c
    assert not res["correct"], c
