"""FLOP and byte counts against hand-computed values for yi-9b-l16."""
import json
import pathlib

import pytest

from chipbench import flops as F

CFG = json.loads((pathlib.Path(__file__).resolve().parents[1]
                  / "configs" / "yi-9b-l16.json").read_text())


def test_parameter_counts():
    # q 4096x4096, k and v 4096x512 each, o 4096x4096, gate/up/down
    # 3 x 4096x11008 = 16777216 + 4194304 + 16777216 + 135266304
    assert F.layer_matmul_params(CFG) == 173_015_040
    assert F.head_params(CFG) == 262_144_000
    # 16 layers + head, bf16, plus 33 norm vectors of 4096
    assert F.weight_bytes(CFG) == 2 * (16 * 173_015_040 + 262_144_000
                                       + 33 * 4096)


def test_kv_and_attention():
    # K and V, 16 layers, 4 kv heads of 128, bf16: 32768 bytes
    assert F.kv_bytes_per_position(CFG) == 32_768
    # 4 * 4096 (q dim) * 1000 positions * 16 layers
    assert F.attn_flops(CFG, 1000) == 262_144_000


def test_token_and_prompt_flops():
    body = 16 * 173_015_040
    assert F.decode_token_flops(CFG, 600) == (2 * (body + 262_144_000)
                                              + 4 * 4096 * 600 * 16)
    # 1024 tokens: matmuls per token, causal attention 1024*1025/2 pairs,
    # head once
    assert F.prefill_flops(CFG, 1024) == (2 * body * 1024
                                          + 4 * 4096 * (1024 * 1025 // 2) * 16
                                          + 2 * 262_144_000)


def test_decode_step_roofline():
    peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    flops, nbytes = F.decode_step_need(CFG, [600] * 32)
    assert nbytes == F.weight_bytes(CFG) + 32 * 600 * 32_768
    t = F.roofline_seconds(flops, nbytes, peaks, 1)
    assert t == pytest.approx(nbytes / 819e9)          # bandwidth-bound
    # (6.061 GB of weights + 0.629 GB of live K/V) / 819 GB/s
    assert t * 1e3 == pytest.approx(8.1687, rel=1e-4)
    assert F.roofline_seconds(flops, nbytes, peaks, 4) == pytest.approx(t / 4)
