"""The peak table is keyed by device kind; a kind not in it is an error."""
import pytest

from chipbench import harness as H


def test_v5e_peaks():
    p = H.load_peaks("TPU v5 lite")
    assert p["bf16_flops_per_s"] == 197e12 and p["int8_ops_per_s"] == 393e12
    assert p["hbm_bytes_per_s"] == 819e9 and p["hbm_bytes"] == 16e9
    assert p["ici_bytes_per_s"] == 200e9


@pytest.mark.parametrize("kind", ["TPU v4", "cpu", "source"])
def test_unknown_kind_is_an_error(kind):
    with pytest.raises(H.RunError):
        H.load_peaks(kind)
