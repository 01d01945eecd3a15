"""The peaks table: keyed by device kind, no default for an unknown one."""
import pytest

from bench.peaks import peaks_for


def test_v5e_peaks():
    p = peaks_for("TPU v5 lite")
    assert p["bf16_flops_per_s"] == 197e12
    assert p["int8_ops_per_s"] == 393e12
    assert p["hbm_bytes"] == 16e9
    assert p["hbm_bytes_per_s"] == 819e9


@pytest.mark.parametrize("kind", ["cpu", "TPU v4", "TPU v5"])
def test_unknown_kind_is_an_error(kind):
    with pytest.raises(KeyError, match="no peaks for device kind"):
        peaks_for(kind)
