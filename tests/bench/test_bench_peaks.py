"""The peaks table: published numbers by device kind, no default."""
from __future__ import annotations

import pytest

from bench import peaks


def test_v5e_peaks():
    p = peaks.peaks("TPU v5 lite")
    assert p["flops"] == 197e12 and p["hbm_bytes_per_s"] == 819e9


@pytest.mark.parametrize("kind", ["cpu", "TPU v4", "TPU v5", ""])
def test_unknown_device_kind_raises(kind):
    with pytest.raises(KeyError):
        peaks.peaks(kind)
