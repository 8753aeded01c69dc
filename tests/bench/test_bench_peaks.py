"""The peaks table and the ops/bytes function of ``wavefaa``."""

import sys
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

from bench import peaks  # noqa: E402


def test_v5e_peaks_with_source():
    p = peaks.peaks_for("TPU v5 lite")
    assert (p.flops_per_s, p.hbm_bytes_per_s, p.hbm_bytes) == (
        197e12, 819e9, 16e9)
    assert "TPU v5e" in p.source


@pytest.mark.parametrize("kind", ["TPU v4", "cpu", "TPU v5e", ""])
def test_unknown_device_kind_raises(kind):
    with pytest.raises(KeyError):
        peaks.peaks_for(kind)


@pytest.mark.parametrize("lanes", [4096, 32768])
def test_wavefaa_work_and_bound(lanes):
    w = peaks.wavefaa_work(lanes)
    assert w == peaks.Work(ops=lanes, bytes=8 * lanes)
    t, bound = peaks.roofline_seconds(w, peaks.peaks_for("TPU v5 lite"))
    assert bound == "memory"
    assert t == pytest.approx(8 * lanes / 819e9)
