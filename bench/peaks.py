"""Published peaks per accelerator, keyed by JAX's ``device_kind``, and the
operation and byte counts of each kernel that has a roofline metric.

A device kind missing from ``PEAKS`` is an error, never a default."""

from __future__ import annotations

from typing import Dict, NamedTuple


class Peaks(NamedTuple):
    flops_per_s: float      # bf16 matrix peak
    hbm_bytes_per_s: float
    hbm_bytes: float
    source: str


PEAKS: Dict[str, Peaks] = {
    "TPU v5 lite": Peaks(
        flops_per_s=197e12, hbm_bytes_per_s=819e9, hbm_bytes=16e9,
        source='Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, '
               '16 GB HBM2 at 819 GB/s per chip'),
}


def peaks_for(device_kind: str) -> Peaks:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no peaks for device kind {device_kind!r}; known: "
                       f"{sorted(PEAKS)}") from None


class Work(NamedTuple):
    ops: float
    bytes: float


def wavefaa_work(lanes: int) -> Work:
    """One ``wavefaa`` call over ``lanes`` request lanes: the exclusive
    prefix rank is one operation per lane; it reads the int32 mask and
    writes the int32 tickets (4 bytes each way per lane)."""
    return Work(ops=float(lanes), bytes=8.0 * lanes)


def roofline_seconds(work: Work, peaks: Peaks) -> tuple:
    """The least time the chip could take for ``work``, and which bound
    sets it (``"compute"`` or ``"memory"``)."""
    t_ops = work.ops / peaks.flops_per_s
    t_mem = work.bytes / peaks.hbm_bytes_per_s
    return (t_mem, "memory") if t_mem >= t_ops else (t_ops, "compute")
