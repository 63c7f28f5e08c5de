"""Published peaks of one chip, keyed by ``device_kind`` as JAX reports it.

Copied from ``bench.py`` (PR 21), which stays where it is. An unknown
device is an error: a utilization against another chip's peak is not a
measurement.
"""

from __future__ import annotations

from typing import NamedTuple


class ChipPeak(NamedTuple):
    bf16_flops: float   # dense bf16 FLOP/s per chip
    hbm_bytes: float    # HBM bytes/s per chip
    source: str


PEAKS = {
    "TPU v4": ChipPeak(
        275e12, 1228e9,
        "Google Cloud TPU documentation, 'TPU v4': 275 TFLOP/s bf16, "
        "1228 GB/s HBM2 per chip"),
    "TPU v5 lite": ChipPeak(
        197e12, 819e9,
        "Google Cloud TPU documentation, 'TPU v5e': 197 TFLOP/s bf16, "
        "819 GB/s HBM2e per chip"),
    "TPU v5p": ChipPeak(
        459e12, 2765e9,
        "Google Cloud TPU documentation, 'TPU v5p': 459 TFLOP/s bf16, "
        "2765 GB/s HBM2e per chip"),
    "TPU v6 lite": ChipPeak(
        918e12, 1640e9,
        "Google Cloud TPU documentation, 'TPU v6e': 918 TFLOP/s bf16, "
        "1640 GB/s HBM per chip"),
}


def chip_peak(device_kind: str) -> ChipPeak:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise LookupError(
            f"device kind {device_kind!r} is not in the benchmark's peak "
            f"table ({sorted(PEAKS)}); add its published peaks with their "
            f"source before reporting a utilization on it") from None
