"""Published peaks of one chip, keyed by ``device_kind`` as JAX reports it.

The benchmark's own copy (the original is
``paddle_tpu/observability/flops.py::PEAKS_BY_DEVICE_KIND``), kept under
the benchmark's paths so that no later PR can move the yardstick.

Source: Google Cloud TPU documentation, the system-architecture page of
each generation ("TPU v5e": 197 TFLOP/s bf16, 819 GB/s HBM, 1,600 Gbit/s
interchip interconnect; likewise "TPU v4", "TPU v5p", "TPU v6e").
A device that is not in the table is an error, never a default.
"""
from __future__ import annotations

from typing import NamedTuple


class Peaks(NamedTuple):
    flops: float        # dense bf16 FLOP/s
    hbm_bytes: float    # HBM bytes/s
    ici_bytes: float    # aggregate interchip bytes/s


_V5E = Peaks(197e12, 0.819e12, 200e9)
_V5P = Peaks(459e12, 2.765e12, 600e9)
_V6E = Peaks(918e12, 1.64e12, 448e9)
PEAKS_BY_DEVICE_KIND = {
    "TPU v4": Peaks(275e12, 1.2e12, 300e9),
    "TPU v5 lite": _V5E, "TPU v5e": _V5E,
    "TPU v5": _V5P, "TPU v5p": _V5P,
    "TPU v6 lite": _V6E, "TPU v6e": _V6E,
}


def peaks_for(device_kind: str) -> Peaks:
    if device_kind not in PEAKS_BY_DEVICE_KIND:
        raise KeyError(
            f"device_kind {device_kind!r} is not in the benchmark's peaks "
            f"table ({sorted(PEAKS_BY_DEVICE_KIND)}); add its published "
            "peaks with their source in a new benchmark PR")
    return PEAKS_BY_DEVICE_KIND[device_kind]
