"""The one table of chip peaks the benchmark's shares are taken against.

Keyed by a marker in jax's ``device_kind``. Source: Google Cloud
documentation, "TPU v5e" (197 TFLOP/s bf16, 819 GB/s HBM, 16 GB), and the
same pages for the other generations. A device that is not in the table is
an error, not a default: a share of somebody else's peak is no measurement.
(Copied from ``bench.py`` ``_PEAKS``/``peaks_for`` so that a later PR which
changes ``bench.py`` cannot move the yardstick.)
"""
from __future__ import annotations

from typing import NamedTuple


class Peaks(NamedTuple):
    flops_per_s: float       # dense bf16
    hbm_bytes_per_s: float
    hbm_bytes: float


_PEAKS = (
    ("v5 lite", Peaks(197e12, 819e9, 16e9)),
    ("v5e", Peaks(197e12, 819e9, 16e9)),
    ("v6 lite", Peaks(918e12, 1640e9, 32e9)),
    ("v6e", Peaks(918e12, 1640e9, 32e9)),
    ("v5p", Peaks(459e12, 2765e9, 95e9)),
    ("v5", Peaks(459e12, 2765e9, 95e9)),  # after the lite checks
    ("v4", Peaks(275e12, 1228e9, 32e9)),
)


def peaks_for(device_kind: str) -> Peaks:
    kind = device_kind.lower()
    for marker, peaks in _PEAKS:
        if marker in kind:
            return peaks
    raise ValueError(
        f"no peaks on record for device kind {device_kind!r}; add it to "
        "benchmark/peaks.py with its source")
