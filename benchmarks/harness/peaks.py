"""Published peaks of the chips the benchmark runs on, keyed by the
``device_kind`` JAX reports.  A kind that is not listed is an error, never
a default.

Source: Google Cloud documentation, "TPU v5e" (system architecture): one
chip has 16 GB of HBM2e at 819 GB/s, 197 TFLOP/s in bf16, 393 TOP/s in int8
and 1,600 Gbit/s of chip-to-chip interconnect.  Copied from
``bench._HBM_BYTES_PER_S`` (PR 21), which a later PR may delete.
"""

from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {"hbm_bytes_per_s": 819e9, "hbm_bytes": 16e9,
                    "ici_bits_per_s": 1600e9},
    "TPU v5e": {"hbm_bytes_per_s": 819e9, "hbm_bytes": 16e9,
                "ici_bits_per_s": 1600e9},
}


def peak(device_kind: str, what: str) -> float:
    try:
        return PEAKS[device_kind][what]
    except KeyError:
        raise KeyError(
            f"no published {what!r} for device kind {device_kind!r}: add "
            f"it to benchmarks/harness/peaks.py with its source") from None
