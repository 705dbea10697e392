"""The one table of device peaks, keyed by ``device_kind`` as JAX reports it.

Source: Google Cloud documentation, "TPU v5e" system architecture page —
per chip 197 TFLOP/s in bf16, 16 GB of HBM2e at 819 GB/s, 1600 Gbit/s of
chip-to-chip interconnect.  A kind that is not here is an error, not a
default: add it with its source before reporting a share of its peak.
(``horovod_tpu/utils/chip.PEAK_BF16_FLOPS`` holds the same FLOP/s for the
program's own scripts; the benchmark keeps its yardstick here.)
"""

from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {
        "bf16_flops_per_s": 197e12,
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
    },
}


def peaks(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(
            f"no peaks on record for device_kind {device_kind!r} (known: "
            f"{sorted(PEAKS)}); add it to benchmarks/peaks.py with its "
            f"source") from None
