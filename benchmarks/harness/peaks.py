"""Published peaks of the chips the benchmark may run on, by ``device_kind``.

Source: Google Cloud documentation, "TPU v5e" (system architecture): one
chip 197 TFLOP/s bf16, 16 GB HBM2e at 819 GB/s.  A device
that is not in the table is an error, never a default.
"""

from __future__ import annotations

PEAKS: dict[str, dict[str, float]] = {
    "TPU v5 lite": {
        "bf16_flops": 197e12,
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
    },
}


def peaks_for(device_kind: str) -> dict[str, float]:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise SystemExit(
            f"device kind {device_kind!r} is not in the benchmark's peaks table "
            f"({sorted(PEAKS)}): no utilization can be stated for it"
        ) from None
