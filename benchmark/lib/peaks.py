"""Published peaks of the chips the benchmark may run on, keyed by ``device_kind``.

A copy of the table in ``evox_tpu/core/xla_cost.py`` (``CHIP_CEILINGS``): a
later PR may change the program's file, not the yardstick. A device that is
not in the table is an error, never a default.
"""

from __future__ import annotations

PEAKS = {
    # Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, 16 GB HBM at
    # 819 GB/s, 1,600 Gbit/s chip-to-chip interconnect
    "TPU v5 lite": {
        "flops_per_s": 197e12,
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
        "source": "Google Cloud documentation, TPU v5e",
    },
}


def peaks(device_kind: str) -> dict:
    """The peaks of ``device_kind``; raises for a device outside the table."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            f"no published peaks for device kind {device_kind!r}; add them to "
            "benchmark/lib/peaks.py with their source"
        ) from None
