"""Peak rates of each accelerator the benchmark runs on, keyed by the
``device_kind`` string exactly as JAX reports it.

Source: Google Cloud documentation, "TPU v5e" (system architecture page):
197 TFLOP/s in bf16, 393 TOP/s in int8, 16 GB of HBM at 819 GB/s, and
1,600 Gbit/s of chip-to-chip interconnect per chip.

A device whose kind is not in the table is an error: the benchmark never
guesses a peak.
"""

from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {
        "bf16_flops": 197e12,       # FLOP/s
        "int8_ops": 393e12,         # OP/s
        "hbm_bytes": 16e9,
        "hbm_bandwidth": 819e9,     # byte/s
        "source": "Google Cloud documentation, TPU v5e",
    },
}


class UnknownDeviceKind(KeyError):
    """The device kind has no row in :data:`PEAKS`."""


def peaks_for(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise UnknownDeviceKind(
            f"no peak rates recorded for device_kind {device_kind!r}; "
            f"known: {sorted(PEAKS)}") from None
