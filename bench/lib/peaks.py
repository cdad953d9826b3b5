"""Published peaks of each accelerator, keyed by JAX's ``device_kind``.

Every roofline share and utilization divides by a number from this table.
A device kind that is missing is an error: a default would silently divide
by the wrong chip.
"""
from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {
        "bf16_flops_per_s": 197e12,
        "int8_ops_per_s": 393e12,
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
        "ici_bits_per_s": 1600e9,
        "source": "Google Cloud documentation, 'TPU v5e' "
                  "(cloud.google.com/tpu/docs/v5e): per chip",
    },
}


def peaks_for(device_kind: str) -> dict:
    """The peaks of ``device_kind``; raises KeyError for an unknown one."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind "
                       f"{device_kind!r}; known: {sorted(PEAKS)}") from None
