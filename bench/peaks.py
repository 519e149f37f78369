"""Published peaks per chip, keyed by JAX's ``device_kind``.

A kind that is not in the table is an error, never a default.
"""
from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {
        "bf16_flops": 197e12,
        "int8_ops": 393e12,
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
        "source": "Google Cloud documentation, 'TPU v5e' (per chip)",
    },
}


def lookup(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise SystemExit(f"bench: no peak table entry for device_kind "
                         f"{device_kind!r}; known: {sorted(PEAKS)}") from None
