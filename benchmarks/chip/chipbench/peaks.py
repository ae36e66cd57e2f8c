"""Published peaks of each accelerator, keyed by JAX's ``device_kind``.

A device that is not in the table is an error, never a default: a
roofline share computed against the wrong chip's peaks is a wrong number.
"""

from __future__ import annotations

import dataclasses


@dataclasses.dataclass(frozen=True)
class Peaks:
    bf16_flops: float  # FLOP/s of one chip's matrix units in bfloat16
    hbm_bytes_per_s: float
    hbm_bytes: float
    ici_bits_per_s: float  # chip-to-chip interconnect, one chip
    source: str


_V5E = Peaks(
    bf16_flops=197e12,
    hbm_bytes_per_s=819e9,
    hbm_bytes=16e9,
    ici_bits_per_s=1600e9,
    source="Google Cloud documentation, 'TPU v5e' (system architecture)",
)

TABLE: dict[str, Peaks] = {
    "TPU v5 lite": _V5E,
    "TPU v5e": _V5E,
}


def peaks_for(device_kind: str) -> Peaks:
    try:
        return TABLE[device_kind]
    except KeyError:
        raise KeyError(
            f"no published peaks for device_kind {device_kind!r}; "
            f"known: {sorted(TABLE)}"
        ) from None
