"""Published peaks per chip, keyed by the ``device_kind`` JAX reports.

A kind that is not in the table is an error, never a default: a roofline
share against another chip's peaks is a wrong number that looks right.
"""
from __future__ import annotations

import dataclasses


@dataclasses.dataclass(frozen=True)
class Peaks:
    bf16_flops: float       # FLOP/s, MXU, bf16 operands
    int8_ops: float         # OP/s, MXU, int8 operands
    hbm_bytes: float        # bytes/s
    hbm_capacity: float     # bytes
    source: str


PEAKS = {
    "TPU v5 lite": Peaks(
        bf16_flops=197e12, int8_ops=393e12, hbm_bytes=819e9,
        hbm_capacity=16 * 2**30,
        source="Google Cloud documentation, TPU v5e"),
}


def for_kind(device_kind: str) -> Peaks:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no peaks for device kind {device_kind!r}; known: "
                       f"{sorted(PEAKS)}") from None
