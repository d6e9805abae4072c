"""Published peaks per chip, keyed by ``jax.Device.device_kind``.

Source: Google Cloud documentation, "TPU v5e" (system architecture
table): 197 TFLOP/s bf16, 394 TOP/s int8, 16 GB HBM at 819 GB/s per chip.
A device kind that is not in the table is an error, never a default.
"""

from __future__ import annotations

from dataclasses import dataclass


@dataclass(frozen=True)
class Peaks:
    bf16_flops: float     # FLOP/s, dense bf16 matmul
    int8_ops: float       # OP/s
    hbm_bytes_per_s: float
    hbm_bytes: float
    source: str


TABLE = {
    "TPU v5 lite": Peaks(
        bf16_flops=197e12, int8_ops=394e12, hbm_bytes_per_s=819e9,
        hbm_bytes=16e9,
        source="Google Cloud documentation, TPU v5e system architecture"),
}


def peaks_for(device_kind: str) -> Peaks:
    try:
        return TABLE[device_kind]
    except KeyError:
        raise KeyError(
            f"no published peaks for device kind {device_kind!r}; add them "
            f"to benchmarks/chip/peaks.py with their source") from None
