"""Published peaks of the chips the benchmark may run on, keyed by the
``device_kind`` JAX reports. A copy of ``utils/chip.PEAKS`` (PR 21): the
yardstick lives with the benchmark, where a later PR cannot move it. A kind
that is not here is an error, never a default."""

from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {
        "bf16_flops_per_s": 197e12,
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
        "source": 'Google Cloud documentation, "TPU v5e": 197 TFLOP/s '
                  'bf16, 16 GB HBM at 819 GB/s per chip',
    },
}


def peak(device_kind: str, what: str) -> float:
    if device_kind not in PEAKS:
        raise KeyError(
            f"no published peaks for device kind {device_kind!r} (known: "
            f"{sorted(PEAKS)}); add a sourced row to benchmark/harness/"
            f"peaks.py before reporting a share of a peak on it")
    return PEAKS[device_kind][what]
