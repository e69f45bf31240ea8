"""Published per-chip peaks, keyed by the `device_kind` JAX reports.

Copied from `bench.DEVICE_PEAKS` (PR 21) so that no later PR can move the
yardstick. A kind that is not listed is an error, never a default: a share
of another chip's peak is a wrong number that looks right.
"""
from __future__ import annotations

# Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, 819 GB/s HBM,
# 16 GB of HBM per chip.
DEVICE_PEAKS = {
    "TPU v5 lite": {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9,
                    "hbm_bytes": 16e9},
}


def device_peaks(device_kind: str) -> dict:
    try:
        return DEVICE_PEAKS[device_kind]
    except KeyError:
        raise RuntimeError(
            f"no peak figures for device_kind {device_kind!r}; known kinds: "
            f"{sorted(DEVICE_PEAKS)}. Add the published peaks, with their "
            "source, to benchmark/peaks.py in a benchmark PR.") from None
