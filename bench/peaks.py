"""Published per-chip peaks, keyed by JAX's ``device_kind``.

TPU v5e ("TPU v5 lite"): Google Cloud documentation, "TPU v5e": 197 TFLOP/s
bf16, 393 TOP/s int8, 16 GB of HBM at 819 GB/s, 1,600 Gbit/s of
chip-to-chip interconnect."""
from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9,
                    "hbm_bytes": 16e9,
                    "source": "Google Cloud documentation, TPU v5e"},
}


def peak(device_kind: str) -> dict:
    """The row of one device kind. A kind the table lacks is an error,
    never a default."""
    if device_kind not in PEAKS:
        raise KeyError(f"no peaks for device kind {device_kind!r}: add them "
                       f"to bench/peaks.py with their source")
    return PEAKS[device_kind]
