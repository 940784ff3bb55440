"""Published per-chip peaks, keyed by jax's ``device_kind``.

Source: Google Cloud documentation, "TPU v5e" (cloud.google.com/tpu/docs/v5e):
197 TFLOP/s in bf16, 16 GB of HBM at 819 GB/s. Copied from ``bench.py``'s
``DEVICE_PEAKS``. A device that is not in this table is an error, not a default.
"""

PEAKS = {
    "TPU v5 lite": {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9,
                    "hbm_bytes": 16e9},
}


def peaks_for(device_kind: str) -> dict:
    if device_kind not in PEAKS:
        raise SystemExit(
            f"benchmark: no published peaks for device_kind {device_kind!r}; "
            "add it to benchmark/harness/peaks.py with its source")
    return PEAKS[device_kind]
