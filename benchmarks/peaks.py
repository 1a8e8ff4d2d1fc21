"""The one table of peaks, keyed by ``device_kind`` as JAX reports it.

Source: Google Cloud documentation, "TPU v5e" (system architecture): 197
TFLOP/s bf16, 819 GB/s HBM, 16 GB HBM a chip. A device that is not here is an
error, not a default.
"""

PEAKS = {
    "TPU v5 lite": {
        "bf16_flops_per_s": 197e12,
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
        "source": "cloud.google.com/tpu/docs/v5e",
    },
}


def lookup(device_kind: str) -> dict:
    if device_kind not in PEAKS:
        raise KeyError(
            f"device_kind {device_kind!r} is not in benchmarks/peaks.py "
            f"(known: {sorted(PEAKS)}); add its published peaks with their source"
        )
    return PEAKS[device_kind]
