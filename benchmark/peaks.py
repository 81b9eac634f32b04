"""Published peaks of the chips the benchmark may run on, by the
``device_kind`` JAX reports. A kind that is not here is an error.

Source: Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16,
393 TOP/s int8, 16 GB HBM at 819 GB/s per chip.
"""

PEAKS = {
    "TPU v5 lite": {
        "bf16_flops_per_s": 197e12,
        "hbm_bytes_per_s": 819e9,
    },
}


def peak(device_kind, what):
    if device_kind not in PEAKS:
        raise RuntimeError(
            f"no peak on record for device_kind {device_kind!r}; the "
            f"benchmark knows {sorted(PEAKS)} only")
    return PEAKS[device_kind][what]
