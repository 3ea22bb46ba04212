"""Published peaks of the chips this benchmark has run on, by the
``device_kind`` JAX reports.  A kind that is not here is an error, never
a default: a share of an unknown peak is no number."""

# Google Cloud documentation, "TPU v5e" (cloud.google.com/tpu/docs/v5e):
# 197 TFLOP/s bf16, 16 GB HBM2e at 819 GB/s per chip.  For scale: a large
# bf16 matmul measured 185 TFLOP/s and an elementwise pass over 2 GiB
# 590 GB/s on one such chip (on-chip-measurement guide, section 4).
PEAKS = {
    "TPU v5 lite": {
        "bf16_flops_per_s": 197e12,
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
        "source": "Google Cloud documentation, 'TPU v5e'",
    },
}


def peaks(device_kind):
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            f"no published peaks for device kind {device_kind!r}; add it "
            f"to benchmark/harness/peaks.py with its source") from None
