"""Published per-chip peak rates, keyed by JAX's ``device_kind``.

Copied from ``tools/device_peaks.py`` so that the yardstick lives with the
benchmark.  A device that is not in the table is an error, never a default:
a roofline against the wrong chip's peak is worse than none.
"""

# Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, 16 GB HBM at
# 819 GB/s per chip.
PEAKS = {
    "TPU v5 lite": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9},
}


def peaks_for(device_kind):
    """The peaks of ``device_kind`` (``jax.devices()[0].device_kind``)."""
    if device_kind not in PEAKS:
        raise SystemExit(
            f"no published peaks for device_kind {device_kind!r} "
            f"(known: {sorted(PEAKS)}); add it to chipbench/peaks.py "
            f"with its source")
    return PEAKS[device_kind]
