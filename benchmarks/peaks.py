"""Published per-chip peaks, keyed by ``device_kind`` as JAX reports it.

Source: Google Cloud TPU documentation, "TPU v5e" system architecture page
(cloud.google.com/tpu/docs/v5e): 197 TFLOP/s bf16, 819 GB/s HBM, 16 GB.
A kind that is not here is an error, never a default.
"""

PEAKS = {
    # device_kind: (bf16 FLOP/s, HBM bytes/s, HBM bytes)
    "TPU v5 lite": (197e12, 819e9, 16e9),
    "TPU v5e": (197e12, 819e9, 16e9),
}


def peak_flops(device_kind: str) -> float:
    try:
        return PEAKS[device_kind][0]
    except KeyError:
        raise ValueError(
            f"no published peak for device kind {device_kind!r} "
            f"(benchmarks/peaks.py holds {sorted(PEAKS)})") from None
