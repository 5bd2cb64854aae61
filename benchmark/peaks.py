"""Published peaks of the chips the benchmark knows, keyed by JAX's
``device_kind``. There is no CPU row and no default: a device that is not
here is an error, never a guess.
"""

PEAKS = {
    # Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, 16 GB of
    # HBM2e at 819 GB/s, per chip.
    "TPU v5 lite": {
        "flops_bf16": 197e12,
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
        "source": "Google Cloud documentation, TPU v5e system architecture",
    },
}
PEAKS["TPU v5e"] = PEAKS["TPU v5 lite"]


def peaks_for(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise RuntimeError(
            f"benchmark/peaks.py has no row for device kind "
            f"{device_kind!r}; add one with its source before reporting a "
            f"share of its peak") from None
