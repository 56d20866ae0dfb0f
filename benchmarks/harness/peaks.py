"""Published peaks of the chips this benchmark may run on, keyed by the
``device_kind`` jax reports. A device that is not here is an error, never
a default: a share of an unknown peak means nothing."""

# Google Cloud documentation, "TPU v5e" (system architecture table): 197
# TFLOP/s bf16, 16 GB HBM2e at 819 GB/s per chip. jax names the chip
# "TPU v5 lite".
_V5E = {
    "flops_per_s": 197e12,
    "hbm_bytes_per_s": 819e9,
    "hbm_bytes": 16e9,
    "source": "Google Cloud documentation, 'TPU v5e' system architecture",
}

PEAKS = {"TPU v5 lite": _V5E, "TPU v5e": _V5E}


def for_device_kind(kind: str) -> dict:
    try:
        return PEAKS[kind]
    except KeyError:
        raise KeyError(
            f"device_kind {kind!r} has no entry in benchmarks/harness/"
            f"peaks.py ({', '.join(PEAKS)}); add its published peaks "
            f"with their source") from None
