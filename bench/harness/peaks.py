"""Published peaks of the chips the benchmark runs on, keyed by
``jax.Device.device_kind``. A kind missing here is an error, never a
default: a share of another chip's peak would be wrong."""

PEAKS = {
    # Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, 393 TOP/s
    # int8, 16 GB of HBM at 819 GB/s per chip
    "TPU v5 lite": {"bf16_flops": 197e12, "int8_ops": 393e12,
                    "hbm_bytes_per_s": 819e9, "hbm_bytes": 16e9},
}


def peaks_for(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(f"no peaks for device kind {device_kind!r}; "
                         f"known: {sorted(PEAKS)}") from None
