"""Published peaks of one chip, keyed by ``device_kind`` as JAX reports
it. A device that is not here is an error, not a default."""

PEAKS = {
    # Google Cloud documentation, "TPU v5e" system architecture page.
    "TPU v5 lite": {
        "bf16_flops": 197e12,
        "int8_ops": 393e12,
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
        "source": "cloud.google.com/tpu/docs/v5e (197 TF/s bf16, 393 "
                  "TOP/s int8, 16 GB HBM2e at 819 GB/s)",
    },
}


def peaks_for(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            f"no published peaks for device kind {device_kind!r}; the "
            f"table has {sorted(PEAKS)}"
        ) from None
