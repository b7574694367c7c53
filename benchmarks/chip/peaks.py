"""Published peaks of one chip, keyed by the ``device_kind`` that JAX
reports. A kind that is not here is an error, not a default."""

PEAKS = {
    # Google Cloud documentation, "TPU v5e": per chip 197 TFLOP/s in
    # bf16 and 16 GB of HBM at 819 GB/s
    "TPU v5 lite": {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9},
}


def peak(device_kind: str, what: str) -> float:
    if device_kind not in PEAKS:
        raise KeyError(f"no published peaks for device kind "
                       f"{device_kind!r} (have {sorted(PEAKS)})")
    return PEAKS[device_kind][what]
