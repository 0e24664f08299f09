"""Published peaks of one chip, keyed by the ``device_kind`` JAX reports.

Source: Google Cloud documentation, "TPU v5e" system architecture: 197
TFLOP/s bf16, 393 TOP/s int8, 16 GB of HBM at 819 GB/s per chip.  The chip
names itself "TPU v5 lite".  A device that is not here is an error, never a
default."""

PEAKS = {
    "TPU v5 lite": {
        "bf16_flops_per_s": 197e12,
        "int8_ops_per_s": 393e12,
        "hbm_bytes": 16e9,
        "hbm_bytes_per_s": 819e9,
    },
}


def peaks(device_kind: str) -> dict:
    if device_kind not in PEAKS:
        raise KeyError(
            f"no peaks recorded for device_kind={device_kind!r} (known: "
            f"{sorted(PEAKS)}); add it to benchmark/peaks.py with its source")
    return PEAKS[device_kind]
