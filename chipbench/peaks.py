"""The one table of device peaks, keyed by `device_kind` as JAX
reports it. A device that is not here is an error, not a default."""

PEAKS = {
    # Google Cloud documentation, "TPU v5e": 16 GB of HBM at 819 GB/s,
    # 197 TFLOP/s bf16 (unused: the ledger kernels have no matmul).
    "TPU v5 lite": {"hbm_bytes_per_s": 819e9, "hbm_bytes": 16e9,
                    "source": "Google Cloud documentation, TPU v5e"},
}


def peak(device_kind: str) -> dict:
    if device_kind not in PEAKS:
        raise KeyError(f"no peaks recorded for device kind {device_kind!r}: "
                       "add a sourced row to chipbench/peaks.py")
    return PEAKS[device_kind]
