"""Percent of one chip's HBM (chipbench/peaks.py) that the fullest chip
held at its peak, by the device's own `memory_stats()` read in the
launcher after the server stopped: how far the deployment fills the
chip. Nothing where the backend keeps no such counter (the CPU)."""

from chipbench.peaks import peak


def read(context: dict):
    held = context["memory_peak_bytes"]
    if held is None:
        return None
    return 100.0 * held / peak(context["device_kind"])["hbm_bytes"]
