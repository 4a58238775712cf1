"""Percent of the HBM roofline a create_transfers dispatch reaches: the
least bytes its events need (chipbench/kernel_bytes.py, from the row
widths alone) over the chip's peak bandwidth, over the kernel's
measured time per dispatch. HBM-bound: the kernels have no matmul."""

from chipbench.kernel_bytes import create_transfers_least_bytes
from chipbench.peaks import peak


def read(context: dict):
    dev = context["device"]
    if dev is None or not dev["dispatch_seconds"]:
        return None
    d = dev["dispatch_seconds"]
    per_dispatch_s = sum(d) / len(d)
    if per_dispatch_s <= 0:
        return None
    least_s = (create_transfers_least_bytes(
        context["window"]["events_per_request"])
        / peak(context["device_kind"])["hbm_bytes_per_s"])
    return 100.0 * least_s / per_dispatch_s
