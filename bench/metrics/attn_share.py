"""Kernels: device time of the Pallas (Mosaic) kernel events over device
busy time, in percent."""


def read(run):
    tr = run["trace"] or {}
    if not tr.get("kernel_count") or not tr.get("busy_ns"):
        return None
    return 100.0 * tr["kernel_ns"] / tr["busy_ns"]
