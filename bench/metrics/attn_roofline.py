"""Kernels: share of their roofline the fused attention kernels (PSSA
self-attention and TIPS cross-attention) reach, in percent.

The least time of the attention calls one slot step makes (``flops``:
the required FLOPs and HBM bytes of each call, the larger of compute and
memory time) times the slot steps traced, over the device time of the
kernel events traced."""
import device_trace
import flops


def read(run):
    tr = run["trace"] or {}
    steps, _ = device_trace.module_total(tr, "slot_step")
    if not steps or not tr.get("kernel_ns"):
        return None
    least = sum(flops.least_time_s(f, b, run["peaks"])
                for _, f, b in run["attention_calls"])
    return 100.0 * least * steps / (tr["kernel_ns"] * 1e-9)
