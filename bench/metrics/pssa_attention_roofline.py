"""Kernels: share of its roofline the PSSA self-attention kernel reaches,
in percent: the least time of the self-attention calls one slot step makes
(required FLOPs and bytes, ``flops.attention_calls``) times the slot steps
traced, over the device time of its ``pssa_attention_kernel`` events."""
import device_trace
import flops


def read(run):
    tr = run["trace"] or {}
    steps, _ = device_trace.module_total(tr, "slot_step")
    ns = tr.get("ops", {}).get("pssa_attention_kernel")
    if not steps or not ns:
        return None
    least = sum(flops.least_time_s(f, b, run["peaks"])
                for k, f, b in run["attention_calls"] if k == "self")
    return 100.0 * least * steps / (ns * 1e-9)
