"""Engine: device time per execution of the slot-step program
(``_slot_step_traced``), from the trace's program events."""
import device_trace


def read(run):
    count, total_ns = device_trace.module_total(run["trace"] or {},
                                                "slot_step")
    return total_ns / count * 1e-6 if count else None
