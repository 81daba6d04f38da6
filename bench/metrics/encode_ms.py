"""Engine: device time per execution of the text-encode program
(``jit_text_encode``: one prompt through every text tower, two an
admission under CFG), in ms, from the trace's program events."""
import device_trace


def read(run):
    count, total_ns = device_trace.module_total(run["trace"] or {},
                                                "text_encode")
    return total_ns / count * 1e-6 if count else None
