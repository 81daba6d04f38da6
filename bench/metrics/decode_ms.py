"""Engine: device time per execution of the VAE decode program
(``jit_vae_decode``), in ms, from the trace's program events."""
import device_trace


def read(run):
    count, total_ns = device_trace.module_total(run["trace"] or {},
                                                "vae_decode")
    return total_ns / count * 1e-6 if count else None
