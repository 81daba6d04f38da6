"""Engine stages outside the step (text encode, admit, VAE decode, the
router's eager ops): their programs' device time over all programs' device
time, in percent."""
import device_trace


def read(run):
    mods = (run["trace"] or {}).get("modules", {})
    total = sum(t for _, t in mods.values())
    _, step = device_trace.module_total(run["trace"] or {}, "slot_step")
    return 100.0 * (total - step) / total if total and step else None
