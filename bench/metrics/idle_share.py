"""Device: share of the traced window in which no operation ran on the
chip, in percent."""


def read(run):
    tr = run["trace"] or {}
    if not tr.get("busy_ns") or not tr.get("window_ns"):
        return None
    return 100.0 * (1.0 - tr["busy_ns"] / tr["window_ns"])
