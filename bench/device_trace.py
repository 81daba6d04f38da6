"""Reduction of a profiler trace (``.xplane.pb``) to what the metrics read.

Device planes are those named ``/device:<PLATFORM>:<n>``.  On each, the
line ``XLA Ops`` holds one event per executed operation and ``XLA
Modules`` one per executed program (a jitted function); where a plane has
no such lines, every event of its lines counts as an operation.  Host
spans are events of the host planes whose name starts with ``bench.``
(the annotations ``system.py`` puts around engine calls).

Everything is kept as intervals in nanoseconds on the trace's clock:

* ``busy_ns``: length of the union of operation intervals, averaged over
  the device planes;
* ``ops``: total duration per operation kind (``op_kind``);
* ``modules``: (count, total duration) per program name;
* ``kernel_ns`` / ``kernel_count``: operations that are Pallas (Mosaic)
  kernels: the Mosaic custom calls, whose HLO text names their target;
* ``idle_by_host_span``: idle time between busy intervals, by the host
  span that covered each gap's middle (gaps under 10 us apart).
"""
from __future__ import annotations

import bisect
import glob
import os
import re

KERNEL_MARKER = "tpu_custom_call"     # the Mosaic call target in HLO
SHORT_GAP_NS = 10_000


def load(trace_dir: str):
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return ProfileData.from_file(paths[-1])


def is_kernel(name: str) -> bool:
    return KERNEL_MARKER in name


def op_kind(name: str) -> str:
    """An operation's kind: its HLO name without the instruction text and
    the instance number (``%fusion.1620 = ...`` -> ``fusion``)."""
    return re.sub(r"\.\d+$", "", name.split(" = ")[0].lstrip("%"))


def _union(intervals):
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


def reduce(planes, window_ns=None) -> dict:
    """``planes``: iterable of objects with ``name`` and ``lines`` (each
    with ``name`` and ``events`` having ``name``, ``start_ns``,
    ``duration_ns``), as ``jax.profiler.ProfileData`` gives them."""
    dev_ops, dev_mods, spans = [], [], []
    for plane in planes:
        lines = list(plane.lines)
        if plane.name.startswith("/device:"):
            names = {ln.name for ln in lines}
            ops, mods = [], []
            for ln in lines:
                evs = [(e.name, float(e.start_ns), float(e.duration_ns))
                       for e in ln.events]
                if ln.name == "XLA Modules":
                    mods += evs
                elif ln.name == "XLA Ops" or "XLA Ops" not in names:
                    ops += evs
            if ops:
                dev_ops.append(ops)
                dev_mods.append(mods)
        else:
            for ln in lines:
                spans += [(e.name, float(e.start_ns), float(e.duration_ns))
                          for e in ln.events if e.name.startswith("bench.")]
    if not dev_ops:
        return {"devices": 0}
    busy, op_tot, mod_tot = 0.0, {}, {}
    kernel_ns, kernel_count = 0.0, 0
    gaps = []
    lo = min(s for ops in dev_ops for _, s, _ in ops)
    hi = max(s + d for ops in dev_ops for _, s, d in ops)
    for ops, mods in zip(dev_ops, dev_mods):
        merged = _union([(s, s + d) for _, s, d in ops])
        busy += sum(e - s for s, e in merged)
        for (_, e0), (s1, _) in zip(merged, merged[1:]):
            gaps.append((e0, s1))
        for name, _, d in ops:
            short = op_kind(name)
            op_tot[short] = op_tot.get(short, 0.0) + d
            if is_kernel(name):
                kernel_ns += d
                kernel_count += 1
        for name, _, d in mods:
            c, t = mod_tot.get(name, (0, 0.0))
            mod_tot[name] = (c + 1, t + d)
    n = len(dev_ops)
    spans.sort(key=lambda x: x[1])
    starts = [a for _, a, _ in spans]
    attributed = {}
    for s, e in gaps:
        if e - s < SHORT_GAP_NS:
            key = "gaps under 10 us"
        else:
            mid = 0.5 * (s + e)
            i = bisect.bisect_right(starts, mid) - 1
            key = (spans[i][0] if i >= 0 and mid <= starts[i] + spans[i][2]
                   else "no bench span")
        attributed[key] = attributed.get(key, 0.0) + (e - s) / n
    return {
        "devices": n,
        "window_ns": float(window_ns) if window_ns else hi - lo,
        "busy_ns": busy / n,
        "ops": {k: v / n for k, v in op_tot.items()},
        "modules": {k: (c / n, t / n) for k, (c, t) in mod_tot.items()},
        "kernel_ns": kernel_ns / n,
        "kernel_count": kernel_count / n,
        "idle_by_host_span": attributed,
    }


def module_total(summary: dict, marker: str):
    """(count, total ns) of programs whose name contains ``marker``."""
    c = t = 0.0
    for name, (n, d) in summary.get("modules", {}).items():
        if marker in name:
            c += n
            t += d
    return c, t


def breakdown(summary: dict, top: int = 10) -> dict:
    ops = sorted(summary.get("ops", {}).items(), key=lambda x: -x[1])[:top]
    gaps = sorted(summary.get("idle_by_host_span", {}).items(),
                  key=lambda x: -x[1])[:top]
    return {"device_ops": [[k, v * 1e-9] for k, v in ops],
            "idle_gaps": [[k, v * 1e-9] for k, v in gaps]}
