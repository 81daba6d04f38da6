"""Reduction of a profiler trace's program spans, on the device's clock.

The served path records host spans with ``jax.profiler.TraceAnnotation``
(``router.*`` in ``ClusterRouter.stream``, ``engine.*`` in the
``DiffusionEngine`` slot methods).  They land on the profiler's host
planes, on the clock of the device planes, so each idle gap of the chip
can be charged to what the host was doing.  ``bench.*`` spans (the
harness's own) are not program spans and are left to ``device_trace``.

``reduce(planes)`` gives, in nanoseconds on the trace's clock:

* ``spans``: per span name, ``count``, ``total_ns`` and ``self_ns`` (the
  duration less what the span's children cover; nesting is interval
  containment on one host thread);
* ``idle``: the chip's idle gaps of at least 10 us, by the innermost
  program span covering each gap's middle, else ``no program span``
  (shorter gaps under ``gaps under 10 us``); busy time is the union of
  operations, by ``device_trace``'s rule, averaged over device planes;
* ``round_idle_ns``: the idle time inside each ``router.round`` span that
  lies wholly within the extent of the device's operations;
* ``clock_check``: the share of slot-step programs (``XLA Modules``
  events) whose end lies inside an ``engine.step_wait`` span: the host
  waits for each step, so near 1 only if the two clocks agree.

A trace without program spans (a program that records none) reduces to
None, and every reader of it returns None.
"""
from __future__ import annotations

import bisect
import json
import sys

import device_trace

PREFIXES = ("router.", "engine.")
NO_SPAN = "no program span"
SHORT = "gaps under 10 us"


def planes_of(run):
    """The trace's planes for a reader's ``run`` context.

    ``run["planes"]`` where the harness puts them there; else the
    ``planes`` it loaded, taken from the frame of the harness call that
    built ``run`` (its reduction to ``run["trace"]`` keeps no host spans
    but its own).  None where neither exists."""
    if run.get("planes") is not None:
        return run["planes"]
    frame = sys._getframe(1)
    while frame is not None:
        if frame.f_locals.get("ctx") is run:
            return frame.f_locals.get("planes")
        frame = frame.f_back
    return None


def summary(run):
    """``reduce`` of the run's planes, computed once per run; the first
    call prints it on a ``bench:`` line."""
    if "program_spans" not in run:
        planes = planes_of(run)
        got = reduce(planes) if planes is not None else None
        run["program_spans"] = got
        if got is not None:
            print("bench: program spans " + json.dumps({
                "idle_s": {k: v * 1e-9 for k, v in sorted(
                    got["idle"].items(), key=lambda x: -x[1])},
                "spans": {k: {"count": v["count"],
                              "total_ms": v["total_ns"] * 1e-6,
                              "self_ms": v["self_ns"] * 1e-6}
                          for k, v in sorted(got["spans"].items())},
                "rounds_in_extent": len(got["round_idle_ns"]),
                "clock_check": got["clock_check"]}), flush=True)
    return run["program_spans"]


def mean_ms(run, name):
    """Mean duration of the program span ``name``, in ms; None without."""
    got = summary(run)
    s = (got or {}).get("spans", {}).get(name)
    return s["total_ns"] / s["count"] * 1e-6 if s else None


def _events(planes):
    """(program spans by host line, device busy intervals by device plane,
    slot-step program ends)."""
    lines, busy, step_ends = [], [], []
    for plane in planes:
        plane_lines = list(plane.lines)
        if plane.name.startswith("/device:"):
            names = {ln.name for ln in plane_lines}
            ops = []
            for ln in plane_lines:
                if ln.name == "XLA Modules":
                    step_ends += [e.start_ns + e.duration_ns
                                  for e in ln.events
                                  if "slot_step" in e.name]
                elif ln.name == "XLA Ops" or "XLA Ops" not in names:
                    ops += [(float(e.start_ns),
                             float(e.start_ns + e.duration_ns))
                            for e in ln.events]
            if ops:
                busy.append(device_trace._union(ops))
        else:
            for ln in plane_lines:
                spans = [(e.name, float(e.start_ns),
                          float(e.start_ns + e.duration_ns))
                         for e in ln.events if e.name.startswith(PREFIXES)]
                if spans:
                    lines.append(sorted(spans, key=lambda x: (x[1], -x[2])))
    return lines, busy, step_ends


def _self_times(spans, out):
    """Add count, total and self time of each span of one host line
    (sorted by start, longest first) to ``out``."""
    cover = [0.0] * len(spans)
    stack = []
    for i, (_, s, e) in enumerate(spans):
        while stack and spans[stack[-1]][2] < e:
            stack.pop()
        if stack:
            cover[stack[-1]] += e - s
        stack.append(i)
    for (name, s, e), c in zip(spans, cover):
        d = out.setdefault(name, {"count": 0, "total_ns": 0.0,
                                  "self_ns": 0.0})
        d["count"] += 1
        d["total_ns"] += e - s
        d["self_ns"] += e - s - c


def _innermost(spans, points):
    """For each of the sorted ``points``, the innermost span of one host
    line covering it, as (name, duration), or None."""
    out, stack, j = [], [], 0
    for p in points:
        while j < len(spans) and spans[j][1] <= p:
            while stack and stack[-1][2] < spans[j][2]:
                stack.pop()
            stack.append(spans[j])
            j += 1
        while stack and stack[-1][2] < p:
            stack.pop()
        out.append((stack[-1][0], stack[-1][2] - stack[-1][1])
                   if stack else None)
    return out


def _busy_within(merged, ends, s, e) -> float:
    """Busy time inside ``[s, e]`` of disjoint sorted intervals."""
    i = bisect.bisect_right(ends, s)
    t = 0.0
    while i < len(merged) and merged[i][0] < e:
        t += min(merged[i][1], e) - max(merged[i][0], s)
        i += 1
    return t


def reduce(planes):
    lines, busy, step_ends = _events(planes)
    if not lines:
        return None
    spans = {}
    for ln in lines:
        _self_times(ln, spans)
    idle, round_idle, clock = {}, [], None
    if busy:
        n = len(busy)
        for merged in busy:
            gaps = [(e0, s1) for (_, e0), (s1, _) in zip(merged, merged[1:])]
            mids = [0.5 * (a + b) for a, b in gaps]
            found = [_innermost(ln, mids) for ln in lines]
            for k, (a, b) in enumerate(gaps):
                if b - a < device_trace.SHORT_GAP_NS:
                    key = SHORT
                else:
                    hits = [f[k] for f in found if f[k] is not None]
                    key = min(hits, key=lambda x: x[1])[0] if hits else NO_SPAN
                idle[key] = idle.get(key, 0.0) + (b - a) / n
        lo = min(m[0][0] for m in busy)
        hi = max(m[-1][1] for m in busy)
        rounds = [(s, e) for ln in lines for name, s, e in ln
                  if name == "router.round" and lo <= s and e <= hi]
        ends = [[b for _, b in m] for m in busy]
        round_idle = [sum(e - s - _busy_within(m, me, s, e)
                          for m, me in zip(busy, ends)) / n
                      for s, e in rounds]
        waits = sorted((s, e) for ln in lines for name, s, e in ln
                       if name == "engine.step_wait")
        if waits and step_ends:
            starts = [s for s, _ in waits]
            inside = 0
            for t in step_ends:
                i = bisect.bisect_right(starts, t) - 1
                inside += i >= 0 and t <= waits[i][1]
            clock = inside / len(step_ends)
    return {"spans": spans, "idle": idle, "round_idle_ns": round_idle,
            "clock_check": clock}
