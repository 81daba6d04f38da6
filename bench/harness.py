"""One run of one cell: set-up, the measured window, the check, the result.

Everything a cell needs is found by name from data: the cell in
``BENCHMARK.json``, its configuration file with the reference and counts
modules it names (``config_modules.py``), its traffic mix
(``traffic/<name>.json``) and one reader per per-layer metric
(``metrics/<name>.py``).  Adding a cell, a mix, a configuration or a
metric adds files and entries; no code here names one.

The window is ``--seconds`` long on the serving clock and starts once the
traffic's ramp (one generation) has passed; set-up (``setup_s``) is
everything from the start of the process to the window's start: loading,
weights, compiling or loading every program, the ramp.  A traced run
traces the window's first ``TRACE_MAX_S``; the profiler's start and stop
hold the stream still, and their time is left out of the window (the
window's end and the check's sample start times move on by it), so a
traced run serves as long a window as an untraced one.
"""
from __future__ import annotations

import gc
import importlib.util
import json
import os
import shutil
import time

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
CACHE_DIR = os.path.join(ROOT, ".jax_cache")
DRAIN_LIMIT_S = 120.0           # wait for requests due in the window
TRACE_MAX_S = 10.0              # a traced run traces the window's start


# --------------------------------------------------------------------------
# data
# --------------------------------------------------------------------------
def load_spec(root: str = ROOT) -> dict:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


def find_cell(spec: dict, name: str) -> dict:
    for cell in spec["workloads"]:
        if cell["name"] == name:
            return cell
    raise KeyError(f"no workload {name!r} in BENCHMARK.json "
                   f"(have {[c['name'] for c in spec['workloads']]})")


def load_config(spec: dict, cell: dict, root: str = ROOT) -> dict:
    for c in spec["configs"]:
        if c["name"] == cell["config"]:
            with open(os.path.join(root, c["file"])) as f:
                return json.load(f)
    raise KeyError(f"no config {cell['config']!r} in BENCHMARK.json")


def cell_metrics(spec: dict, cell: dict) -> tuple:
    """(end-to-end, per-layer) metric entries this cell reports."""
    def ours(m):
        return "workloads" not in m or cell["name"] in m["workloads"]
    return ([m for m in spec["end_to_end"] if ours(m)],
            [m for m in spec["per_layer"] if ours(m)])


def load_peaks(kind: str) -> dict:
    with open(os.path.join(HERE, "peaks.json")) as f:
        peaks = json.load(f)
    if kind not in peaks:
        raise KeyError(f"device kind {kind!r} is not in peaks.json "
                       f"(have {sorted(peaks)})")
    return peaks[kind]


def reader(name: str, root: str = HERE):
    path = os.path.join(root, "metrics", f"{name}.py")
    spec = importlib.util.spec_from_file_location(f"bench_metric_{name}",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


# --------------------------------------------------------------------------
# end-to-end arithmetic
# --------------------------------------------------------------------------
def percentile(values, q: float) -> float:
    """Linear-interpolated percentile over every value (no chunking)."""
    return float(np.percentile(np.asarray(values, np.float64), q))


def image_rate(step_ends, first_steps, n_steps: int, start: float,
               end: float):
    """Images' worth of denoising completed per second over the whole
    rounds of the window ``[start, end]``.

    ``step_ends`` are the ends of the slot steps on the serving clock,
    ``first_steps`` the index of each admitted request's first step; a
    request takes part in ``n_steps`` steps from there, each 1/n_steps of
    its image.  The rounds counted are those whose step ends inside the
    window, and the time is theirs: from the end of the step before the
    first of them to the end of the last, so a round cut by the window's
    edge counts neither its work nor its time.  Every round's host work
    (admission, retirement decode, readback) lies in that span.  Returns
    None where the window holds fewer than two rounds.
    """
    t = np.asarray(step_ends, np.float64)
    ks = np.flatnonzero((t > start) & (t <= end))
    if len(ks) < 2:
        return None
    k0, k1 = int(ks[0]), int(ks[-1])
    rows = np.zeros(len(t) + 1, np.int64)
    for f in first_steps:
        rows[f] += 1
        rows[min(f + n_steps, len(t))] -= 1
    rows = np.cumsum(rows)[:len(t)]            # rows stepped by step k
    t_from = t[k0 - 1] if k0 > 0 else start
    return float(rows[k0:k1 + 1].sum()) / n_steps / (t[k1] - t_from)


def sample_times(slots: int, w0: float, w1: float, gen_s: float,
                 seed: int) -> list:
    """When each slot's sampled request may start: the check records, for
    every slot, the first request admitted into it at or after a time drawn
    from the seed in ``[w0 - gen_s, w1 - 3 gen_s]``.  Such a request is
    admitted within a generation and done within another, with a third to
    spare for rounds slower than the warm-up's step time, so the sample
    covers every slot that serves requests in the window."""
    lo = max(w0 - gen_s, 0.0)
    hi = max(w1 - 3.0 * gen_s, lo)
    return np.random.default_rng(seed).uniform(lo, hi, slots).tolist()


def owed(order: list, first_step: dict, n_run: int, n_steps: int) -> set:
    """Requests the served path owes an image by the time the stream
    stopped: every admitted request with a slot step run after its last
    one (the router yields ``finished`` before that step), and every
    request the FIFO router passed over for a later one.

    ``order`` is the rids in arrival order, ``first_step`` the index of
    each admitted request's first step, ``n_run`` the steps run in all.
    """
    due = {rid for rid, f in first_step.items() if f + n_steps < n_run}
    admitted = [i for i, rid in enumerate(order) if rid in first_step]
    skipped = {rid for rid in order[:admitted[-1]]
               if rid not in first_step} if admitted else set()
    return due | skipped


# --------------------------------------------------------------------------
# compile counters (JAX monitoring events)
# --------------------------------------------------------------------------
class CompileCounter:
    """Persistent-cache hits and misses (JAX monitoring events); with the
    cache on for every program, each compile or load is one of the two."""
    EVENTS = {"/jax/compilation_cache/cache_hits": "cache_hits",
              "/jax/compilation_cache/cache_misses": "cache_misses"}

    def __init__(self):
        from jax import monitoring
        self.counts = {"cache_hits": 0, "cache_misses": 0}
        monitoring.register_event_listener(self._event)

    def _event(self, event, **kw):
        if event in self.EVENTS:
            self.counts[self.EVENTS[event]] += 1

    def snapshot(self) -> dict:
        return dict(self.counts)


def use_compile_cache(path: str = CACHE_DIR) -> str:
    """JAX's persistent cache at a fixed path inside the checkout, every
    program cached, whatever ``JAX_COMPILATION_CACHE_DIR`` says: two
    checkouts share no cache."""
    import jax

    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def device_info() -> dict:
    import jax
    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def memory_peak_bytes():
    import jax
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in jax.devices()]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


# --------------------------------------------------------------------------
# the run
# --------------------------------------------------------------------------
def say(msg: str) -> None:
    print(f"bench: {msg}", flush=True)


def run_cell(spec: dict, cell: dict, *, seed: int, seconds: float,
             trace: bool, t_start: float, root: str = ROOT,
             require_compiled: bool = True, peaks=None, patch=None) -> dict:
    """Run one cell; returns the result object (the last stdout line).

    ``root`` holds ``BENCHMARK.json`` and the data files under ``bench/``.
    A configuration whose reference or counts module does not implement
    one of its keys is refused (``config_modules.Unimplemented``) before
    anything is set up.
    The tests run a cell on the CPU through three hooks: no check that
    the kernels are the compiled route, ``peaks`` given for a device the
    table does not have, and ``patch(system)``, which may replace parts of
    the served path before set-up to plant a fault.
    """
    import jax

    import check
    import config_modules
    import traffic
    import weights
    from system import StreamDeadline, System

    cfg = load_config(spec, cell, root)
    config_modules.refuse_unimplemented(cfg)
    counts = config_modules.counts(cfg)
    counter = CompileCounter()
    say(f"compile cache {use_compile_cache(os.path.join(root, '.jax_cache'))}")
    mix = traffic.load(cell["traffic"], os.path.join(root, "bench"))
    dev = device_info()
    system = System(cfg, lambda abstract: weights.make(abstract, seed),
                    spans=trace)
    policy = system.kernel_policy()
    say(f"kernel policy {json.dumps(policy, sort_keys=True)}")
    if require_compiled:
        ok = (policy["self_attention"] == "fused"
              and policy["cross_attention"] == "fused"
              and policy["interpret_resolved"] is False)
        if not ok:
            raise RuntimeError(f"the served kernels are not the compiled "
                               f"fused route: {policy}")
    if patch is not None:
        patch(system)
    warm_s = system.warmup()
    step_s = system.step_seconds()
    n_steps = cfg["sampler"]["num_inference_steps"]
    ramp_s = mix.get("ramp_generations", 1.0) * n_steps * step_s
    image_flops = counts.image_flops(cfg)
    peaks = peaks or load_peaks(dev["kind"])
    rate_bound = peaks["bf16_flops_per_s"] / image_flops
    due = traffic.arrivals(mix, seed=seed, window_s=seconds, ramp_s=ramp_s,
                           slots=system.slots, rate_bound=rate_bound)
    toks, uncond, lat = traffic.content(cfg, len(due), seed)
    reqs = system.requests(toks, uncond, lat, due)
    say(f"warm-up {warm_s:.3f} s, slot step {step_s * 1e3:.3f} ms at "
        f"{system.slots} slots, ramp {ramp_s:.3f} s, {len(reqs)} requests")
    before = counter.snapshot()

    w0, w1 = ramp_s, ramp_s + seconds
    trace_dir = os.path.join(root, ".bench_traces",
                             f"{cell['name']}-{seed}")
    shutil.rmtree(trace_dir, ignore_errors=True)
    tracing = traced_ns = None
    finished, first_step = {}, {}
    in_window = None
    wait_for = {r.rid for r in reqs if w0 <= r.arrival_s <= w1}
    gen = system.router.stream(reqs)
    t0 = time.perf_counter()
    queue = mix["kind"] == "standing_queue"
    system.serve(
        t0, t0 + w1 + (0.0 if queue else DRAIN_LIMIT_S),
        sample_times(system.slots, w0, w1, n_steps * step_s, seed), n_steps)
    trace_end = w0 + min(seconds, TRACE_MAX_S)
    held_s = []

    def held(fn):
        """Run ``fn``, which holds the stream still (the profiler's start
        or stop: the stop writes the trace out, tens of seconds for a
        DiT window), and leave its time out of the window; returns the
        clock before and after it."""
        nonlocal w1
        a = time.perf_counter()
        fn()
        b = time.perf_counter()
        system.pause(a - t0, b - a)
        w1 += b - a
        held_s.append(b - a)
        return a, b

    def start_trace():
        nonlocal tracing, trace_end
        _, tracing = held(lambda: jax.profiler.start_trace(trace_dir))
        trace_end = tracing - t0 + min(seconds, TRACE_MAX_S)

    def stop_trace(in_window=True):
        nonlocal traced_ns, traced_from, traced_to
        if tracing is not None and traced_ns is None:
            if in_window:
                t_stop, _ = held(jax.profiler.stop_trace)
            else:
                t_stop = time.perf_counter()
                jax.profiler.stop_trace()
            traced_ns = (t_stop - tracing) * 1e9
            traced_from, traced_to = tracing - t0, t_stop - t0

    traced_from = traced_to = None
    try:
        for ev in gen:
            clock = time.perf_counter() - t0
            if ev["event"] == "finished":
                finished[ev["rid"]] = ev["t_s"]
            elif ev["event"] == "admitted":
                first_step[ev["rid"]] = system.admitted(ev["rid"], ev["slot"],
                                                        ev["t_s"])
            if in_window is None and clock >= w0:
                in_window = counter.snapshot()
                if trace:
                    start_trace()
            if clock >= trace_end:
                stop_trace()
            if clock >= w1 and wait_for <= set(finished):
                break
    except StreamDeadline:
        pass
    stop_trace(in_window=False)
    gen.close()
    after = counter.snapshot()
    if in_window is None:
        raise RuntimeError("the stream ended before the window started")
    setup_s = t0 + w0 - t_start
    memory = memory_peak_bytes()
    if held_s:
        say(f"the profiler held the stream {json.dumps(held_s)} s "
            f"(start, stop), left out of the window")
    say(f"compile counts before the window {json.dumps(before)}, "
        f"inside it {json.dumps({k: after[k] - in_window[k] for k in after})}")

    # request records on the serving clock; what the path owes: every
    # request it should have answered by the stream's stop, and under
    # arrivals every request due in the window (drained after it)
    records = [{"arrival_s": r.arrival_s, "admitted_s": r.admitted_s,
                "finished_s": finished.get(r.rid)} for r in reqs]
    order = [r.rid for r in sorted(reqs, key=lambda r: (r.arrival_s, r.rid))]
    must = owed(order, first_step, len(system.step_ends), n_steps)
    if not queue:
        must |= wait_for
    attempted, failed = len(must), len(must - set(finished))
    tracked = system.recorded()
    sample = [{"tokens": r.tokens, "uncond": uncond, "x0": r.latents,
               "steps": tracked[r.rid],
               "image": r.image if r.rid in finished else None}
              for r in reqs if r.rid in tracked
              and (r.rid in finished or r.rid in must)]
    del reqs, gen
    weights_made = system.weights
    system.close()
    gc.collect()

    # ---- correctness: the reference teacher-forced along the sample
    t_ref = time.perf_counter()
    got = check.readings(cfg, weights_made, sample)
    due_in_window = [r for r in records if w0 <= r["arrival_s"] <= w1]
    finished_in_window = [t for t in finished.values() if w0 <= t <= w1]
    checks = check.checks(cfg, got, failed)
    correct = bool(sample) and check.passed(checks)
    say(f"reference along {len(sample)} sampled requests in "
        f"{time.perf_counter() - t_ref:.3f} s: readings {json.dumps(got)}")

    e2e, layer = cell_metrics(spec, cell)
    device = {"platform": dev["platform"], "kind": dev["kind"],
              "count": dev["count"], "memory_peak_bytes": memory}
    result = {"correct": correct, "attempted": attempted, "failed": failed}
    ctx = {"cfg": cfg, "slots": system.slots, "records": records,
           "window": (w0, w1), "finished": finished, "peaks": peaks,
           "image_flops": image_flops, "trace": None,
           "image_rate": lambda a, b: image_rate(
               system.step_ends, first_step.values(), n_steps, a, b)}
    if not trace:
        values = {"setup_s": setup_s}
        lat_s = [r["finished_s"] - r["arrival_s"] for r in due_in_window
                 if r["finished_s"] is not None]
        if lat_s:
            values["latency_p50_s"] = percentile(lat_s, 50)
            values["latency_p90_s"] = percentile(lat_s, 90)
        values["images_per_s"] = ctx["image_rate"](w0, w1)
        say(f"{len(due_in_window)} requests due in the window, "
            f"{len(lat_s)} finished; {len(finished_in_window)} images "
            f"finished in it, {len(system.step_ends)} slot steps in all; "
            f"{failed} of {attempted} owed requests unanswered")
        metrics = {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
                   for m in e2e if values.get(m["name"]) is not None}
    else:
        import device_trace
        planes = list(device_trace.load(trace_dir).planes)
        say("trace planes " + json.dumps(
            {p.name: [ln.name for ln in p.lines] for p in planes}))
        summary = device_trace.reduce(planes, traced_ns)
        shutil.rmtree(trace_dir, ignore_errors=True)
        ctx["trace"] = summary
        ctx["traced_window"] = (traced_from, traced_to)
        ctx["attention_calls"] = counts.attention_calls(cfg, system.slots)
        metrics = {}
        for m in layer:
            v = reader(m["name"])(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        device["busy_s"] = summary.get("busy_ns", 0.0) * 1e-9
        device["window_s"] = traced_ns * 1e-9
        result["breakdown"] = device_trace.breakdown(summary)
        say(f"traced programs {json.dumps(summary.get('modules', {}))}")
    result.update(metrics=metrics, device=device, checks=checks)
    return result
