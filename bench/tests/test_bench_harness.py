"""The harness on the CPU: generators, arithmetic, data-driven cells, the
result line, the trace reduction, the FLOP counts, and the refusal to run
without a TPU."""
from __future__ import annotations

import json
import os
import subprocess
import sys
import time
from types import SimpleNamespace as NS

import numpy as np
import pytest

from conftest import BENCH, PEAKS, ROOT, smoke_config, write_root

import device_trace
import flops
import harness
import traffic


# --------------------------------------------------------------------------
# traffic
# --------------------------------------------------------------------------
@pytest.mark.parametrize("mix", [{"kind": "standing_queue"},
                                 {"kind": "poisson", "rate_per_s": 3.0}])
def test_arrivals_are_deterministic_from_the_seed(mix):
    kw = dict(window_s=20.0, ramp_s=5.0, slots=4, rate_bound=2.0)
    a = traffic.arrivals(mix, seed=2**33 + 1, **kw)
    b = traffic.arrivals(mix, seed=2**33 + 1, **kw)
    c = traffic.arrivals(mix, seed=2**33 + 2, **kw)
    np.testing.assert_array_equal(a, b)
    assert len(a) == len(c)
    if mix["kind"] == "poisson":
        # the same gaps in another order: same load, same span
        assert not np.array_equal(a, c)
        np.testing.assert_allclose(np.sort(np.diff(a, prepend=0)),
                                   np.sort(np.diff(c, prepend=0)))
        assert a[-1] == pytest.approx(25.0)
    else:
        assert len(a) == 40 + 4
        np.testing.assert_allclose(a[:4], [0.0, 1.25, 2.5, 3.75])
        assert (a[4:] == 5.0).all()


def test_content_is_deterministic_from_the_seed():
    cfg = smoke_config("dit-s2-256")
    a = traffic.content(cfg, 3, 7)
    b = traffic.content(cfg, 3, 7)
    c = traffic.content(cfg, 3, 8)
    for x, y in zip(a, b):
        np.testing.assert_array_equal(x, y)
    assert not np.array_equal(a[2], c[2])
    assert a[0].shape == (3, 8) and a[2].shape == (3, 1, 16, 16, 4)


# --------------------------------------------------------------------------
# end-to-end arithmetic
# --------------------------------------------------------------------------
def test_image_rate_counts_the_work_and_time_of_whole_rounds():
    ends = [1.0, 2.0, 3.0, 4.0, 5.0, 6.0, 7.0, 8.5, 9.0, 10.0]
    # requests of 4 steps each, admitted before steps 0, 2 and 6
    firsts = [0, 2, 6]
    # steps ending in (2.5, 8.5] are 2..7, holding 2+2+1+1+1+1 request
    # steps, 2 images' worth, from the end of step 1 (2.0) to 8.5
    assert harness.image_rate(ends, firsts, 4, 2.5, 8.5) == pytest.approx(
        2 / 6.5)
    # a round that the window's end cuts counts neither work nor time
    assert harness.image_rate(ends, firsts, 4, 2.5, 8.4) == pytest.approx(
        1.75 / 5.0)
    # a full standing queue: every step holds all rows
    ends = list(np.arange(1, 101) * 0.5)
    firsts = [0, 0, 0] + [25 * k for k in range(1, 4) for _ in range(3)]
    assert harness.image_rate(ends, firsts, 25, 10.0, 40.0) == pytest.approx(
        3 / (25 * 0.5))
    assert harness.image_rate(ends, firsts, 25, 10.0, 10.4) is None


def test_tail_is_over_all_requests_not_a_median_of_chunks():
    lat = list(range(1, 101))
    p90 = harness.percentile(lat, 90)
    assert p90 == pytest.approx(90.1)
    chunks = [harness.percentile(lat[i:i + 10], 90) for i in range(0, 100, 10)]
    assert np.median(chunks) != pytest.approx(p90)


def test_metrics_are_chosen_per_cell_from_data():
    spec = harness.load_spec()
    for cell in spec["workloads"]:
        e2e, layer = harness.cell_metrics(spec, cell)
        names = {m["name"] for m in e2e}
        assert "setup_s" in names and len(names) >= 2
        assert layer and all(m["moves"] in names for m in layer)
        for m in layer:
            assert os.path.exists(os.path.join(BENCH, "metrics",
                                               f"{m['name']}.py"))


# --------------------------------------------------------------------------
# a cell from data alone, end to end on the CPU
# --------------------------------------------------------------------------
def test_a_cell_added_as_data_runs_and_prints_the_result(tmp_path):
    cfg = smoke_config("dit-s2-256")
    spec = write_root(tmp_path, cfg, {"kind": "standing_queue",
                                      "ramp_generations": 1.0})
    res = harness.run_cell(spec, spec["workloads"][0], seed=2**40 + 3,
                           seconds=2.0, trace=False,
                           t_start=time.perf_counter(), root=str(tmp_path),
                           require_compiled=False, peaks=PEAKS)
    assert list(res) == ["correct", "attempted", "failed", "metrics",
                         "device", "checks"]
    assert res["correct"] is True and res["failed"] == 0
    assert res["attempted"] > 0
    assert set(res["metrics"]) == {"images_per_s", "setup_s"}
    assert res["metrics"]["images_per_s"]["value"] > 0
    assert set(res["device"]) >= {"platform", "kind", "count",
                                  "memory_peak_bytes"}
    for c in res["checks"].values():
        assert set(c) == {"value", "limit"}
    json.dumps(res)


def test_a_traced_run_leaves_the_profilers_hold_out_of_the_window(
        tmp_path, monkeypatch):
    """The profiler's stop writes the trace out and holds the stream still.
    A stop that lasts past the window's end still leaves the window its
    length, and the check its sampled requests, drawn after the trace."""
    import jax

    cfg = smoke_config("dit-s2-256")
    spec = write_root(tmp_path, cfg, {"kind": "standing_queue",
                                      "ramp_generations": 1.0})
    stop = jax.profiler.stop_trace

    def slow_stop():
        stop()
        time.sleep(3.0)
    monkeypatch.setattr(jax.profiler, "stop_trace", slow_stop)
    monkeypatch.setattr(harness, "TRACE_MAX_S", 0.2)
    monkeypatch.setattr(harness, "sample_times",
                        lambda slots, w0, w1, gen, seed:
                        [w0 + 0.6 * (w1 - w0)] * slots)
    res = harness.run_cell(spec, spec["workloads"][0], seed=2**33 + 5,
                           seconds=2.0, trace=True,
                           t_start=time.perf_counter(), root=str(tmp_path),
                           require_compiled=False, peaks=PEAKS)
    assert res["correct"] is True, res["checks"]
    assert res["failed"] == 0
    assert 0 < res["device"]["window_s"] < 1.0


def test_without_a_tpu_the_command_exits_nonzero_and_prints_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    spec = harness.load_spec()
    cell = spec["workloads"][0]["name"]
    p = subprocess.run([sys.executable, "bench/run.py", "--workload", cell,
                        "--seed", "1", "--seconds", "1", "--trace", "0"],
                       cwd=ROOT, env=env, capture_output=True, text=True,
                       timeout=120)
    assert p.returncode != 0
    assert "{" not in p.stdout
    assert "TPU" in p.stderr


# --------------------------------------------------------------------------
# trace reduction, on a synthetic trace
# --------------------------------------------------------------------------
def _ev(name, start, dur):
    return NS(name=name, start_ns=start, duration_ns=dur)


def test_trace_reduction_busy_kernels_programs_and_idle_attribution():
    device = NS(name="/device:TPU:0", lines=[
        NS(name="XLA Modules", events=[
            _ev("jit__slot_step_traced(1)", 0, 600),
            _ev("jit__lambda(7)", 800, 100)]),
        NS(name="XLA Ops", events=[
            _ev("fusion.1", 0, 200), _ev('%pssa_attention_kernel.3 = f32[8] custom-call(f32[8] %x), '
                'custom_call_target="tpu_custom_call"', 150, 250),
            _ev("convolution.2", 400, 200), _ev("fusion.3", 800, 100)]),
    ])
    host = NS(name="/host:CPU", lines=[NS(name="python", events=[
        _ev("bench.finished_slots", 610, 150), _ev("other", 0, 5000)])])
    s = device_trace.reduce([device, host], window_ns=1000)
    assert s["busy_ns"] == 700             # [0, 600] and [800, 900]
    assert s["kernel_ns"] == 250 and s["kernel_count"] == 1
    assert s["modules"]["jit__slot_step_traced(1)"] == (1, 600)
    assert device_trace.module_total(s, "slot_step") == (1, 600)
    # the gap [600, 800] is under 10 us: counted as short
    assert s["idle_by_host_span"] == {"gaps under 10 us": 200}
    s2 = device_trace.reduce([NS(name="/device:TPU:0", lines=[
        NS(name="XLA Ops", events=[_ev("a", 0, 10), _ev("b", 50_010, 10)])]),
        NS(name="/host:CPU", lines=[NS(name="py", events=[
            _ev("bench.slot_step", 0, 60_000)])])])
    assert s2["idle_by_host_span"] == {"bench.slot_step": 50_000}
    run = {"trace": s, "window": (0, 1), "records": [], "finished": {},
           "peaks": PEAKS, "attention_calls": [("self", 1e3, 1e3)]}
    assert harness.reader("idle_share")(run) == pytest.approx(30.0)
    assert harness.reader("attn_share")(run) == pytest.approx(250 / 7)
    assert harness.reader("step_ms")(run) == pytest.approx(600e-6)
    assert harness.reader("offstep_share")(run) == pytest.approx(100 / 7)
    # least time 1 ns per step (bytes bound), one step, 250 ns of kernel
    # time: 0.4 %
    assert harness.reader("attn_roofline")(run) == pytest.approx(0.4)
    assert harness.reader("pssa_attention_roofline")(run) == pytest.approx(
        0.4)
    assert harness.reader("cross_attention_tips_roofline")(run) is None
    empty = {"trace": {}, "window": (0, 1), "records": [], "finished": {},
             "peaks": PEAKS}
    for name in ("idle_share", "attn_share", "step_ms", "offstep_share",
                 "attn_roofline"):
        assert harness.reader(name)(empty) is None


# --------------------------------------------------------------------------
# FLOP counts against XLA's own count of the reference
# --------------------------------------------------------------------------
def _xla_flops(fn, *args):
    import jax
    return jax.jit(fn).lower(*args).compile().cost_analysis()["flops"]


@pytest.mark.parametrize("name,smoke", [("bk-sdm-small-512", True),
                                        ("dit-s2-256", True),
                                        ("bk-sdm-small-512", False),
                                        ("dit-s2-256", False)])
def test_flop_counts_match_xla_cost_analysis(name, smoke):
    """Hand counts of matmuls and convolutions against XLA's count of the
    reference (which adds elementwise work and counts padded convolution
    borders as it computes them), at smoke size and at full width."""
    import jax
    import jax.numpy as jnp

    import reference
    from system import abstract_weights

    if smoke:
        cfg = smoke_config(name)
    else:
        with open(os.path.join(BENCH, "configs", f"{name}.json")) as f:
            cfg = json.load(f)
    ab = abstract_weights(cfg)
    den, text = cfg["denoiser"], cfg["text"]
    s, tl = den["latent_size"], text["max_len"]
    sds = jax.ShapeDtypeStruct
    lat = sds((1, s, s, 4), jnp.float32)
    ctx = sds((1, tl, text["d_model"]), jnp.float32)
    t = sds((1,), jnp.int32)
    eps = reference.EPS[den["family"]]
    xla = _xla_flops(lambda p, x, tt, c: eps(p, x, tt, c, den, True),
                     ab["denoiser"], lat, t, ctx)
    f = flops.denoiser_flops(den, tl)
    tol = 0.15 if smoke else 0.03
    assert f["prefix"] + f["rest"] == pytest.approx(xla, rel=tol)
    xla = _xla_flops(lambda p, x: reference.vae_decode(p, x, cfg["vae"]),
                     ab["vae"], lat)
    assert flops.vae_flops(cfg["vae"], s) == pytest.approx(xla, rel=tol)
    xla = _xla_flops(lambda p, x: reference.encode_text(p, x, text),
                     ab["text"], sds((1, tl), jnp.int32))
    assert flops.text_flops(text) == pytest.approx(xla, rel=tol)


def test_full_width_hand_counts():
    """Per-image counts at the published widths, worked out by hand."""
    with open(os.path.join(BENCH, "configs", "dit-s2-256.json")) as f:
        dit = json.load(f)
    # DiT-S/2 block at 256 tokens, d=384, 77 text tokens, GEGLU 4x
    t, d, tk, c = 256, 384, 77, 768
    block = 2 * (t * d * d * 8 + 2 * t * t * d + 2 * t * d * tk
                 + 2 * tk * c * d + t * d * 8 * d + t * 4 * d * d)
    f = flops.dit_flops(dit["denoiser"], tk)
    adaln = 2 * 384 * 9 * 384
    assert f["prefix"] + f["rest"] == (
        12 * (block + adaln) + 2 * 384 * 384 + 2 * 384 * 384
        + 2 * t * 16 * d + 2 * 384 * 2 * 384 + 2 * t * d * 16)
    # text tower: 12 layers at 77 tokens, d=768, MLP 3072
    tt, dd, ff = 77, 768, 3072
    assert flops.text_flops(dit["text"]) == 12 * 2 * (
        tt * dd * 3 * dd + 2 * tt * tt * dd + tt * dd * dd + 2 * tt * dd * ff)
