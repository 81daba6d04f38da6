"""The program-span reduction and its readers, on a synthetic trace, and
on a traced run of a cell on the CPU."""
from __future__ import annotations

import time
from types import SimpleNamespace as NS

import pytest

from conftest import PEAKS, smoke_config, write_root

import device_trace
import harness
import program_spans

US = 1_000          # ns


def _ev(name, start, end):
    return NS(name=name, start_ns=start, duration_ns=end - start)


def _device():
    return NS(name="/device:TPU:0", lines=[
        NS(name="XLA Modules", events=[
            _ev("jit__slot_step_traced(1)", 100 * US, 150 * US),
            _ev("jit_vae_decode(2)", 200 * US, 204 * US),
            _ev("jit__slot_step_traced(1)", 300 * US, 350 * US),
            _ev("jit__slot_step_traced(1)", 400 * US, 405 * US)]),
        NS(name="XLA Ops", events=[
            _ev("fusion.1", 50 * US, 60 * US),
            _ev("fusion.2", 100 * US, 150 * US),
            _ev("convolution.3", 200 * US, 204 * US),
            _ev("fusion.4", 300 * US, 350 * US),
            _ev("fusion.5", 400 * US, 405 * US),
            _ev("fusion.6", 405 * US + 5, 406 * US)]),
    ])


PROGRAM = [
    _ev("router.round", 90 * US, 260 * US),
    _ev("router.admit", 92 * US, 99 * US),
    _ev("engine.admit", 93 * US, 98 * US),
    _ev("engine.encode", 93 * US + 500, 94 * US),
    _ev("engine.encode", 94 * US + 500, 95 * US),
    _ev("engine.slot_step", 99 * US, 152 * US),
    _ev("engine.step_wait", 101 * US, 151 * US),
    _ev("engine.finished_slots", 152 * US, 170 * US),
    _ev("router.retire", 170 * US, 199 * US),
    _ev("engine.decode", 171 * US, 174 * US),
    _ev("engine.retire", 176 * US, 178 * US),
    _ev("router.round", 265 * US, 360 * US),
    _ev("engine.slot_step", 280 * US, 355 * US),
    _ev("engine.step_wait", 290 * US, 352 * US),
    _ev("engine.finished_slots", 355 * US, 358 * US),
]
OTHER = [_ev("bench.slot_step", 99 * US, 152 * US),
         _ev("PjitFunction(step)", 99 * US, 100 * US)]


def _planes(program=True):
    events = (PROGRAM if program else []) + OTHER
    return [_device(), NS(name="/host:CPU", lines=[
        NS(name="python3", events=sorted(events,
                                         key=lambda e: e.start_ns))])]


def _run(planes):
    return {"trace": device_trace.reduce(planes), "planes": planes,
            "peaks": PEAKS}


def test_self_times_innermost_idle_round_idle_and_clock_check():
    got = program_spans.reduce(_planes())
    sp = got["spans"]
    # a round's self time is what no child covers: 170 - (7+53+18+29)
    assert sp["router.round"]["count"] == 2
    assert sp["router.round"]["self_ns"] == (63 + 95 - 75 - 3) * US
    assert sp["router.admit"]["self_ns"] == 2 * US
    assert sp["engine.admit"]["self_ns"] == 4 * US
    assert sp["engine.encode"] == {"count": 2, "total_ns": 1 * US,
                                   "self_ns": 1 * US}
    assert sp["engine.slot_step"]["self_ns"] == (3 + 13) * US
    assert sp["router.retire"]["self_ns"] == 24 * US
    # idle gaps by the innermost span over their middle: [60, 100] -> no
    # span (80), [150, 200] -> the retirement's own time (175), [204, 300]
    # -> the round's own time (252), [350, 400] -> none (375), 5 ns short
    assert got["idle"] == {program_spans.NO_SPAN: 90 * US,
                           "router.retire": 50 * US,
                           "router.round": 96 * US,
                           program_spans.SHORT: 5}
    # both rounds lie in the device extent [50, 406]: 170 - 54 and 95 - 50
    assert got["round_idle_ns"] == [116 * US, 45 * US]
    # two of the three steps end inside a wait
    assert got["clock_check"] == pytest.approx(2 / 3)


def test_innermost_prefers_the_deepest_covering_span():
    spans = sorted([("a", 0.0, 100.0), ("b", 10.0, 50.0),
                    ("c", 20.0, 30.0), ("d", 60.0, 70.0)],
                   key=lambda x: (x[1], -x[2]))
    got = program_spans._innermost(spans, [5, 25, 40, 65, 80, 150])
    assert [g and g[0] for g in got] == ["a", "c", "b", "d", "a", None]


def test_rounds_outside_the_device_extent_are_left_out():
    planes = _planes()
    planes[0].lines[1].events = planes[0].lines[1].events[1:]   # from 100
    got = program_spans.reduce(planes)
    assert got["round_idle_ns"] == [45 * US]


@pytest.mark.parametrize("name,value", [
    ("round_idle_ms", (116 + 45) / 2 * 1e-3),
    ("admit_ms", 7e-3),
    ("readback_ms", (18 + 3) / 2 * 1e-3),
    ("retire_ms", 29e-3),
    ("decode_ms", 4e-3),
])
def test_readers_give_the_hand_computed_value_and_none_without_spans(
        name, value):
    assert harness.reader(name)(_run(_planes())) == pytest.approx(value)
    bare = _planes(program=False)
    bare[0].lines[0].events = bare[0].lines[0].events[:1]   # no decode
    assert harness.reader(name)(_run(bare)) is None


def test_device_trace_reduction_is_the_same_with_or_without_program_spans():
    assert (device_trace.reduce(_planes(), 500 * US)
            == device_trace.reduce(_planes(program=False), 500 * US))


def test_planes_are_found_in_the_frame_that_built_the_context():
    planes = _planes()
    ctx = {"trace": device_trace.reduce(planes), "peaks": PEAKS}
    assert program_spans.planes_of(ctx) is planes
    assert harness.reader("admit_ms")(ctx) == pytest.approx(7e-3)
    assert program_spans.planes_of({}) is None


def test_a_traced_run_reports_the_host_span_metrics(tmp_path, monkeypatch,
                                                    capsys):
    """Through the harness as it is: the readers find the planes it
    loaded.  The CPU has no device planes, so only the host spans'
    durations are read."""
    cfg = smoke_config("dit-s2-256")
    spec = write_root(tmp_path, cfg, {"kind": "standing_queue",
                                      "ramp_generations": 1.0})
    monkeypatch.setattr(harness, "TRACE_MAX_S", 1.0)
    res = harness.run_cell(spec, spec["workloads"][0], seed=2**35 + 9,
                           seconds=2.0, trace=True,
                           t_start=time.perf_counter(), root=str(tmp_path),
                           require_compiled=False, peaks=PEAKS)
    assert res["correct"] is True, res["checks"]
    for name in ("admit_ms", "readback_ms", "retire_ms"):
        assert res["metrics"][name]["value"] > 0, name
    assert "round_idle_ms" not in res["metrics"]
    assert "bench: program spans " in capsys.readouterr().out
