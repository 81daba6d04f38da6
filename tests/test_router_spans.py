"""Host spans of the served path, on the profiler's own trace.

``ClusterRouter.stream`` and the engine's slot methods record
``jax.profiler.TraceAnnotation`` spans (``router.*``, ``engine.*``), so a
profiler trace shows what the host did between device programs, on the
device planes' clock.  A short stream is served under the profiler on the
CPU and its ``.xplane.pb`` read back:

  * spans nest by layer: a round holds one slot step, which holds one
    wait; an admission holds the engine's admit and its two text encodes
    under CFG; a retirement holds the decode and the retire;
  * every span that serves one request carries its ``rid``;
  * no program span takes a ``bench.`` name (the benchmark's own);
  * the profiler changes no image.

Also pinned: the program names a trace reduction keys on.
"""
import dataclasses
import glob
import os
import re
import warnings

import jax
import numpy as np
import pytest

from repro.diffusion.engine import DiffusionEngine
from repro.diffusion.pipeline import PipelineConfig
from repro.launch.router import ClusterRouter
from repro.launch.scheduler import make_requests

N_REQUESTS = 3


@pytest.fixture(scope="module")
def eng():
    base = PipelineConfig.smoke()
    cfg = dataclasses.replace(
        base, ddim=dataclasses.replace(base.ddim, guidance_scale=7.5))
    return DiffusionEngine(cfg, key=jax.random.PRNGKey(0))


def _serve(eng):
    router = ClusterRouter(eng, replicas=1, slots_per_replica=2)
    reqs = make_requests(eng.cfg, N_REQUESTS, seed=3)
    # the last request arrives late, so the router may wait for it
    reqs[-1].arrival_s = 0.5
    for _ in router.stream(reqs):
        pass
    return router, reqs


def _spans(trace_dir):
    """``[(name, start_ns, end_ns, args)]`` of the program's host spans."""
    from jax.profiler import ProfileData

    path = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                            recursive=True))[-1]
    out = []
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for e in line.events:
                if not e.name.startswith(("router.", "engine.", "bench.")):
                    continue
                # the stats type's first use warns that it has no module
                with warnings.catch_warnings():
                    warnings.simplefilter("ignore", DeprecationWarning)
                    args = dict(e.stats)
                out.append((e.name, e.start_ns, e.start_ns + e.duration_ns,
                            args))
    return out


@pytest.fixture(scope="module")
def served(eng, tmp_path_factory):
    """The same stream served with the profiler off, then on."""
    ClusterRouter(eng, replicas=1, slots_per_replica=2).warmup()
    _, plain = _serve(eng)
    trace_dir = str(tmp_path_factory.mktemp("trace"))
    with jax.profiler.trace(trace_dir):
        router, traced = _serve(eng)
    return {"router": router, "plain": plain, "traced": traced,
            "spans": _spans(trace_dir)}


def _named(spans, name):
    return [s for s in spans if s[0] == name]


def _inside(spans, outer, name):
    return [s for s in _named(spans, name)
            if outer[1] <= s[1] and s[2] <= outer[2]]


@pytest.mark.parametrize("outer,inner,count", [
    ("router.round", "engine.slot_step", 1),
    ("router.round", "engine.finished_slots", 1),
    ("engine.slot_step", "engine.step_wait", 1),
    ("router.admit", "engine.admit", 1),
    ("engine.admit", "engine.encode", 2),
    ("router.retire", "engine.decode", 1),
    ("router.retire", "engine.retire", 1),
])
def test_spans_nest_by_layer(served, outer, inner, count):
    outers = _named(served["spans"], outer)
    assert outers, f"no {outer} span"
    for span in outers:
        assert len(_inside(served["spans"], span, inner)) == count, span


def test_one_round_span_per_round_and_waits_between_rounds(served):
    spans = served["spans"]
    rounds = _named(spans, "router.round")
    assert len(rounds) == served["router"]._rounds
    # rounds follow one another and never overlap
    assert all(a[2] <= b[1] for a, b in zip(rounds, rounds[1:]))
    for wait in _named(spans, "router.wait_arrival"):
        assert not any(r[1] <= wait[1] and wait[2] <= r[2] for r in rounds)


@pytest.mark.parametrize("name", ["router.admit", "router.fetch"])
def test_each_request_has_one_span_carrying_its_rid(served, name):
    rids = sorted(r[3]["rid"] for r in _named(served["spans"], name))
    assert rids == sorted(r.rid for r in served["traced"])


@pytest.mark.parametrize("name,keys", [
    ("router.admit", {"rid"}),
    ("router.fetch", {"rid"}),
    ("router.round", set()),
    ("router.retire", set()),
    ("engine.decode", set()),
])
def test_spans_carry_only_the_request_id(served, name, keys):
    """A span argument is worked out on the hot path even with the
    profiler off, so a span carries only the ``rid`` that ties one
    request's spans together."""
    spans = _named(served["spans"], name)
    assert spans, f"no {name} span"
    for _, _, _, args in spans:
        assert set(args) == keys
        assert all(isinstance(v, int) for v in args.values())


def test_no_program_span_takes_a_benchmark_name(served):
    assert served["spans"]
    assert not [s for s in served["spans"] if s[0].startswith("bench.")]


def test_images_are_bit_identical_with_the_profiler_on(served):
    for a, b in zip(served["plain"], served["traced"]):
        assert a.image is not None and np.array_equal(a.image, b.image)


def _module_name(lowered) -> str:
    return re.match(r"module @(\S+)", lowered.as_text()).group(1)


def test_program_names_a_trace_reduction_keys_on(eng):
    """The trace names each program by its module: the text encode and
    the VAE decode have names of their own, and only the slot step's
    contains ``slot_step`` (its reader sums every match)."""
    state = eng.init_slots(2)
    toks = np.zeros((1, eng.cfg.text.max_len), np.int32)
    state = eng.admit(state, 0, toks, jax.random.PRNGKey(0),
                      uncond_tokens=toks)
    state = eng.slot_step(state)
    eng.decode_slots(state, [0])
    ctx = eng._encode_compiled()(toks)
    names = {
        "encode": _module_name(eng._encode_fn.lower(eng.text_params, toks)),
        "decode": _module_name(eng._decode_fn.lower(eng.vae_params,
                                                    state.latents[:1])),
        "admit": _module_name(eng._admit_compiled().lower(
            state, np.int32(1), ctx[0], state.latents[0], ctx[0],
            np.int32(0))),
        "step": _module_name(next(iter(eng._slot_compiled.values())).lower(
            eng.unet_params, state)),
    }
    assert names["encode"] == "jit_text_encode"
    assert names["decode"] == "jit_vae_decode"
    assert [k for k, v in names.items() if "slot_step" in v] == ["step"]
