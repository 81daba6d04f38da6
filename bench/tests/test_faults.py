"""A whole run on the CPU with the timed path broken underneath: ``correct``
has to come out false for each fault a serving cell can have, and true for
the unbroken path.

The faults are planted in the served program through ``run_cell``'s
``patch`` hook, after the weights are made and before set-up:

* ``stuck_step``: the slot step returns its state unchanged (no request
  advances, so none finishes: every request owed an image is unanswered,
  and the sampled ones read inf);
* ``half_batch``: the slot step denoises the first half of the slots and
  leaves the other half's latents as they were, while every row's step
  counter moves on;
* ``wrong_row``: retirement decodes the neighbouring slot's latents, an
  answer altered where it is produced;
* ``altered_token``: admission encodes the prompt with its last token
  changed, a token altered where it is produced;
* ``dropped_image``: every other retirement's image never comes back,
  while the slots step on as before.

The cells of one chip have no exchange between chips to leave out.
"""
from __future__ import annotations

import dataclasses
import time

import numpy as np
import pytest

from conftest import PEAKS, smoke_config, write_root

import harness


def _stuck_step(system):
    eng = system.engine
    system.engine.slot_step = lambda state: (setattr(eng, "last_wall_s", 0.0)
                                             or state)


def _half_batch(system):
    eng = system.engine
    step = eng.slot_step

    def half(state):
        h = state.latents.shape[0] // 2
        kept = state.latents[h:]          # the step donates its input
        new = step(state)
        return dataclasses.replace(new, latents=new.latents.at[h:].set(kept))
    eng.slot_step = half


def _wrong_row(system):
    eng = system.engine
    decode = eng.decode_slots

    def decode_slots(state, slots=None):
        n = state.latents.shape[0]
        return decode(state, [(s + 1) % n for s in slots])
    eng.decode_slots = decode_slots


def _altered_token(system):
    eng = system.engine
    admit = eng.admit

    def altered(state, slot, tokens, key, **kw):
        tokens = np.array(tokens)
        tokens[:, -1] = (tokens[:, -1] + 1) % 256
        return admit(state, slot, tokens, key, **kw)
    eng.admit = altered


def _dropped_image(system):
    stream = system.router.stream

    def lossy(requests):
        n = 0
        for ev in stream(requests):
            if ev["event"] == "finished":
                n += 1
                if n % 2 == 0:
                    continue
            yield ev
    system.router.stream = lossy


FAULTS = {"stuck_step": _stuck_step, "half_batch": _half_batch,
          "wrong_row": _wrong_row, "altered_token": _altered_token,
          "dropped_image": _dropped_image, "none": None}


@pytest.mark.parametrize("fault", list(FAULTS))
@pytest.mark.parametrize("kind", ["standing_queue", "poisson"])
def test_correct_is_false_exactly_when_the_path_is_broken(tmp_path, fault,
                                                          kind, monkeypatch):
    monkeypatch.setattr(harness, "DRAIN_LIMIT_S", 3.0)
    cfg = smoke_config("dit-s2-256")
    mix = {"kind": kind, "ramp_generations": 1.0, "rate_per_s": 20.0}
    spec = write_root(tmp_path, cfg, mix)
    res = harness.run_cell(spec, spec["workloads"][0], seed=2**36 + 9,
                           seconds=1.5, trace=False,
                           t_start=time.perf_counter(), root=str(tmp_path),
                           require_compiled=False, peaks=PEAKS,
                           patch=FAULTS[fault])
    assert res["correct"] is (fault == "none"), res["checks"]
    c = res["checks"]
    over = {k for k, v in c.items() if v["value"] > v["limit"]}
    if fault == "wrong_row":
        assert "decode_rel_l2_max" in over
    if fault == "altered_token":
        assert "step_upd_rel_err_max" in over
    if fault in ("stuck_step", "dropped_image"):
        assert res["failed"] > 0 and "requests_unfinished" in over
    else:
        assert res["failed"] == 0 and res["attempted"] > 0
