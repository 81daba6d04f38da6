"""SDXL base 1.0 enters the benchmark with new files only.

* ``configs/sdxl-base-1024.json`` resolves to ``reference_sdxl`` and
  ``flops_sdxl``; the guard admits it, and would refuse it by name with
  the default ``reference.py``.
* ``system.pipeline_config`` builds the program's SDXL presets field for
  field, at full size and at the CPU's.
* ``image_flops`` and ``attention_calls`` are pinned.
* At the CPU's size the cell runs through the unedited harness, traced,
  and reads ``correct``; the ``int8`` control, with the weights left as
  stored (``control_inplace.py``), computes what ``control.py``'s does and
  fails the check.
"""
from __future__ import annotations

import copy
import json
import os
import time

import numpy as np
import pytest

from conftest import BENCH, PEAKS, write_root

import check
import config_modules
import control
import control_inplace
import harness
import system
import weights

QUEUE = {"kind": "standing_queue", "ramp_generations": 1.0}

# image_flops; the count, FLOPs and bytes of one slot step's attention
# calls at 2 slots
PINNED = (345839381301248, 140, 3135661670400, 10349977600)


def _config() -> dict:
    with open(os.path.join(BENCH, "configs", "sdxl-base-1024.json")) as f:
        return json.load(f)


def _smoke() -> dict:
    """The file cut to the program's ``sdxl_base.SMOKE`` sizes."""
    cfg = _config()
    cfg["denoiser"].update(
        block_channels=[32, 64, 64], transformer_depth=[1, 1, 2],
        num_heads=[2, 4, 4], context_dim=80, text_len=8, time_dim=64,
        latent_size=16, groups=8, addition_time_dim=8, pooled_dim=40,
        addition_time_ids=[128, 128, 0, 0, 128, 128])
    cfg["text"].update(vocab_size=256, max_len=8, d_model=32, num_layers=2,
                       num_heads=4, d_ff=64)
    cfg["text"]["second"].update(vocab_size=256, max_len=8, d_model=48,
                                 num_layers=2, num_heads=4, d_ff=96,
                                 projection_dim=40)
    cfg["vae"].update(channels=[32, 32, 16, 16], groups=8)
    cfg["sampler"].update(num_inference_steps=3, tips_active_iters=2)
    cfg["serve"]["slots"] = 2
    return cfg


def test_the_file_resolves_to_its_own_modules():
    import flops_sdxl
    import reference
    import reference_sdxl

    cfg = _config()
    assert config_modules.reference(cfg) is reference_sdxl
    assert config_modules.counts(cfg) is flops_sdxl
    config_modules.refuse_unimplemented(cfg)
    refused = " ".join(config_modules.unimplemented(cfg,
                                                    reference.IMPLEMENTS))
    for phrase in ("denoiser.has_mid_block = True",
                   "denoiser.transformer_depth = [1, 2, 10]",
                   "denoiser.num_heads = [5, 10, 20]",
                   "the key denoiser.addition_embed",
                   "the key text.second", "the key vae.dtype"):
        assert phrase in refused, refused


@pytest.mark.parametrize("size", ["full", "smoke"])
def test_pipeline_config_builds_the_program_preset(size):
    from repro.configs import sdxl_base
    from repro.core.policies import ServePolicies

    cfg = _config() if size == "full" else _smoke()
    preset = sdxl_base.CONFIG if size == "full" else sdxl_base.SMOKE
    got = system.pipeline_config(cfg)
    want = ServePolicies.parse(kernels="auto", tips="fixed,threshold=0.05",
                               reuse="off").apply(preset)
    for group in ("unet", "text", "vae", "ddim"):
        a, b = getattr(got, group), getattr(want, group)
        for name in a.__dataclass_fields__:
            assert getattr(a, name) == getattr(b, name), (group, name)
    assert got == want


def test_counts_are_pinned():
    cfg = _config()
    counts = config_modules.counts(cfg)
    calls = counts.attention_calls(cfg, 2)
    assert (counts.image_flops(cfg), len(calls), sum(c[1] for c in calls),
            sum(c[2] for c in calls)) == PINNED
    assert [k for k, _, _ in calls] == ["self", "cross"] * 70
    # one row of the UNet: 6.76 TFLOP, 76 % of it in the 70 stacks
    f = counts.unet_flops(cfg["denoiser"], 77)
    row = f["prefix"] + f["rest"]
    stacks = (2 * counts.stack_flops(4096, 640, 2, cfg["denoiser"], 77)
              + 6 * counts.stack_flops(1024, 1280, 10, cfg["denoiser"], 77)
              + 3 * counts.stack_flops(4096, 640, 2, cfg["denoiser"], 77))
    assert round(row / 1e12, 2) == 6.76
    assert 0.75 < stacks / row < 0.77


def test_the_cell_runs_and_reads_correct_on_the_cpu(tmp_path, monkeypatch):
    monkeypatch.setattr(harness, "TRACE_MAX_S", 0.2)
    spec = write_root(tmp_path, _smoke(), QUEUE)
    names = [m["name"] for m in spec["per_layer"]
             if spec["workloads"][0]["name"] in m.get("workloads", [])]
    assert "encode_ms" in names
    res = harness.run_cell(spec, spec["workloads"][0], seed=2**37 + 21,
                           seconds=2.0, trace=True,
                           t_start=time.perf_counter(), root=str(tmp_path),
                           require_compiled=False, peaks=PEAKS)
    assert res["correct"] is True, res["checks"]


def test_encode_ms_reads_the_text_encode_programs():
    read = harness.reader("encode_ms")
    modules = {"jit_text_encode": (8.0, 12e6), "jit__slot_step_traced":
               (4.0, 400e6)}
    assert read({"trace": {"modules": modules}}) == 1.5
    assert read({"trace": {"modules": {}}}) is None
    assert read({"trace": None}) is None


def test_the_inplace_int8_control_is_control_py_s_and_fails(monkeypatch):
    import traffic

    cfg = _smoke()
    w = weights.make(system.abstract_weights(cfg), 2**37 + 22)
    content = traffic.content(cfg, 1, 2**37 + 22)
    got = [fn(cfg, w, *content, "int8")[0]
           for fn in (control.trajectories, control_inplace.trajectories)]
    for a, b in zip(*[g["steps"] + [g["image"]] for g in got]):
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-6)
    monkeypatch.setattr(control, "trajectories", control_inplace.trajectories)
    rows = control.readings(copy.deepcopy(cfg), seeds=[2**37 + 23],
                            n_requests=1, mode="int8")
    assert not check.passed(rows[0]["checks"])
