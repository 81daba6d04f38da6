"""A configuration brings its own reference and counts modules, is held to
what they implement, and is built into any denoiser family the program
registers, with no edit to the harness.

* The two accepted configurations resolve to ``reference`` and ``flops``
  and read the counts they read before modules could be named.
* A configuration under ``tests/fixtures/`` names recording wrappers of
  those modules; the harness's counts, the check and the control read
  through them.
* A key or value its modules do not implement is refused by name before
  any weights are made.
* ``system.pipeline_config`` builds a family that only the test registers,
  nested groups, and a group for any ``PipelineConfig`` field.
"""
from __future__ import annotations

import dataclasses
import json
import os
import time

import pytest

from conftest import BENCH, PEAKS, smoke_config, write_root

import check
import config_modules
import control
import harness
import system
import weights

QUEUE = {"kind": "standing_queue", "ramp_generations": 1.0}

# image_flops, then the count, FLOPs and bytes of one slot step's attention
# calls at the file's slots, as the counts read before a configuration
# could name its modules
PINNED = {"bk-sdm-small-512": (26441367887872, 18, 518929776640, 1281773568),
          "dit-s2-256": (1528420671488, 24, 24335351808, 489259008)}


def _config(name: str) -> dict:
    with open(os.path.join(BENCH, "configs", f"{name}.json")) as f:
        return json.load(f)


# --------------------------------------------------------------------------
# (a) the accepted configurations read what they read
# --------------------------------------------------------------------------
@pytest.mark.parametrize("name", sorted(PINNED))
def test_accepted_configurations_resolve_to_the_default_modules(name):
    import flops
    import reference

    cfg = _config(name)
    assert "reference" not in cfg and "counts" not in cfg
    assert config_modules.reference(cfg) is reference
    assert config_modules.counts(cfg) is flops
    config_modules.refuse_unimplemented(cfg)
    counts = config_modules.counts(cfg)
    calls = counts.attention_calls(cfg, cfg["serve"]["slots"])
    assert (counts.image_flops(cfg), len(calls), sum(c[1] for c in calls),
            sum(c[2] for c in calls)) == PINNED[name]


@pytest.mark.parametrize("name", sorted(PINNED))
def test_accepted_configurations_build_the_pipeline_config_they_built(name):
    """The program's config as it was built from two named families and
    four flat groups, field by field."""
    from repro.diffusion.dit import DiTConfig
    from repro.diffusion.sampler import DDIMConfig
    from repro.diffusion.text_encoder import TextEncoderConfig
    from repro.diffusion.unet import UNetConfig
    from repro.diffusion.vae import VAEConfig

    cfg = _config(name)
    pipe = system.pipeline_config(cfg)

    def flat(klass, group):
        return klass(**{k: tuple(v) if isinstance(v, list) else v
                        for k, v in group.items()})
    den = {k: v for k, v in cfg["denoiser"].items()
           if k not in ("family", "tips_threshold")}
    klass = {"unet": UNetConfig, "dit": DiTConfig}[cfg["denoiser"]["family"]]
    want = flat(klass, den)
    assert type(pipe.unet) is klass
    for f in dataclasses.fields(klass):
        if f.name not in ("kernel_policy", "precision", "reuse_policy"):
            assert getattr(pipe.unet, f.name) == getattr(want, f.name), f.name
    assert pipe.unet.precision.threshold == cfg["denoiser"]["tips_threshold"]
    assert pipe.text == flat(TextEncoderConfig, cfg["text"])
    assert pipe.vae == flat(VAEConfig, cfg["vae"])
    assert pipe.ddim == flat(DDIMConfig, cfg["sampler"])


# --------------------------------------------------------------------------
# (b) a configuration with modules of its own, read by every caller
# --------------------------------------------------------------------------
def test_a_configuration_brings_its_own_reference_and_counts(tmp_path,
                                                            monkeypatch):
    import flops
    import reference

    cfg = smoke_config("recording", folder=os.path.join("tests", "fixtures"))
    ref = config_modules.reference(cfg)
    counts = config_modules.counts(cfg)
    assert ref is config_modules.load("tests/fixtures/recording_reference")
    assert counts is config_modules.load("tests/fixtures/recording_counts")
    assert ref is not reference and counts is not flops
    ref.CALLS.clear()
    counts.CALLS.clear()

    # the harness: its counts in set-up and for the traced readers, and
    # the check after the window
    monkeypatch.setattr(harness, "TRACE_MAX_S", 0.2)
    spec = write_root(tmp_path, cfg, QUEUE)
    res = harness.run_cell(spec, spec["workloads"][0], seed=2**37 + 11,
                           seconds=2.0, trace=True,
                           t_start=time.perf_counter(), root=str(tmp_path),
                           require_compiled=False, peaks=PEAKS)
    assert res["correct"] is True, res["checks"]
    assert counts.CALLS == ["image_flops", "attention_calls"]
    assert ref.CALLS == ["Pipeline"]

    # the control: the reference in the program's place, its int8
    # rounding, and the check that judges it
    ref.CALLS.clear()
    rows = control.readings(cfg, seeds=[2**37 + 12], n_requests=1,
                            mode="int8")
    assert ref.CALLS == ["Pipeline", "operands", "Pipeline"]
    assert not check.passed(rows[0]["checks"])


def test_a_module_name_stays_under_bench():
    for name in ("../reference", "/abs/reference", "tests/../flops", ""):
        with pytest.raises(ValueError, match="not a path under bench"):
            config_modules.load(name)


# --------------------------------------------------------------------------
# (c) the guard: keys and values the modules do not implement
# --------------------------------------------------------------------------
@pytest.mark.parametrize("group,key,value", [
    ("denoiser", "has_mid_block", True),
    ("denoiser", "transformer_depth", 2),
    ("denoiser", "num_heads", [5, 10, 20]),
    ("denoiser", "addition_embed_type", "text_time"),
    ("text", "projection_dim", 1280),
    ("sampler", "num_inference_steps", 25.0),
])
def test_the_guard_names_each_key_the_modules_do_not_implement(group, key,
                                                               value):
    cfg = _config("bk-sdm-small-512")
    cfg[group][key] = value
    with pytest.raises(config_modules.Unimplemented) as e:
        config_modules.refuse_unimplemented(cfg)
    msg = str(e.value)
    assert f"{group}.{key}" in msg
    assert "module 'reference'" in msg and "module 'flops'" in msg


def test_the_guard_names_what_one_module_alone_does_not_implement():
    cfg = _config("dit-s2-256")
    cfg["sampler"]["guidance_scale"] = 1.0
    with pytest.raises(config_modules.Unimplemented) as e:
        config_modules.refuse_unimplemented(cfg)
    msg = str(e.value)
    assert "sampler.guidance_scale = 1.0" in msg
    assert "module 'flops'" in msg and "module 'reference'" not in msg
    cfg["denoiser"]["family"] = "sdxl"
    cfg["text_2"] = dict(cfg.pop("text"))
    with pytest.raises(config_modules.Unimplemented,
                       match=r"denoiser.family = 'sdxl' \(it implements "
                             r"\['dit', 'unet'\]\); .*reads the group "
                             r"'text', which the configuration lacks; "
                             r".*implement the group 'text_2'"):
        config_modules.refuse_unimplemented(cfg)


@pytest.mark.parametrize("key,value", [("has_mid_block", True),
                                       ("transformer_depth", 2)])
def test_run_cell_and_control_refuse_before_any_weights(tmp_path, monkeypatch,
                                                        capsys, key, value):
    made = []
    monkeypatch.setattr(weights, "make", lambda *a, **kw: made.append(a))
    cfg = smoke_config("bk-sdm-small-512")
    cfg["denoiser"][key] = value
    spec = write_root(tmp_path, cfg, QUEUE)
    with pytest.raises(config_modules.Unimplemented,
                       match=f"'reference' does not implement denoiser.{key}"):
        harness.run_cell(spec, spec["workloads"][0], seed=1, seconds=1.0,
                         trace=False, t_start=time.perf_counter(),
                         root=str(tmp_path), require_compiled=False,
                         peaks=PEAKS)
    with pytest.raises(config_modules.Unimplemented, match=f"denoiser.{key}"):
        control.readings(cfg, seeds=[1], n_requests=1, mode="bf16")
    monkeypatch.setattr(control, "HERE", str(tmp_path / "bench"))
    assert control.main(["--config", "smoke", "--seeds", "1"]) == 1
    err = capsys.readouterr().err
    assert f"denoiser.{key}" in err and "'reference'" in err
    assert made == []


# --------------------------------------------------------------------------
# (d) the builder: any registered family, nested groups, any field
# --------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class _Inner:
    width: int = 1
    widths: tuple = ()


@dataclasses.dataclass(frozen=True)
class _StubConfig:
    depth: int = 1
    inner: _Inner = _Inner()
    label: str = ""
    kernel_policy: object = None
    precision: object = None
    reuse_policy: object = None


@pytest.fixture
def stub_family(monkeypatch):
    """A denoiser family that only this test registers."""
    from repro.diffusion import denoiser

    denoiser._ensure_registered()
    monkeypatch.setattr(denoiser, "_REGISTRY", dict(denoiser._REGISTRY))
    monkeypatch.setattr(denoiser, "_BY_CONFIG", dict(denoiser._BY_CONFIG))
    denoiser.register_family(denoiser.FamilySpec(
        "stub", _StubConfig, init_params=None, forward=None,
        abstract_params=None))


def test_pipeline_config_builds_a_family_the_program_registers(stub_family):
    cfg = _config("dit-s2-256")
    cfg["denoiser"] = {"family": "stub", "depth": 3, "tips_threshold": 0.07,
                       "inner": {"width": 5, "widths": [[1, 2], [3]]}}
    pipe = system.pipeline_config(cfg)
    assert type(pipe.unet) is _StubConfig
    assert pipe.unet.depth == 3
    assert pipe.unet.inner == _Inner(width=5, widths=((1, 2), (3,)))
    assert pipe.unet.precision.threshold == 0.07
    assert pipe.unet.kernel_policy is not None
    assert pipe.vae.channels == (512, 512, 256, 128)
    cfg["denoiser"]["label"] = {"width": 1}
    with pytest.raises(ValueError, match="_StubConfig.label holds no "
                                         "dataclass"):
        system.pipeline_config(cfg)


def test_an_unregistered_family_is_refused_naming_the_registered_ones(
        stub_family):
    cfg = _config("dit-s2-256")
    cfg["denoiser"]["family"] = "sdxl"
    with pytest.raises(ValueError, match=r"'sdxl' is not registered; the "
                                         r"program registers \['dit', "
                                         r"'stub', 'unet'\]"):
        system.pipeline_config(cfg)


def test_a_group_named_as_any_pipeline_config_field_fills_it(monkeypatch):
    """A field the program adds (a second text tower, say) is filled from
    the group of its name, with no edit here."""
    from repro.diffusion import pipeline
    from repro.diffusion.text_encoder import TextEncoderConfig

    @dataclasses.dataclass(frozen=True)
    class Wider(pipeline.PipelineConfig):
        text_2: TextEncoderConfig = TextEncoderConfig()

    monkeypatch.setattr(pipeline, "PipelineConfig", Wider)
    cfg = _config("bk-sdm-small-512")
    cfg["text_2"] = {**cfg["text"], "d_model": 1280, "num_layers": 32}
    pipe = system.pipeline_config(cfg)
    assert type(pipe) is Wider
    assert pipe.text_2 == TextEncoderConfig(d_model=1280, num_layers=32)
    assert pipe.text == TextEncoderConfig()
    assert not hasattr(pipe, "denoiser") and not hasattr(pipe, "serve")
