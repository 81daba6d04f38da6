"""SDXL base 1.0 through the served path, against its plain reference.

* At a smoke size (mixed transformer depths, a mid block, two text towers,
  "text_time" conditioning, bfloat16 weights), the program's UNet and one
  guided DDIM step per slot through ``DiffusionEngine``'s slot path match
  ``bench/reference_sdxl.py`` on the benchmark's seeded random weights.
* At full size (``jax.eval_shape``), the parameter counts are SDXL's.
* The two accepted configurations' parameter trees are unchanged.
"""
import dataclasses
import hashlib
import importlib
import math
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import bk_sdm, dit_s, sdxl_base
from repro.diffusion.denoiser import make_denoiser
from repro.diffusion.engine import DiffusionEngine
from repro.diffusion.text_encoder import init_text_encoder_params
from repro.diffusion.unet import unet_forward
from repro.diffusion.vae import init_vae_params

BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "bench")

# Both sides read the same bfloat16 weights in float32 on the CPU, so they
# differ by summation order (about 1e-6), except where TIPS's quantiser
# turns that into a grid step: an unimportant token's FFN input sits on a
# 6-bit grid, and a value that crosses a grid point moves by 64/4095 of
# its row's max.  At the smoke size's 8 text tokens the CLS score is about
# 1/8, above the 0.05 threshold, so most tokens are unimportant: eps then
# reads about 3e-3 apart (relative L2).  A served step is judged as the
# benchmark's check judges it, by its error over the reference's update
# ||x_{i+1} - x_i||: it reads 5e-4 or less.  A conditioning fault (the
# pooled text swapped between rows, the CFG tile after the first resnet)
# reads 1e-2 or more.
EPS_TOL = 1e-2
STEP_TOL = 5e-3


@pytest.fixture(scope="module")
def bench_modules():
    if BENCH not in sys.path:
        sys.path.append(BENCH)
    return (importlib.import_module("reference_sdxl"),
            importlib.import_module("weights"))


def _ref_cfg(pipe) -> dict:
    """The reference's configuration dict for a program ``PipelineConfig``."""
    return {"denoiser": dataclasses.asdict(pipe.unet),
            "text": dataclasses.asdict(pipe.text),
            "vae": dataclasses.asdict(pipe.vae),
            "sampler": dataclasses.asdict(pipe.ddim)}


def _engine(pipe, weights, seed):
    built = []

    def build():
        eng = DiffusionEngine(pipe, key=jax.random.PRNGKey(0))
        built.append(eng)
        return {"text": eng.text_params, "denoiser": eng.unet_params,
                "vae": eng.vae_params}
    w = weights.make(jax.eval_shape(build), seed)
    eng = built[0]
    eng.text_params, eng.unet_params, eng.vae_params = (
        w["text"], w["denoiser"], w["vae"])
    return eng, w


def _tokens(key, pipe, n):
    return jax.random.randint(key, (n, pipe.text.max_len), 1,
                              pipe.text.vocab_size, jnp.int32)


def _rel(a, b):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.linalg.norm(a - b) / np.linalg.norm(b))


def test_smoke_config_has_mixed_depths_a_mid_block_and_two_towers():
    u, t = sdxl_base.SMOKE.unet, sdxl_base.SMOKE.text
    assert u.has_mid_block and u.addition_embed == "text_time"
    assert [u.stage_depth(i) for i, a in enumerate(u.down_attn) if a] \
        == [1, 2]
    assert t.has_second
    assert t.pooled_dim == u.pooled_dim and t.context_dim == u.context_dim
    # one stats entry per basic block: down (1 + 2) * 2 resnets, mid 2,
    # up (2 + 1) * 3 resnets
    assert len(make_denoiser(u).layer_order()) == 6 + 2 + 9


def test_unet_forward_matches_reference(bench_modules):
    ref, weights = bench_modules
    pipe = sdxl_base.SMOKE
    eng, w = _engine(pipe, weights, seed=3100000001)
    cfg = _ref_cfg(pipe)
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(7), 3)
    u = pipe.unet
    lat = jax.random.normal(k1, (2, u.latent_size, u.latent_size, 4))
    ctx = 0.5 * jax.random.normal(k2, (2, u.text_len, u.context_dim))
    pooled = jax.random.normal(k3, (2, u.pooled_dim))
    t = jnp.array([960, 40], jnp.int32)
    fwd = jax.jit(lambda p, cond: unet_forward(p, lat, t, cond, u))
    eps, stats = fwd(eng.unet_params, (ctx, pooled))
    assert len(stats.layers) == len(make_denoiser(u).layer_order())
    with jax.default_matmul_precision("highest"):
        want = jax.jit(lambda p: ref.unet_eps(
            ref._cast(p, jnp.float32), lat, t, (ctx, pooled),
            cfg["denoiser"], True))(w["denoiser"])
    assert _rel(eps, want) < EPS_TOL
    # the pooled text reaches eps: each row answers to its own
    eps2, _ = fwd(eng.unet_params, (ctx, pooled[::-1]))
    assert _rel(eps2, eps) > 1e-2


def test_temporal_reuse_is_refused():
    """Patch reuse caches one block per stack and no added conditioning,
    so a deeper stack or "text_time" is refused before anything runs."""
    from repro.core.reuse import ReusePolicy
    u = dataclasses.replace(sdxl_base.SMOKE.unet,
                            reuse_policy=ReusePolicy(enabled=True))
    s = u.latent_size
    with pytest.raises(ValueError, match="temporal patch reuse"):
        unet_forward({}, jnp.zeros((1, s, s, 4)), jnp.zeros((1,), jnp.int32),
                     (jnp.zeros((1, u.text_len, u.context_dim)),
                      jnp.zeros((1, u.pooled_dim))), u, reuse_cache=object())


def test_guided_slot_steps_match_reference(bench_modules):
    """Two slots at different iterations, through admit and slot_step; the
    uncond prompt's pooled text differs from each prompt's."""
    ref, weights = bench_modules
    pipe = sdxl_base.SMOKE
    eng, w = _engine(pipe, weights, seed=3100000002)
    cfg = _ref_cfg(pipe)
    toks = _tokens(jax.random.PRNGKey(11), pipe, 2)
    uncond = jnp.zeros((1, pipe.text.max_len), jnp.int32)
    s, c = pipe.unet.latent_size, pipe.unet.in_channels
    x0 = jax.random.normal(jax.random.PRNGKey(12), (2, 1, s, s, c))

    state = eng.init_slots(2)
    state = eng.admit(state, 0, toks[0:1], None, uncond_tokens=uncond,
                      latents=x0[0])
    state = eng.slot_step(state)
    x1_slot0 = np.asarray(state.latents[0])
    state = eng.admit(state, 1, toks[1:2], None, uncond_tokens=uncond,
                      latents=x0[1])
    state = eng.slot_step(state)
    served = {(0, 0): (x0[0], x1_slot0),
              (0, 1): (x1_slot0[None], np.asarray(state.latents[0])),
              (1, 0): (x0[1], np.asarray(state.latents[1]))}

    pipe_ref = ref.Pipeline(cfg)
    with jax.default_matmul_precision("highest"):
        un = pipe_ref.enc(w["text"], uncond)
        for (slot, i), (x_in, x_out) in served.items():
            cond = pipe_ref.enc(w["text"], toks[slot:slot + 1])
            assert _rel(cond[1], un[1]) > 0.1      # pooled texts differ
            want = pipe_ref.step(w, jnp.asarray(x_in), cond, un,
                                 jnp.int32(i))
            err = (np.linalg.norm(np.asarray(x_out) - np.asarray(want)[0])
                   / np.linalg.norm(np.asarray(want) - np.asarray(x_in)))
            assert err < STEP_TOL, (slot, i, err)


# published parameter counts: unet/config.json's UNet2DConditionModel,
# text_encoder (CLIPTextModel) and text_encoder_2
# (CLIPTextModelWithProjection) of stable-diffusion-xl-base-1.0
SDXL_UNET, CLIP_L, BIG_G = 2_567_463_684, 123_060_480, 694_659_840


def _count(tree) -> int:
    return sum(math.prod(a.shape) for a in jax.tree.leaves(tree))


def test_full_size_parameter_counts():
    cfg = sdxl_base.CONFIG
    key = jax.random.PRNGKey(0)
    unet = jax.eval_shape(lambda: make_denoiser(cfg.unet).init_params(key))
    text = jax.eval_shape(lambda: init_text_encoder_params(key, cfg.text))
    assert abs(_count(unet) / SDXL_UNET - 1) < 0.005
    big_g = text.pop("second")
    assert abs(_count(text) / CLIP_L - 1) < 0.01
    assert abs(_count(big_g) / BIG_G - 1) < 0.01
    assert big_g["proj"].shape == (1280, 1280)
    dtypes = {a.dtype for a in jax.tree.leaves((unet, text, big_g))}
    assert dtypes == {jnp.dtype(jnp.bfloat16)}
    assert len(make_denoiser(cfg.unet).layer_order()) == 70


# (leaves, parameters, sha256 of "path shape dtype" per leaf) of the text,
# denoiser and VAE weight groups, as the parent program built them
TREES = {
    "bk_sdm": (576, 633399431, "1f2e376b3b6dc53b427452139d3a4ec2"
                               "4bbb0471804c7f347cd56aabd3e51fbd"),
    "dit_s": (528, 210188179, "de99a4bb59afa337088a9b95fad52e12"
                              "668c1f2e334e5e81ca2437bafbaeaf25"),
}


@pytest.mark.parametrize("name", sorted(TREES))
def test_accepted_parameter_trees_unchanged(name):
    cfg = {"bk_sdm": bk_sdm, "dit_s": dit_s}[name].CONFIG
    key = jax.random.PRNGKey(0)
    tree = jax.eval_shape(lambda: {
        "text": init_text_encoder_params(key, cfg.text),
        "denoiser": make_denoiser(cfg.unet).init_params(key),
        "vae": init_vae_params(key, cfg.vae)})
    flat = jax.tree_util.tree_flatten_with_path(tree)[0]
    lines = "\n".join(f"{jax.tree_util.keystr(p)} {tuple(a.shape)} "
                      f"{a.dtype}" for p, a in flat)
    assert (len(flat), sum(math.prod(a.shape) for _, a in flat),
            hashlib.sha256(lines.encode()).hexdigest()) == TREES[name]
