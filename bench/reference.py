"""Plain reference of the served text-to-image pipeline, written apart from
the program: it imports nothing of ``repro`` and reads only the weights that
the benchmark made (``weights.py``) and the request's tokens and latents.

It states the semantics the served path must compute, layer by layer, in
straightforward ``jax.numpy``:

* CLIP-style text tower: pre-LN transformer, CLS first, tanh-GELU MLP;
* denoiser (BK-SDM-Tiny UNet, or DiT-S/2 with adaLN modulation), whose
  transformer blocks are
    - self-attention with PSSA score pruning: post-softmax probabilities
      below the threshold are set to 0 (no renormalisation) before the
      value matmul;
    - cross-attention over the text, whose head-averaged probability on
      the CLS key (CAS) marks a token important where it is below the TIPS
      threshold;
    - a GEGLU FFN whose input is quantised per sample on an unsigned
      12-bit grid (negatives clip to 0), with unimportant tokens dropped to
      the grid's 6 high bits while TIPS is active (the first
      ``tips_active_iters`` iterations);
* classifier-free guidance over separate cond and uncond forwards, and the
  deterministic DDIM update (eta = 0) on a linear-beta schedule;
* the VAE decoder, tanh output.

``dtype`` is the compute type: float32 for the reference (run it under
``jax.default_matmul_precision("highest")``), bfloat16 for the control.
Every function takes plain nested dicts and lists of arrays and a dict of
sizes read from the configuration file.

This is the default reference module of a configuration
(``config_modules.py`` states the interface): ``Pipeline(cfg)`` with
``enc(text_weights, tokens)``, ``step(weights, x, ctx, unctx, i)`` and
``dec(vae_weights, x)``, and ``operands(fn)``.  ``IMPLEMENTS`` declares the
configuration keys it implements: a UNet with one transformer block after
each resnet (``transformer_depth`` 1), no mid block, and one head count for
every stage; one CLIP text tower.
"""
from __future__ import annotations

import contextlib
import math

import jax
import jax.numpy as jnp

_round = None       # rounding of both operands of every product (control)

_NUMBER = (int, float)
_SHARED = {"in_channels": int, "out_channels": int, "num_heads": int,
           "context_dim": int, "text_len": int, "time_dim": int,
           "latent_size": int, "groups": int, "ffn_mult": int,
           "pssa_threshold": _NUMBER, "tips_threshold": _NUMBER}
IMPLEMENTS = {
    "denoiser": {
        "unet": {**_SHARED, "family": ["unet"], "block_channels": list,
                 "down_attn": list, "resnets_per_down": int,
                 "resnets_per_up": int, "has_mid_block": [False],
                 "transformer_depth": [1]},
        "dit": {**_SHARED, "family": ["dit"], "patch": int,
                "hidden_size": int, "depth": int},
    },
    "text": {"vocab_size": int, "max_len": int, "d_model": int,
             "num_layers": int, "num_heads": int, "d_ff": int},
    "vae": {"latent_channels": int, "out_channels": int, "channels": list,
            "resnets_per_stage": int, "groups": int, "scale_factor": _NUMBER},
    "sampler": {"num_train_steps": int, "num_inference_steps": int,
                "beta_start": _NUMBER, "beta_end": _NUMBER,
                "guidance_scale": _NUMBER, "tips_active_iters": int},
}


@contextlib.contextmanager
def operands(fn):
    """Round both operands of every matrix product and convolution with
    ``fn`` in what is traced inside the block (the control's int8 path)."""
    global _round
    prev, _round = _round, fn
    try:
        yield
    finally:
        _round = prev


def _r(x):
    return x if _round is None else _round(x)


def _mm(a, b):
    return _r(a) @ _r(b)


# --------------------------------------------------------------------------
# primitives
# --------------------------------------------------------------------------
def _lin(x, p):
    y = _mm(x, p["w"])
    return y + p["b"] if "b" in p else y


def _conv(x, p, stride: int = 1, pad: int = 1):
    y = jax.lax.conv_general_dilated(
        _r(x), _r(p["w"]), (stride, stride), ((pad, pad), (pad, pad)),
        dimension_numbers=("NHWC", "HWIO", "NHWC"))
    return y + p["b"]


def _layer_norm(x, scale, bias, eps=1e-5):
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), -1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * scale + bias


def _group_norm(x, p, groups: int, eps=1e-5):
    n, h, w, c = x.shape
    g = math.gcd(groups, c)
    xg = x.reshape(n, h, w, g, c // g)
    mu = jnp.mean(xg, (1, 2, 4), keepdims=True)
    var = jnp.mean(jnp.square(xg - mu), (1, 2, 4), keepdims=True)
    xg = (xg - mu) / jnp.sqrt(var + eps)
    return xg.reshape(n, h, w, c) * p["scale"] + p["bias"]


def _silu(x):
    return x * jax.nn.sigmoid(x)


def _gelu(x):                                   # tanh approximation
    return 0.5 * x * (1.0 + jnp.tanh(
        math.sqrt(2.0 / math.pi) * (x + 0.044715 * x ** 3)))


def _upsample2(x):                              # nearest neighbour, x2
    return jnp.repeat(jnp.repeat(x, 2, axis=1), 2, axis=2)


def _heads(x, n):                               # (B, T, C) -> (B, n, T, d)
    b, t, c = x.shape
    return x.reshape(b, t, n, c // n).transpose(0, 2, 1, 3)


def _merge(x):                                  # (B, n, T, d) -> (B, T, C)
    b, n, t, d = x.shape
    return x.transpose(0, 2, 1, 3).reshape(b, t, n * d)


def _timestep_embedding(t, dim: int, dtype):
    # the angles in float32 whatever the compute type: t reaches 960
    half = dim // 2
    freqs = jnp.exp(-math.log(10000.0) * jnp.arange(half, dtype=jnp.float32)
                    / half)
    ang = t.astype(jnp.float32)[:, None] * freqs[None]
    return jnp.concatenate([jnp.cos(ang), jnp.sin(ang)], -1).astype(dtype)


# --------------------------------------------------------------------------
# text tower
# --------------------------------------------------------------------------
def encode_text(p, tokens, text: dict):
    """(B, T) int32 -> (B, T, d)."""
    h = p["embed"][tokens] + p["pos"][None, :tokens.shape[1]]
    n = text["num_heads"]
    for lp in p["layers"]:
        x = _layer_norm(h, lp["ln1"], lp["ln1_b"])
        q, k, v = jnp.split(_mm(x, lp["wqkv"]), 3, axis=-1)
        q, k, v = _heads(q, n), _heads(k, n), _heads(v, n)
        s = _mm(q, k.swapaxes(-1, -2)) / math.sqrt(q.shape[-1])
        h = h + _mm(_merge(_mm(jax.nn.softmax(s, -1), v)), lp["wo"])
        x = _layer_norm(h, lp["ln2"], lp["ln2_b"])
        h = h + _mm(_gelu(_mm(x, lp["w1"])), lp["w2"])
    return _layer_norm(h, p["ln_f"], p["ln_f_b"])


# --------------------------------------------------------------------------
# the transformer block both denoisers share
# --------------------------------------------------------------------------
def _tips_quantise(x, important):
    """Per-sample unsigned INT12 grid; unimportant rows keep 6 high bits."""
    amax = jnp.max(jnp.maximum(x, 0), axis=(1, 2), keepdims=True)
    scale = jnp.maximum(amax, 1e-8) / 4095.0
    q = jnp.clip(jnp.round(x / scale), 0, 4095)
    q = jnp.where(important[..., None], q, jnp.floor(q / 64.0) * 64.0)
    return q * scale


def transformer_block(x2d, p, ctx, den: dict, tips_active, mod=None):
    """(B, H, W, C) tokens -> same; ``mod`` is DiT's 9 adaLN vectors."""
    b, hh, ww, c = x2d.shape
    n = den["num_heads"]

    def modulate(hn, i):
        if mod is None:
            return hn
        return hn * (1.0 + mod[3 * i + 1]) + mod[3 * i]

    def gate(y, i):
        return y if mod is None else y * mod[3 * i + 2]

    h = _group_norm(x2d, p["norm_in"], den["groups"]).reshape(b, hh * ww, c)
    h = _lin(h, p["proj_in"])

    hn = modulate(_layer_norm(h, p["ln1"]["scale"], p["ln1"]["bias"]), 0)
    q, k, v = (_heads(_lin(hn, p[f"sa_{w}"]), n) for w in "qkv")
    probs = jax.nn.softmax(_mm(q, k.swapaxes(-1, -2))
                           / math.sqrt(q.shape[-1]), -1)
    probs = jnp.where(probs >= den["pssa_threshold"], probs, 0.0)
    h = h + gate(_lin(_merge(_mm(probs, v)), p["sa_o"]), 0)

    hn = modulate(_layer_norm(h, p["ln2"]["scale"], p["ln2"]["bias"]), 1)
    q = _heads(_lin(hn, p["ca_q"]), n)
    k, v = _heads(_lin(ctx, p["ca_k"]), n), _heads(_lin(ctx, p["ca_v"]), n)
    probs = jax.nn.softmax(_mm(q, k.swapaxes(-1, -2))
                           / math.sqrt(q.shape[-1]), -1)
    cas = jnp.mean(probs[..., 0], axis=1)                      # (B, T)
    important = jnp.logical_or(cas < den["tips_threshold"],
                               jnp.logical_not(tips_active))
    h = h + gate(_lin(_merge(_mm(probs, v)), p["ca_o"]), 1)

    hn = modulate(_layer_norm(h, p["ln3"]["scale"], p["ln3"]["bias"]), 2)
    g, u = jnp.split(_lin(_tips_quantise(hn, important), p["ff_geglu"]), 2,
                     axis=-1)
    h = h + gate(_lin(_gelu(g) * u, p["ff_out"]), 2)
    return x2d + _lin(h, p["proj_out"]).reshape(b, hh, ww, c)


# --------------------------------------------------------------------------
# denoisers
# --------------------------------------------------------------------------
def _resnet(x, p, groups, temb=None):
    h = _conv(_silu(_group_norm(x, p["norm1"], groups)), p["conv1"])
    if temb is not None:
        h = h + _lin(_silu(temb), p["time"])[:, None, None, :]
    h = _conv(_silu(_group_norm(h, p["norm2"], groups)), p["conv2"])
    skip = _conv(x, p["skip"], pad=0) if "skip" in p else x
    return skip + h


def _time_mlp(p, t, dim, dtype):
    temb = _lin(_timestep_embedding(t, dim, dtype), p["time_mlp1"])
    return _lin(_silu(temb), p["time_mlp2"])


def unet_eps(p, lat, t, ctx, den: dict, tips_active):
    groups = den["groups"]
    temb = _time_mlp(p, t, den["block_channels"][0], lat.dtype)
    h = _conv(lat, p["conv_in"])
    skips = [h]
    for stage in p["down"]:
        for r, rp in enumerate(stage["resnets"]):
            h = _resnet(h, rp, groups, temb)
            if stage["attns"]:
                h = transformer_block(h, stage["attns"][r], ctx, den,
                                      tips_active)
            skips.append(h)
        if "down" in stage:
            h = _conv(h, stage["down"], stride=2)
            skips.append(h)
    for stage in p["up"]:
        for r, rp in enumerate(stage["resnets"]):
            h = _resnet(jnp.concatenate([h, skips.pop()], -1), rp, groups,
                        temb)
            if stage["attns"]:
                h = transformer_block(h, stage["attns"][r], ctx, den,
                                      tips_active)
        if "up" in stage:
            h = _conv(_upsample2(h), stage["up"])
    return _conv(_silu(_group_norm(h, p["norm_out"], groups)), p["conv_out"])


def dit_eps(p, lat, t, ctx, den: dict, tips_active):
    b, s, _, c = lat.shape
    pt, d = den["patch"], den["hidden_size"]
    g = s // pt
    temb = _time_mlp(p, t, d, lat.dtype)
    x = lat.reshape(b, g, pt, g, pt, c).transpose(0, 1, 3, 2, 4, 5)
    h = _lin(x.reshape(b, g, g, pt * pt * c), p["patch_embed"])
    for bp in p["blocks"]:
        ada = _lin(_silu(temb), bp["ada"])
        mod = [m[:, None, :] for m in jnp.split(ada, 9, axis=-1)]
        h = transformer_block(h, bp["attn"], ctx, den, tips_active, mod)
    shift, scale = jnp.split(_lin(_silu(temb), p["final_ada"]), 2, axis=-1)
    hn = _layer_norm(h.reshape(b, g * g, d), p["final_norm"]["scale"],
                     p["final_norm"]["bias"])
    out = _lin(hn * (1.0 + scale[:, None]) + shift[:, None], p["final_out"])
    co = den["out_channels"]
    out = out.reshape(b, g, g, pt, pt, co).transpose(0, 1, 3, 2, 4, 5)
    return out.reshape(b, s, s, co)


EPS = {"unet": unet_eps, "dit": dit_eps}


# --------------------------------------------------------------------------
# sampler and VAE
# --------------------------------------------------------------------------
def ddim_tables(sampler: dict):
    """(timesteps, alpha-bar at t, alpha-bar at t - stride) per iteration."""
    n_train, n = sampler["num_train_steps"], sampler["num_inference_steps"]
    betas = jnp.linspace(sampler["beta_start"] ** 0.5,
                         sampler["beta_end"] ** 0.5, n_train) ** 2
    acp = jnp.cumprod(1.0 - betas)
    stride = n_train // n
    ts = (n - 1 - jnp.arange(n)) * stride
    a_prev = jnp.where(ts - stride >= 0, acp[jnp.maximum(ts - stride, 0)],
                       1.0)
    return ts, acp[ts], a_prev


def denoise_step(weights, lat, ctx, unctx, i, cfg: dict):
    """One guided DDIM iteration ``i`` (a traced int) for a batch of rows."""
    den, smp = cfg["denoiser"], cfg["sampler"]
    ts, a_t, a_prev = ddim_tables(smp)
    t = jnp.full((lat.shape[0],), ts[i], jnp.int32)
    active = i < smp["tips_active_iters"]
    eps_fn = EPS[den["family"]]
    eps_c = eps_fn(weights["denoiser"], lat, t, ctx, den, active)
    eps_u = eps_fn(weights["denoiser"], lat, t, unctx, den, active)
    eps = eps_u + smp["guidance_scale"] * (eps_c - eps_u)
    a, ap = a_t[i].astype(lat.dtype), a_prev[i].astype(lat.dtype)
    x0 = (lat - jnp.sqrt(1.0 - a) * eps) / jnp.sqrt(a)
    return jnp.sqrt(ap) * x0 + jnp.sqrt(1.0 - ap) * eps


class Pipeline:
    """The three stages, compiled once each for a configuration: ``enc``
    (weights["text"], tokens), ``step`` (weights, latents, context, uncond
    context, iteration) and ``dec`` (weights["vae"], latents).  The step
    takes its iteration as a traced int, so one compile serves them all."""

    def __init__(self, cfg: dict):
        self.enc = jax.jit(lambda p, t: encode_text(p, t, cfg["text"]))
        self.step = jax.jit(lambda w, lat, c, u, i: denoise_step(
            w, lat, c, u, i, cfg))
        self.dec = jax.jit(lambda p, x: vae_decode(p, x, cfg["vae"]))


def vae_decode(p, lat, vae: dict):
    groups = vae["groups"]
    h = _conv(lat / vae["scale_factor"], p["conv_in"])
    for st in p["stages"]:
        for rp in st["resnets"]:
            h = _resnet(h, rp, groups)
        if "up" in st:
            h = _conv(_upsample2(h), st["up"])
    h = _silu(_group_norm(h, p["norm_out"], groups))
    return jnp.tanh(_conv(h, p["conv_out"]))
