"""Plain float32 reference of SDXL base 1.0 as the program serves it,
written apart from the program: it imports nothing of ``repro``, and reuses
the parts of ``reference.py`` that SDXL shares with the accepted
configurations (the primitives, the resnet, the TIPS quantiser, the VAE
decoder and the DDIM tables).

What SDXL adds, in straightforward ``jax.numpy``:

* two text towers (CLIP ViT-L/14, OpenCLIP ViT-bigG/14), each pre-LN,
  CLS first, tanh-GELU (the program's conventions); each gives the states
  its last layer reads (``output`` "penultimate") or its last layer's
  through the final LN ("final"); the states of the two are concatenated
  along features, and the second tower's pooled text is its final LN at
  the CLS position through ``proj``;
* the UNet with a transformer depth and head count per stage: a stack is
  GroupNorm + ``proj_in``, ``depth`` basic blocks (LN + self-attention with
  PSSA pruning, LN + cross-attention whose CLS score marks TIPS's important
  tokens, LN + the TIPS-quantised GEGLU FFN), ``proj_out`` and the
  residual; a depth-1 stack holds its block's weights beside its own,
  a deeper one under ``blocks``; a mid block (resnet, stack of the last
  stage's depth and heads, resnet);
* "text_time" added conditioning: [pooled text, the 256-wide cos-first
  sinusoid of each of the six size and crop ids] through Linear, SiLU,
  Linear, added to the time embedding;
* classifier-free guidance from one forward over the [cond | uncond] rows
  (rows are independent, so it equals two forwards, and each stored weight
  is read once a step).

Weights are stored in bfloat16 and cast to the compute type inside each
program: the latents' type in ``step`` and ``dec`` (float32 for the
reference, bfloat16 for the ``bf16`` control), always float32 in ``enc``,
which cannot tell the control from the reference by its weights.

The interface is ``config_modules.py``'s: ``Pipeline(cfg)`` with ``enc``
(returning the pytree (states, pooled text)), ``step`` and ``dec``, and
``operands``.  ``IMPLEMENTS`` declares what it implements.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

import reference as ref
from reference import _conv, _gelu, _group_norm, _heads, _layer_norm, _lin
from reference import _merge, _mm, _resnet, _silu, _upsample2

operands = ref.operands

_NUMBER = (int, float)
_TOWER = {"vocab_size": int, "max_len": int, "d_model": int,
          "num_layers": int, "num_heads": int, "d_ff": int,
          "output": ["final", "penultimate"], "projection_dim": int}
_DTYPE = ["bfloat16", "float32"]


def _per_stage(v) -> bool:
    """an int, or a list of ints with one per stage"""
    return (isinstance(v, int) and not isinstance(v, bool)) or (
        isinstance(v, list) and all(isinstance(x, int) for x in v))


def _second_tower(v) -> bool:
    """a second tower: an object with vocab_size, max_len, d_model,
    num_layers, num_heads, d_ff, output ('final' or 'penultimate') and
    projection_dim, and nothing else"""
    return isinstance(v, dict) and set(v) == set(_TOWER) and all(
        v[k] in want if isinstance(want, list) else isinstance(v[k], want)
        for k, want in _TOWER.items())


IMPLEMENTS = {
    "denoiser": {
        "unet": {"family": ["unet"], "in_channels": int,
                 "out_channels": int, "block_channels": list,
                 "down_attn": list, "resnets_per_down": int,
                 "resnets_per_up": int, "has_mid_block": bool,
                 "transformer_depth": _per_stage, "num_heads": _per_stage,
                 "context_dim": int, "text_len": int, "time_dim": int,
                 "latent_size": int, "groups": int, "ffn_mult": int,
                 "addition_embed": ["text_time"], "addition_time_dim": int,
                 "pooled_dim": int, "addition_time_ids": list,
                 "pssa_threshold": _NUMBER, "tips_threshold": _NUMBER,
                 "dtype": _DTYPE},
    },
    "text": {**_TOWER, "second": _second_tower, "dtype": _DTYPE},
    "vae": {**ref.IMPLEMENTS["vae"], "dtype": _DTYPE},
    "sampler": ref.IMPLEMENTS["sampler"],
}


def _cast(tree, dtype):
    return jax.tree.map(lambda a: a.astype(dtype), tree)


def _stage(v, i: int) -> int:
    return v if isinstance(v, int) else v[i]


# --------------------------------------------------------------------------
# text towers
# --------------------------------------------------------------------------
def _tower(p, tokens, spec: dict):
    """(B, T) -> (states (B, T, d), pooled (B, projection_dim) or None)."""
    h = p["embed"][tokens] + p["pos"][None, :tokens.shape[1]]
    n = spec["num_heads"]
    states = h
    for lp in p["layers"]:
        states = h
        x = _layer_norm(h, lp["ln1"], lp["ln1_b"])
        q, k, v = jnp.split(_mm(x, lp["wqkv"]), 3, axis=-1)
        q, k, v = _heads(q, n), _heads(k, n), _heads(v, n)
        s = _mm(q, k.swapaxes(-1, -2)) / math.sqrt(q.shape[-1])
        h = h + _mm(_merge(_mm(jax.nn.softmax(s, -1), v)), lp["wo"])
        x = _layer_norm(h, lp["ln2"], lp["ln2_b"])
        h = h + _mm(_gelu(_mm(x, lp["w1"])), lp["w2"])
    final = _layer_norm(h, p["ln_f"], p["ln_f_b"])
    pooled = (_mm(final[:, 0], p["proj"]) if spec.get("projection_dim")
              else None)
    return (states if spec.get("output") == "penultimate" else final), pooled


def encode_text(p, tokens, text: dict):
    """(B, T) int32 -> (states of both towers (B, T, d1 + d2), pooled)."""
    p = _cast(p, jnp.float32)
    outs = [_tower(p, tokens, text)]
    if "second" in text:
        outs.append(_tower(p["second"], tokens, text["second"]))
    pooled = [x for _, x in outs if x is not None]
    return (jnp.concatenate([s for s, _ in outs], -1),
            jnp.concatenate(pooled, -1))


# --------------------------------------------------------------------------
# the UNet
# --------------------------------------------------------------------------
def _stack(x2d, p, ctx, den: dict, n: int, tips_active):
    """A transformer stack of ``n``-head basic blocks, (B, H, W, C) ->
    the same."""
    b, hh, ww, c = x2d.shape
    h = _group_norm(x2d, p["norm_in"], den["groups"]).reshape(b, hh * ww, c)
    h = _lin(h, p["proj_in"])
    for bp in p["blocks"] if "blocks" in p else [p]:
        hn = _layer_norm(h, bp["ln1"]["scale"], bp["ln1"]["bias"])
        q, k, v = (_heads(_lin(hn, bp[f"sa_{w}"]), n) for w in "qkv")
        probs = jax.nn.softmax(_mm(q, k.swapaxes(-1, -2))
                               / math.sqrt(q.shape[-1]), -1)
        probs = jnp.where(probs >= den["pssa_threshold"], probs, 0.0)
        h = h + _lin(_merge(_mm(probs, v)), bp["sa_o"])

        hn = _layer_norm(h, bp["ln2"]["scale"], bp["ln2"]["bias"])
        q = _heads(_lin(hn, bp["ca_q"]), n)
        k, v = (_heads(_lin(ctx, bp[f"ca_{w}"]), n) for w in "kv")
        probs = jax.nn.softmax(_mm(q, k.swapaxes(-1, -2))
                               / math.sqrt(q.shape[-1]), -1)
        cas = jnp.mean(probs[..., 0], axis=1)                  # (B, T)
        important = jnp.logical_or(cas < den["tips_threshold"],
                                   jnp.logical_not(tips_active))
        h = h + _lin(_merge(_mm(probs, v)), bp["ca_o"])

        hn = _layer_norm(h, bp["ln3"]["scale"], bp["ln3"]["bias"])
        g, u = jnp.split(_lin(ref._tips_quantise(hn, important),
                              bp["ff_geglu"]), 2, axis=-1)
        h = h + _lin(_gelu(g) * u, bp["ff_out"])
    return x2d + _lin(h, p["proj_out"]).reshape(b, hh, ww, c)


def _added(p, pooled, den: dict):
    ids = ref._timestep_embedding(jnp.asarray(den["addition_time_ids"]),
                                  den["addition_time_dim"], pooled.dtype)
    ids = jnp.broadcast_to(ids.reshape(1, -1),
                           (pooled.shape[0], ids.size))
    x = jnp.concatenate([pooled, ids], -1)
    return _lin(_silu(_lin(x, p["add_mlp1"])), p["add_mlp2"])


def unet_eps(p, lat, t, cond, den: dict, tips_active):
    ctx, pooled = cond
    groups, heads = den["groups"], den["num_heads"]
    last = len(den["block_channels"]) - 1
    temb = (ref._time_mlp(p, t, den["block_channels"][0], lat.dtype)
            + _added(p, pooled, den))

    def attn(h, stage, sp):
        return _stack(h, sp, ctx, den, _stage(heads, stage), tips_active)

    h = _conv(lat, p["conv_in"])
    skips = [h]
    for i, stage in enumerate(p["down"]):
        for r, rp in enumerate(stage["resnets"]):
            h = _resnet(h, rp, groups, temb)
            if stage["attns"]:
                h = attn(h, i, stage["attns"][r])
            skips.append(h)
        if "down" in stage:
            h = _conv(h, stage["down"], stride=2)
            skips.append(h)
    if den["has_mid_block"]:
        h = _resnet(h, p["mid"]["res1"], groups, temb)
        h = attn(h, last, p["mid"]["attn"])
        h = _resnet(h, p["mid"]["res2"], groups, temb)
    for j, stage in enumerate(p["up"]):
        for r, rp in enumerate(stage["resnets"]):
            h = _resnet(jnp.concatenate([h, skips.pop()], -1), rp, groups,
                        temb)
            if stage["attns"]:
                h = attn(h, last - j, stage["attns"][r])
        if "up" in stage:
            h = _conv(_upsample2(h), stage["up"])
    return _conv(_silu(_group_norm(h, p["norm_out"], groups)), p["conv_out"])


# --------------------------------------------------------------------------
# one guided step, the pipeline
# --------------------------------------------------------------------------
def denoise_step(weights, lat, cond, uncond, i, cfg: dict):
    """One guided DDIM iteration ``i`` (a traced int) for a batch of rows,
    in the latents' type."""
    den, smp = cfg["denoiser"], cfg["sampler"]
    dt = lat.dtype
    ts, a_t, a_prev = ref.ddim_tables(smp)
    both = jax.tree.map(lambda c, u: jnp.concatenate([c, u]).astype(dt),
                        cond, uncond)
    x2 = jnp.concatenate([lat, lat])
    t = jnp.full((x2.shape[0],), ts[i], jnp.int32)
    eps_c, eps_u = jnp.split(
        unet_eps(_cast(weights["denoiser"], dt), x2, t, both, den,
                 i < smp["tips_active_iters"]), 2)
    eps = eps_u + smp["guidance_scale"] * (eps_c - eps_u)
    a, ap = a_t[i].astype(dt), a_prev[i].astype(dt)
    x0 = (lat - jnp.sqrt(1.0 - a) * eps) / jnp.sqrt(a)
    return jnp.sqrt(ap) * x0 + jnp.sqrt(1.0 - ap) * eps


class Pipeline:
    """``enc`` (weights["text"], tokens), ``step`` (weights, latents,
    cond, uncond, iteration) and ``dec`` (weights["vae"], latents),
    compiled once each for a configuration."""

    def __init__(self, cfg: dict):
        self.enc = jax.jit(lambda p, t: encode_text(p, t, cfg["text"]))
        self.step = jax.jit(lambda w, lat, c, u, i: denoise_step(
            w, lat, c, u, i, cfg))
        self.dec = jax.jit(lambda p, x: ref.vae_decode(
            _cast(p, x.dtype), x, cfg["vae"]))
