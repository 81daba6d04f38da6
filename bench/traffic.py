"""The one traffic generator: a mix file of parameters -> requests.

A mix is ``traffic/<name>.json`` with a ``kind`` and its parameters; this
module is the only code that reads it.  Request content is the same for
every kind: per request, ``text_len`` random token ids (CLS first, as the
served tokenizer would put it) and a unit-normal initial latent, with the
all-zero unconditional prompt of classifier-free guidance; all drawn from
``--seed`` in one jitted call.

Kinds (times in seconds on the serving clock, whose 0 is when the stream
starts; the measured window is ``[ramp_s, ramp_s + window_s]``):

``standing_queue``  ``slots`` requests become due spread evenly over the
    ramp (one generation), so the slots' step indices are staggered and
    completions come a few per round rather than in waves; every other
    request is due at ``ramp_s``.  The request count is
    ``ceil(rate_bound * window_s) + slots``, where ``rate_bound`` is the
    chip's peak over one image's FLOPs, so no chip can drain the queue in
    the window.
``poisson``  Arrivals at ``rate_per_s`` over ramp and window.  Every seed
    gets the same set of exponential gaps (the quantiles of the
    exponential distribution at ``(i + 0.5) / n``) in a seed-drawn order, so
    the load is the same across seeds and only its order differs.
"""
from __future__ import annotations

import json
import math
import os

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))


def load(name: str, root: str = HERE) -> dict:
    with open(os.path.join(root, "traffic", f"{name}.json")) as f:
        return json.load(f)


def arrivals(mix: dict, *, seed: int, window_s: float, ramp_s: float,
             slots: int, rate_bound: float) -> np.ndarray:
    """Due times of every request, in request order."""
    kind = mix["kind"]
    if kind == "standing_queue":
        n = math.ceil(rate_bound * window_s) + slots
        due = np.full(n, ramp_s, np.float64)
        due[:slots] = ramp_s * np.arange(slots) / slots
        return due
    if kind == "poisson":
        rate = float(mix["rate_per_s"])
        n = max(1, round(rate * (ramp_s + window_s)))
        q = (np.arange(n) + 0.5) / n
        gaps = -np.log1p(-q) / rate
        gaps *= (ramp_s + window_s) / gaps.sum()
        rng = np.random.default_rng(seed)
        return np.cumsum(rng.permutation(gaps))
    raise ValueError(f"traffic kind {kind!r} is not one of "
                     f"'standing_queue', 'poisson'")


def content(cfg: dict, n: int, seed: int):
    """(tokens, uncond tokens, latents) as host arrays, drawn on the device."""
    import jax
    import jax.numpy as jnp

    from weights import seed_key

    text, den = cfg["text"], cfg["denoiser"]
    s, c = den["latent_size"], den["in_channels"]

    def draw(key):
        kt, kl = jax.random.split(jax.random.fold_in(key, 1))
        toks = jax.random.randint(kt, (n, text["max_len"]), 0,
                                  text["vocab_size"], jnp.int32)
        lat = jax.random.normal(kl, (n, 1, s, s, c), jnp.float32)
        return toks, lat

    toks, lat = jax.device_get(jax.jit(draw)(seed_key(seed)))
    uncond = np.zeros((1, text["max_len"]), np.int32)
    return toks, uncond, lat
