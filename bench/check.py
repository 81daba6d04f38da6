"""The comparison that decides ``correct``.

A run records, for a seed-drawn sample of its requests (one per slot), the
latents the served slot step produced after each of the request's denoising
steps, and the image the served decode produced (``System.serve``).  Once
the window has closed and the served program is freed, the float32
reference (the configuration's reference module, ``reference.py`` unless
it names another; matmuls at ``highest``) is teacher-forced along the
served trajectory, one request at a time.  The numbers compared
are named in the configuration file (``check``: name -> limit):

* ``step_upd_rel_err_max``: for every step i, the reference's step from
  the served x_i (the request's own initial latent for i = 0) against the
  served x_{i+1}, measured on the step's update:
  ||served x_{i+1} - reference x_{i+1}|| / ||reference x_{i+1} - x_i||;
  the widest over steps and requests.  Each step is judged alone, so a
  rounding difference does not compound over the 25 guided steps, and a
  fault in any step (text encode, admission, the denoiser and its kernels,
  the guidance and DDIM update) shows where it happens.  Judged on the
  update, late steps, whose updates are small beside the latent, weigh as
  much as early ones.
* ``decode_rel_l2_max``: the reference VAE decode of the served final
  latent against the served image, relative L2; the widest over requests.

A sampled request that never came back (no image), or whose record is
incomplete or not finite, reads inf, and so does every number when nothing
was recorded.  Besides, ``requests_unfinished`` counts the requests the
path owed an image and never answered (limit 0).  ``PERF.md`` gives the
readings each limit was set from.
"""
from __future__ import annotations

import math

import numpy as np


def rel(a, b) -> float:
    a = np.asarray(a, np.float64)
    b = np.asarray(b, np.float64)
    return float(np.linalg.norm(a - b) / max(np.linalg.norm(b), 1e-30))


def readings(cfg: dict, weights, sample: list) -> dict:
    """``sample``: [{tokens, uncond, x0, steps: [x_1..x_n], image}].

    Returns, per request, ``step_upd`` (each step's error relative to the
    reference's update) and ``decode`` (relative L2 of the image).
    """
    import jax
    import jax.numpy as jnp

    import config_modules

    pipe = config_modules.reference(cfg).Pipeline(cfg)
    n = cfg["sampler"]["num_inference_steps"]
    w = weights
    out = {"step_upd": [], "decode": []}
    with jax.default_matmul_precision("highest"):
        for req in sample:
            xs = [np.asarray(req["x0"])] + [np.asarray(x)[None]
                                            for x in req["steps"]]
            if (req["image"] is None or len(xs) != n + 1
                    or not all(np.isfinite(x).all() for x in xs)):
                out["step_upd"].append([math.inf])
                out["decode"].append(math.inf)
                continue
            ctx = pipe.enc(w["text"], req["tokens"])
            unctx = pipe.enc(w["text"], req["uncond"])
            upd = []
            for i in range(n):
                want = pipe.step(w, jnp.asarray(xs[i]), ctx, unctx,
                                 jnp.int32(i))
                want = np.asarray(want, np.float32)
                upd.append(rel(xs[i + 1] - xs[i], want - xs[i]))
            out["step_upd"].append(upd)
            want = pipe.dec(w["vae"], jnp.asarray(xs[n]))
            out["decode"].append(rel(req["image"],
                                     np.asarray(want, np.float32)[0]))
    return out


NUMBERS = {
    "step_upd_rel_err_max": lambda g: max(max(e) for e in g["step_upd"]),
    "decode_rel_l2_max": lambda g: max(g["decode"]),
}


def checks(cfg: dict, got: dict, unfinished: int) -> dict:
    """Each compared number beside its limit, and the requests owed an
    image and left unanswered (limit 0)."""
    out = {name: {"value": NUMBERS[name](got) if got["decode"] else math.inf,
                  "limit": limit}
           for name, limit in cfg["check"].items()}
    out["requests_unfinished"] = {"value": unfinished, "limit": 0}
    return out


def passed(c: dict) -> bool:
    return all(v["value"] <= v["limit"] for v in c.values())
