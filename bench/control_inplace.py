#!/usr/bin/env python3
"""``control.py``'s controls with the weights left in the type they are
stored in, for a configuration whose stored weights fill most of the chip.

  python3 bench/control_inplace.py --config <name> --seeds 11 12 13
      [--requests 3] [--modes bf16 int8]

``control.py`` casts every weight to the control's type before the
reference runs in the program's place.  For ``int8`` that is a float32
copy: with SDXL's bfloat16 weights (6.8 GB) beside it, 20.6 GB, more than
the chip's 16.  A reference whose programs cast the weights they read to
the compute type (``reference_sdxl.py``: the latents' type in ``step`` and
``dec``, float32 in ``enc``) computes the same from the stored weights, so
here they stay as stored; for ``bf16`` the cast did nothing to bfloat16
weights anyway.  The requests, the rounding, the comparison and the output
are ``control.py``'s.
"""
from __future__ import annotations

import sys

import control


def trajectories(cfg: dict, w, toks, uncond, lat, mode: str) -> list:
    """``control.trajectories`` without the cast of the weights."""
    import contextlib

    import jax.numpy as jnp
    import numpy as np

    import config_modules

    reference = config_modules.reference(cfg)
    dtype = jnp.bfloat16 if mode == "bf16" else jnp.float32
    ctl = reference.Pipeline(cfg)
    n = cfg["sampler"]["num_inference_steps"]
    rounding = (contextlib.nullcontext() if control.MODES[mode] is None
                else reference.operands(control.MODES[mode]))
    sample = []
    with rounding:
        for i in range(len(toks)):
            ctx = ctl.enc(w["text"], toks[i:i + 1])
            unctx = ctl.enc(w["text"], uncond)
            x = jnp.asarray(lat[i]).astype(dtype)
            steps = []
            for k in range(n):
                x = ctl.step(w, x, ctx, unctx, jnp.int32(k))
                steps.append(np.asarray(x[0], np.float32))
            image = np.asarray(ctl.dec(w["vae"], x)[0], np.float32)
            sample.append({"tokens": toks[i:i + 1], "uncond": uncond,
                           "x0": lat[i], "steps": steps, "image": image})
    return sample


if __name__ == "__main__":
    control.trajectories = trajectories
    sys.exit(control.main())
