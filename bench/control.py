#!/usr/bin/env python3
"""The controls of the correctness check: the reference in a lower precision.

  python3 bench/control.py --config <name> --seeds 11 12 13 [--requests 3]
      [--modes bf16 int8]

For each seed: the cell's weights and the first ``--requests`` requests of
its traffic content, run by the configuration's reference module
(``config_modules.py``) in the served program's place in
the precision next below each of the two the configuration states (its
``precision``), and judged by the run's own comparison (``check.py``)
against the float32 reference:

* ``bf16``, below float32 storage: weights, activations and latents in
  bfloat16;
* ``int8``, below bfloat16 multiplies: float32, with both operands of every
  matrix product and convolution rounded to int8 on a per-tensor
  symmetric scale.

Prints, per seed, the numbers compared and the readings behind them.  Runs
on the chip at the cell's own size; ``tests/test_control.py`` runs it at a
small size on the CPU.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))


def _int8(x):
    import jax.numpy as jnp
    s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 127.0
    return jnp.clip(jnp.round(x / s), -127, 127) * s


MODES = {"bf16": None, "int8": _int8}


def trajectories(cfg: dict, w, toks, uncond, lat, mode: str) -> list:
    """The control in the program's place: per request its latents after
    every step and its image, as the check's ``sample`` records them."""
    import contextlib

    import jax
    import jax.numpy as jnp
    import numpy as np

    import config_modules

    reference = config_modules.reference(cfg)
    dtype = jnp.bfloat16 if mode == "bf16" else jnp.float32
    w = jax.tree.map(lambda x: x.astype(dtype), w)
    ctl = reference.Pipeline(cfg)
    n = cfg["sampler"]["num_inference_steps"]
    rounding = (contextlib.nullcontext() if MODES[mode] is None
                else reference.operands(MODES[mode]))
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


def readings(cfg: dict, seeds, n_requests: int, mode: str) -> list:
    """Per seed, the check's readings with the control in the program's
    place: its own trajectory and image, judged by ``check.readings``.
    Raises ``config_modules.Unimplemented`` before any weights are made
    where the configuration's modules do not implement it."""
    import check
    import config_modules
    import traffic
    import weights
    from system import abstract_weights

    config_modules.refuse_unimplemented(cfg)
    abstract = abstract_weights(cfg)
    out = []
    for seed in seeds:
        t0 = time.perf_counter()
        w = weights.make(abstract, seed)
        toks, uncond, lat = traffic.content(cfg, n_requests, seed)
        sample = trajectories(cfg, w, toks, uncond, lat, mode)
        got = check.readings(cfg, w, sample)
        out.append({"seed": seed, "mode": mode,
                    "checks": check.checks(cfg, got, 0), "readings": got,
                    "seconds": time.perf_counter() - t0})
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--requests", type=int, default=3)
    ap.add_argument("--modes", choices=sorted(MODES), nargs="+",
                    default=sorted(MODES))
    args = ap.parse_args(argv)
    import config_modules
    import harness
    with open(os.path.join(HERE, "configs", f"{args.config}.json")) as f:
        cfg = json.load(f)
    try:
        config_modules.refuse_unimplemented(cfg)
    except config_modules.Unimplemented as e:
        print(f"control: {e}", file=sys.stderr)
        return 1
    harness.use_compile_cache()
    for mode in args.modes:
        for row in readings(cfg, args.seeds, args.requests, mode):
            print(json.dumps({"config": args.config, **row}), flush=True)
    print(json.dumps({"device": harness.device_info()}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
