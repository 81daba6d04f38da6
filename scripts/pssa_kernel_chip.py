#!/usr/bin/env python3
"""Time the PSSA attention kernel on one TPU chip at the served geometries.

  python scripts/pssa_kernel_chip.py [--tiles default,128x128] [--out FILE]

For each served self-attention geometry (the BK-SDM UNet's three levels at
64x64 latents and DiT-S/2, as (B, H, T, d) with the PSXU patch, B being the
four slots under guidance) and each tiling, one JSON line: milliseconds a
call (median over repetitions of a few back-to-back calls), the share of
the call's roofline (required QK and PV FLOPs at the bf16 peak against
Q/K/V/O and counter bytes at the HBM peak, as ``bench/flops.py`` counts
them), and how far the outputs and counters are from the first tiling's.
``default`` is the tiling ``pssa_attention`` picks by itself.  Exits 2 with
no line where JAX finds no TPU: a CPU timing is not a kernel time.
"""
from __future__ import annotations

import argparse
import contextlib
import functools
import json
import os
import statistics
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "src"))

THRESHOLD = 1.0 / 8192.0
# name -> ((B, H, T, d), patch)
GEOMETRIES = {
    "unet_64x64": ((8, 8, 4096, 40), 64),
    "unet_32x32": ((8, 8, 1024, 80), 32),
    "unet_16x16": ((8, 8, 256, 160), 16),
    "dit_s2": ((16, 6, 256, 64), 16),
}
F32 = 4


def roofline_s(shape: tuple, peaks: dict) -> float:
    """Least time of one call: the larger of compute and memory time."""
    b, h, t, d = shape
    bh = b * h
    return max(4 * bh * t * t * d / peaks["bf16_flops_per_s"],
               F32 * bh * (4 * t * d + 2 * t) / peaks["hbm_bytes_per_s"])


def time_call(fn, args, reps: int = 20, min_rep_s: float = 0.02) -> dict:
    """Median milliseconds a call, each repetition timing enough calls
    back to back to last ``min_rep_s``, so dispatch hides behind them."""
    import jax

    jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    jax.block_until_ready(fn(*args))
    calls = max(1, round(min_rep_s / max(time.perf_counter() - t0, 1e-6)))
    walls = []
    for _ in range(reps):
        t0 = time.perf_counter()
        for _ in range(calls):
            out = fn(*args)
        jax.block_until_ready(out)
        walls.append((time.perf_counter() - t0) / calls)
    return {"ms": 1e3 * statistics.median(walls), "calls": calls,
            "reps": reps}


def compare(res, base) -> dict:
    """Output and counter distance of ``res`` from ``base``."""
    import numpy as np

    out = {"out_max_abs": float(np.max(np.abs(
        np.asarray(res[0]) - np.asarray(base[0]))))}
    for name, a, b in zip(("nnz", "xor_ones"), res[1:], base[1:]):
        a, b = np.asarray(a), np.asarray(b)
        out[f"{name}_rows_differing"] = int(np.sum(a != b))
        out[f"{name}_max_diff"] = int(np.max(np.abs(a - b)))
    return out


def parse_tiles(spec: str) -> list:
    """'default,128x128' -> [None, (128, 128)]."""
    tiles = []
    for item in spec.split(","):
        item = item.strip()
        if item == "default":
            tiles.append(None)
        else:
            bq, bk = item.split("x")
            tiles.append((int(bq), int(bk)))
    return tiles


def _geometry_lines(name, shape, patch, tiles, peaks, device):
    """One JSON line per tiling of one geometry."""
    import jax

    from repro.kernels.pssa_attention.ops import (default_blocks,
                                                  pssa_attention)

    q, k, v = (jax.random.normal(jax.random.PRNGKey(i), shape)
               for i in range(3))
    base = None
    for tile in parse_tiles(tiles):
        bq, bk = tile or (None, None)
        fn = jax.jit(functools.partial(pssa_attention, threshold=THRESHOLD,
                                       patch=patch, bq=bq, bk=bk))
        res = jax.block_until_ready(fn(q, k, v))
        row = {"geometry": name, "shape": list(shape), "patch": patch,
               "tiles": list(tile or default_blocks(shape[2])),
               "default": tile is None, **time_call(fn, (q, k, v))}
        row["roofline_pct"] = 100 * roofline_s(shape, peaks) / (
            row["ms"] * 1e-3)
        if base is None:
            base = res
        else:
            row.update(compare(res, base))
        row["device"] = device
        yield json.dumps(row)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--tiles", default="128x128,default",
                    help="comma-separated BQxBK pairs or 'default'; the "
                         "first is the base the others are compared with")
    ap.add_argument("--out", default=None,
                    help="also append the JSON lines to this file")
    args = ap.parse_args()

    import jax

    if jax.default_backend() != "tpu":
        print("no TPU: kernel times come only from the chip",
              file=sys.stderr)
        return 2
    import repro.core.attention  # noqa: F401  (resolves the kernel imports)

    dev = jax.devices()[0]
    device = {"platform": dev.platform, "device_kind": dev.device_kind,
              "count": jax.device_count()}
    with open(os.path.join(ROOT, "bench", "peaks.json")) as f:
        peaks = json.load(f)[dev.device_kind]
    with (open(args.out, "a") if args.out
          else contextlib.nullcontext()) as sink:
        for name, (shape, patch) in GEOMETRIES.items():
            for line in _geometry_lines(name, shape, patch, args.tiles,
                                        peaks, device):
                print(line, flush=True)
                if sink:
                    sink.write(line + "\n")
                    sink.flush()
    return 0


if __name__ == "__main__":
    sys.exit(main())
