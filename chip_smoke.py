#!/usr/bin/env python3
"""Bring-up check: the served text-to-image path at full width on a TPU.

  python chip_smoke.py                # one chip
  python chip_smoke.py --four-chips   # data-parallel serving over four chips

One chip: the full ``PipelineConfig()`` (BK-SDM-Tiny UNet at 64x64 latents,
i.e. 512x512 images, the CLIP-L text tower and the VAE), 25-step DDIM with
guidance 7.5 and ``KernelPolicy.auto()``.  Phases:

  1. the integer-exact Pallas kernels (PSXU bitmap, DBSC, reuse delta, and
     the PSSA attention counters on inputs whose every keep decision sits
     far from the threshold) against their references on the chip —
     bit-identical or fail; the PSSA counters on random inputs against the
     reference at full f32 matmul precision are reported;
  2. four synthetic requests served through the slot runtime and its
     ``ContinuousScheduler`` (two slots, what ``serve_continuous`` runs)
     with the ledger on; every image finite and (512, 512, 3);
  3. request 0 replayed one-shot through the fused route and through
     ``KernelPolicy.reference()`` on the same latents: images within
     ``IMAGE_REL_L2_TOL`` of each other and of the served image; the
     PSSA/TIPS integer counters of the two routes compared and reported.

Four chips: the requests through the engine over ``make_data_mesh(4)`` in
one micro-batch (one request per device) and at dp=1 one request per call,
the same per-device batch: the ledger within ``LEDGER_REL_TOL`` (bit
identity reported), images within ``IMAGE_REL_L2_TOL``, the dp=4 image
batch sharded over all four devices.  Reported beside it: dp=1 at the dp=4
micro-batch (four requests on one device), and the ledger with one
request's counters dropped or duplicated (planted faults); the dropped one
must read above ``LEDGER_REL_TOL``, so every run shows the limit catching
a lost shard.  (Duplication is reported only: where two requests' counters
are equal, as at smoke geometry, it cannot show.)

Weights, tokens and latents come from seeds.  Diagnostics go to stdout
first; they are bring-up readings, not benchmark numbers.  The last line
of stdout is ``{"ok": true, "device": {...}}`` and is printed only when
JAX finds a TPU and every check passed; otherwise the exit code is
non-zero.  Everything runs in this one process, which holds the chip.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
import traceback

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

STEPS = 25
GUIDANCE = 7.5
REQUESTS = 4
SLOTS = 2
FULL_IMAGE = (512, 512, 3)
# ||a - b|| / ||b|| over whole [-1, 1] images.  The fused kernels and the
# reference attention differ in float summation order (blocked online
# softmax against one materialized softmax), which 25 guided steps carry
# into the image; unrelated images sit near sqrt(2).
IMAGE_REL_L2_TOL = 0.1
# ledger summary values, dp=4 against dp=1, relative.  The PSSA counters
# threshold float probabilities, and the partitioned program rounds them
# differently from the one-device program: at full width on a TPU v5e the
# ledgers differ by 1.93e-6 at the same per-device batch (2.8e-7 and
# 1.2e-5 at the dp=4 micro-batch), while one request's counters dropped
# or duplicated read 4.9e-3 and 3.5e-3.
LEDGER_REL_TOL = 1e-4
PSSA_THRESHOLD = 1.0 / 8192.0          # the served PSSA operating point
PSSA_SHAPE = (2, 8, 4096, 40)          # (B, H, T, d) of the 64x64 blocks


class CheckFailed(Exception):
    pass


def say(msg: str) -> None:
    print(f"chip_smoke: {msg}", flush=True)


def require(cond: bool, what: str) -> None:
    if not cond:
        raise CheckFailed(what)


def device_info() -> dict:
    import jax
    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def build_config(kernels: str = "auto", smoke: bool = False,
                 steps: int = STEPS, guidance: float = GUIDANCE):
    """The served ``PipelineConfig`` through the CLI wiring, geometry explicit."""
    from repro.launch.cli import config_from_args
    args = argparse.Namespace(smoke=smoke, model="unet", kernels=kernels,
                              tips="fixed", reuse="off", solver="",
                              tiers=None, steps=steps, guidance=guidance)
    return config_from_args(args)


def check_compiled_policy(described: dict) -> None:
    """The resolved kernel policy must be the compiled fused route."""
    for op in ("self_attention", "cross_attention"):
        require(described[op] == "fused",
                f"kernel policy {op}={described[op]!r}, expected 'fused'")
    require(described["bitmap"] == "kernel" and described["reuse"] == "kernel",
            f"kernel policy bitmap/reuse not on the kernels: {described}")
    require(described["interpret_resolved"] is False,
            f"Pallas kernels would run interpreted: {described}")


def rel_l2(a, b) -> float:
    import numpy as np
    a = np.asarray(a, np.float64)
    b = np.asarray(b, np.float64)
    return float(np.linalg.norm(a - b) / max(np.linalg.norm(b), 1e-30))


def check_images(images, shape) -> None:
    import numpy as np
    for i, img in enumerate(images):
        img = np.asarray(img)
        require(img.shape == tuple(shape),
                f"image {i} has shape {img.shape}, expected {tuple(shape)}")
        require(bool(np.isfinite(img).all()), f"image {i} is not finite")


def image_shape(cfg) -> tuple:
    s = 8 * cfg.unet.latent_size              # the VAE upsamples by 8
    return (s, s, cfg.vae.out_channels)


# ----------------------------------------------------------------------------
# phase 1: integer-exact kernels on the device
# ----------------------------------------------------------------------------
def pssa_margin_inputs(key, shape, gain: float = 10.0):
    """(B, H, T, d) q/k/v whose softmax probabilities avoid the threshold.

    Every query and key gets a class, one-hot over the head dim (head ``i``
    of the B*H draws from 2 up to ``d`` classes), so a scaled score is
    ``gain`` where the classes match and 0 elsewhere.  With ``n`` matches
    in a row, a match has probability about ``1/n`` (>= 1/2048 at T=4096,
    4x the 1/8192 threshold) and a miss under ``1/(n e^gain)``, more than
    two orders below it: no rounding of the scores or of the softmax sum
    can flip a keep bit, so the kernel's nnz and patch-XOR counters must
    equal the reference's exactly.  The random classes along the keys make
    every patch boundary decide some XOR bits, the carry across key blocks
    included.
    """
    import jax
    import jax.numpy as jnp
    import numpy as np

    b, h, t, d = shape
    classes = jnp.asarray(np.linspace(2, d, b * h).round().astype(np.int32))
    kq, kk, kv = jax.random.split(key, 3)

    def one_hot(k):
        c = jax.random.randint(k, (b * h, t), 0, 1 << 30) % classes[:, None]
        return jax.nn.one_hot(c, d).reshape(b, h, t, d)

    q = one_hot(kq) * (gain * d ** 0.5)
    return q, one_hot(kk), jax.random.normal(kv, shape)


def pssa_counters(q, k, v, patch: int, use_kernel: bool,
                  interpret: bool | None = None):
    """(nnz, xor_ones) of the PSSA op; the reference at full f32 matmuls."""
    import contextlib

    import jax

    from repro.kernels.pssa_attention.ops import pssa_attention

    with (contextlib.nullcontext() if use_kernel
          else jax.default_matmul_precision("highest")):
        _, nnz, xor = pssa_attention(q, k, v, PSSA_THRESHOLD, patch=patch,
                                     use_kernel=use_kernel,
                                     interpret=interpret)
    return jax.device_get(nnz), jax.device_get(xor)


def pssa_random_report(shape=PSSA_SHAPE, patch: int = 64,
                       interpret: bool | None = None) -> dict:
    """PSSA counters on random q/k/v: kernel against the reference.

    Here keep decisions may sit within rounding of the threshold, so this
    is a reading, not a check: how many query rows' counters differ.
    """
    import jax
    import numpy as np

    q, k, v = (jax.random.normal(jax.random.PRNGKey(20 + i), shape)
               for i in range(3))
    got = pssa_counters(q, k, v, patch, True, interpret)
    ref = pssa_counters(q, k, v, patch, False)
    out = {"rows": int(got[0].size)}
    for name, g, r in zip(("nnz", "xor_ones"), got, ref):
        g, r = np.asarray(g, np.int64), np.asarray(r, np.int64)
        out[f"{name}_rows_differing"] = int((g != r).sum())
        out[f"{name}_total_kernel"] = int(g.sum())
        out[f"{name}_total_reference"] = int(r.sum())
    return out


def kernel_exactness_phase(interpret: bool | None = None,
                           sas_shape=(256, 4096), patch: int = 64,
                           ffn=(512, 320, 1280),
                           tokens=(2, 4096, 320),
                           pssa_shape=PSSA_SHAPE) -> dict:
    """Pallas kernels whose outputs are integer functions of their inputs.

    Each must equal its reference bit for bit: the PSXU bitmap (patch-XOR
    through a lane rotation, popcount and bit-pack through the MXU), the
    DBSC FFN matmul (int8 bit-slice accumulators, then the shared float
    rescale), the temporal-reuse patch delta, and the PSSA attention's nnz
    and patch-XOR counters on ``pssa_margin_inputs``.
    """
    import jax
    import numpy as np

    from repro.kernels.bitslice_matmul.ops import bitslice_matmul
    from repro.kernels.patch_bitmap.ops import patch_bitmap
    from repro.kernels.patch_reuse.ops import patch_delta

    key = jax.random.PRNGKey(11)
    k1, k2, k3, k4, k5, k6 = jax.random.split(key, 6)
    out = {}

    rows, tk = sas_shape
    sas = jax.nn.softmax(3.0 * jax.random.normal(k1, (rows, tk)), axis=-1)
    got = patch_bitmap(sas, patch, PSSA_THRESHOLD, use_kernel=True,
                       interpret=interpret)
    ref = patch_bitmap(sas, patch, PSSA_THRESHOLD, use_kernel=False)
    out["bitmap_bit_identical"] = all(
        np.array_equal(np.asarray(g), np.asarray(r)) for g, r in zip(got, ref))

    m, kd, n = ffn
    x = jax.nn.gelu(jax.random.normal(k2, (m, kd)))
    w = jax.random.normal(k3, (kd, n)) * kd ** -0.5
    important = jax.random.bernoulli(k4, 0.5, (m,))
    got = bitslice_matmul(x, w, important, interpret=interpret)
    ref = bitslice_matmul(x, w, important, use_kernel=False)
    out["dbsc_bit_identical"] = bool(np.array_equal(np.asarray(got),
                                                    np.asarray(ref)))

    x = jax.random.normal(k5, tokens)
    x_ref = x + 1e-3 * jax.random.normal(k6, tokens)
    got = patch_delta(x, x_ref, patch, 1e-3, use_kernel=True,
                      interpret=interpret)
    ref = patch_delta(x, x_ref, patch, 1e-3, use_kernel=False)
    out["reuse_delta_bit_identical"] = all(
        np.array_equal(np.asarray(g), np.asarray(r)) for g, r in zip(got, ref))

    q, k, v = pssa_margin_inputs(jax.random.PRNGKey(12), pssa_shape)
    got = pssa_counters(q, k, v, patch, True, interpret)
    ref = pssa_counters(q, k, v, patch, False)
    out["pssa_counters_bit_identical"] = all(
        np.array_equal(g, r) for g, r in zip(got, ref))
    return out


# ----------------------------------------------------------------------------
# phase 2: the served path
# ----------------------------------------------------------------------------
def serve_phase(cfg, n_requests: int = REQUESTS, slots: int = SLOTS,
                seed: int = 7):
    """Serve ``n_requests`` through the slot runtime; (metrics, requests).

    The steps of ``serve_diffusion.serve_continuous`` (engine, synthetic
    requests, ``ContinuousScheduler`` warmup, then its run with the ledger),
    taken here so that each served ``Request.image`` can be read back.
    """
    import jax

    from repro.diffusion.engine import DiffusionEngine
    from repro.launch.scheduler import ContinuousScheduler, make_requests

    eng = DiffusionEngine(cfg, key=jax.random.PRNGKey(0))
    requests = make_requests(cfg, n_requests, seed=seed)
    sched = ContinuousScheduler(eng, slots)
    compile_s = sched.warmup()
    metrics = sched.run(requests, ledger=True)
    metrics.pop("state")
    metrics["compile_s"] = compile_s
    return metrics, requests


# ----------------------------------------------------------------------------
# phase 3: one request one-shot, fused route against the reference route
# ----------------------------------------------------------------------------
def oneshot(cfg, request) -> dict:
    """Request ``request`` through ``DiffusionEngine.generate`` (batch 1)."""
    import jax
    import jax.numpy as jnp

    from repro.diffusion.engine import DiffusionEngine

    eng = DiffusionEngine(cfg, key=jax.random.PRNGKey(0))
    t0 = time.perf_counter()
    out = eng.generate(request.tokens, None,
                       uncond_tokens=request.uncond_tokens,
                       latents=jnp.copy(request.latents))
    first_call_s = time.perf_counter() - t0
    stats = jax.device_get(out.stats)
    return {"image": jax.device_get(out.images)[0],
            "first_call_s": first_call_s,
            "nnz": [s.nnz for s in stats.pssa],
            "ones_xor": [s.bitmap_ones_xor for s in stats.pssa],
            "important": [t.important for t in stats.tips]}


def compare_counters(a: dict, b: dict) -> dict:
    """Per counter kind: mismatching (step, layer) entries and totals."""
    import numpy as np
    out = {}
    for name in ("nnz", "ones_xor", "important"):
        xa = np.stack([np.asarray(x, np.float64).reshape(len(x), -1).sum(-1)
                       for x in a[name]])
        xb = np.stack([np.asarray(x, np.float64).reshape(len(x), -1).sum(-1)
                       for x in b[name]])
        exact = all(np.array_equal(np.asarray(x), np.asarray(y))
                    for x, y in zip(a[name], b[name]))
        out[name] = {"equal": exact,
                     "entries_differing": int((xa != xb).sum()),
                     "entries": int(xa.size),
                     "total_fused": float(xa.sum()),
                     "total_reference": float(xb.sum())}
    return out


def parity_phase(cfg, cfg_ref, request, served_image) -> dict:
    fused = oneshot(cfg, request)
    ref = oneshot(cfg_ref, request)
    check_images([fused["image"], ref["image"]], image_shape(cfg))
    return {
        "fused_vs_reference_rel_l2": rel_l2(fused["image"], ref["image"]),
        "served_vs_oneshot_rel_l2": rel_l2(served_image, fused["image"]),
        "counters": compare_counters(fused, ref),
        "first_call_s": {"fused": fused["first_call_s"],
                         "reference": ref["first_call_s"]},
    }


def peak_bytes() -> int | None:
    import jax
    stats = jax.devices()[0].memory_stats() or {}
    return stats.get("peak_bytes_in_use")


def run_one_chip(cfg, cfg_ref, interpret: bool | None = None,
                 **kernel_shapes) -> None:
    t0 = time.perf_counter()
    described = cfg.unet.effective_kernel_policy().describe()
    say(f"kernel policy: {json.dumps(described, sort_keys=True)}")

    exact = kernel_exactness_phase(interpret=interpret, **kernel_shapes)
    say(f"integer-exact kernels: {json.dumps(exact)}")
    for name, ok in exact.items():
        require(ok, f"{name} is False")
    report = pssa_random_report(kernel_shapes.get("pssa_shape", PSSA_SHAPE),
                                kernel_shapes.get("patch", 64), interpret)
    say(f"PSSA counters on random inputs, kernel vs reference: "
        f"{json.dumps(report)}")

    metrics, requests = serve_phase(cfg)
    check_images([r.image for r in requests], image_shape(cfg))
    energy = metrics.get("energy", {})
    say(f"served {metrics['requests']} requests: compile_s "
        f"{metrics['compile_s']:.3f}, makespan_s {metrics['makespan_s']:.3f}, "
        f"engine_steps {metrics['engine_steps']}, "
        f"mj_per_iter_with_ema {energy.get('mj_per_iter_with_ema')}")
    require(bool(energy), "the --ledger report is empty")

    par = parity_phase(cfg, cfg_ref, requests[0], requests[0].image)
    say(f"one-shot first call (compile + run) s: "
        f"{json.dumps(par['first_call_s'])}")
    say(f"fused vs reference image rel L2 "
        f"{par['fused_vs_reference_rel_l2']:.6g} (tol {IMAGE_REL_L2_TOL}); "
        f"served vs one-shot fused {par['served_vs_oneshot_rel_l2']:.6g}")
    for name, c in par["counters"].items():
        say(f"counter {name}: equal={c['equal']} "
            f"differing {c['entries_differing']}/{c['entries']} "
            f"total fused {c['total_fused']:.0f} "
            f"reference {c['total_reference']:.0f}")
    require(par["fused_vs_reference_rel_l2"] <= IMAGE_REL_L2_TOL,
            "fused image is not within tolerance of the reference image")
    require(par["served_vs_oneshot_rel_l2"] <= IMAGE_REL_L2_TOL,
            "served image is not within tolerance of the one-shot image")
    say(f"wall_s {time.perf_counter() - t0:.3f}, "
        f"peak_bytes_in_use {peak_bytes()}")


# ----------------------------------------------------------------------------
# four chips: data-parallel serving against dp=1
# ----------------------------------------------------------------------------
def generate_in_batches(eng, toks, latents, micro_batch: int) -> dict:
    """The requests through ``eng.generate``, ``micro_batch`` per call.

    Returns the image batches as the engine placed them, each call's
    scalar ledger stats (``UNetStats.ledger_fetch``), and the compile and
    serving seconds.
    """
    import jax.numpy as jnp

    uncond = jnp.zeros((micro_batch, toks.shape[1]), jnp.int32)
    compile_s = eng.warmup(micro_batch, True)
    images, stats, wall = [], [], 0.0
    for i in range(0, toks.shape[0], micro_batch):
        rows = slice(i, i + micro_batch)
        out = eng.generate(toks[rows], None, uncond_tokens=uncond,
                           latents=jnp.copy(latents[rows]))
        wall += eng.last_wall_s
        images.append(out.images)
        stats.append(out.stats.ledger_fetch())
    return {"images": images, "stats": stats, "compile_s": compile_s,
            "wall_s": wall}


def ledger_summary(cfg, stats) -> dict:
    """The ``--ledger`` summary over several engine calls' stats."""
    from repro.diffusion.pipeline import energy_report_multi
    return {k: float(v)
            for k, v in energy_report_multi(cfg, stats).summary().items()}


def max_rel_diff(a: dict, b: dict) -> float:
    return max(abs(a[k] - v) / max(abs(v), 1e-30) for k, v in b.items())


def four_chip_phase(cfg, dp: int = 4, n_requests: int = REQUESTS) -> dict:
    """dp=``dp`` over ``make_data_mesh`` against dp=1, in this process."""
    import jax
    import numpy as np

    from repro.diffusion.engine import DiffusionEngine
    from repro.launch.mesh import make_data_mesh
    from repro.launch.serve_diffusion import synthetic_requests

    toks = synthetic_requests(cfg, n_requests)
    key = jax.random.PRNGKey(0)
    # one engine alive at a time: each holds its own copy of the weights
    eng = DiffusionEngine(cfg, key=key, mesh=make_data_mesh(dp))
    latents = jax.device_get(eng.init_latents(n_requests,
                                              jax.random.PRNGKey(1)))
    run_dp = generate_in_batches(eng, toks, latents, n_requests)
    sharding = run_dp["images"][0].sharding
    del eng
    eng = DiffusionEngine(cfg, key=key)
    run_b1 = generate_in_batches(eng, toks, latents, n_requests // dp)
    run_b4 = generate_in_batches(eng, toks, latents, n_requests)
    del eng

    def images(run):
        return np.concatenate([jax.device_get(x) for x in run["images"]])

    e_dp, e_b1, e_b4 = (ledger_summary(cfg, r["stats"])
                        for r in (run_dp, run_b1, run_b4))
    # planted faults in the dp=1 ledger: the last request's counters
    # dropped (zeroed, as a lost shard's psum term) or replaced by its
    # neighbour's (a duplicated shard)
    head, last = run_b1["stats"][:-1], run_b1["stats"][-1]
    planted = {"dropped": head + [jax.tree.map(np.zeros_like, last)],
               "duplicated": head + head[-1:]}
    return {
        "devices_holding_batch": len(sharding.device_set),
        "batch_sharded": not sharding.is_fully_replicated,
        "images": list(images(run_dp)),
        # against dp=1 at the same per-device batch: the checked pair
        "ledger_bit_identical": e_dp == e_b1,
        "ledger_max_rel_diff": max_rel_diff(e_dp, e_b1),
        "images_bit_identical": bool(np.array_equal(images(run_dp),
                                                    images(run_b1))),
        "images_rel_l2": rel_l2(images(run_dp), images(run_b1)),
        # against dp=1 with all the requests on one device: reported
        "same_micro_batch": {
            "ledger_bit_identical": e_dp == e_b4,
            "ledger_max_rel_diff": max_rel_diff(e_dp, e_b4),
            "images_rel_l2": rel_l2(images(run_dp), images(run_b4)),
        },
        "planted_fault_ledger_max_rel_diff": {
            name: max_rel_diff(ledger_summary(cfg, stats), e_b1)
            for name, stats in planted.items()},
        "compile_s": {"dp": run_dp["compile_s"], "one": run_b1["compile_s"],
                      "one_same_micro_batch": run_b4["compile_s"]},
        "serve_wall_s": {"dp": run_dp["wall_s"], "one": run_b1["wall_s"],
                         "one_same_micro_batch": run_b4["wall_s"]},
    }


def run_four_chips(cfg, dp: int = 4) -> None:
    t0 = time.perf_counter()
    out = four_chip_phase(cfg, dp=dp)
    check_images(out.pop("images"), image_shape(cfg))
    say(f"dp={dp} vs dp=1: {json.dumps(out, sort_keys=True)}")
    require(out["devices_holding_batch"] == dp and out["batch_sharded"],
            f"the image batch is not sharded over {dp} devices")
    require(out["planted_fault_ledger_max_rel_diff"]["dropped"]
            > LEDGER_REL_TOL,
            "the ledger limit misses one request's counters dropped")
    require(out["ledger_max_rel_diff"] <= LEDGER_REL_TOL,
            "the dp ledger is not within tolerance of the dp=1 ledger")
    require(out["images_rel_l2"] <= IMAGE_REL_L2_TOL,
            "dp images are not within tolerance of dp=1")
    say(f"wall_s {time.perf_counter() - t0:.3f}, "
        f"peak_bytes_in_use {peak_bytes()}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only data-parallel serving over four chips "
                         "against dp=1")
    args = ap.parse_args(argv)

    dev = device_info()
    if dev["platform"] != "tpu":
        print(f"chip_smoke: JAX found no TPU (platform {dev['platform']!r}); "
              f"this check runs on the chip only", file=sys.stderr)
        return 2
    want = 4 if args.four_chips else 1
    if dev["count"] < want:
        print(f"chip_smoke: needs {want} TPU devices, found {dev['count']}",
              file=sys.stderr)
        return 2
    try:
        from repro.launch.platform import use_compile_cache
        say(f"device_kind {dev['kind']}, devices {dev['count']}, "
            f"compile cache {use_compile_cache()}")
        cfg = build_config("auto")
        check_compiled_policy(cfg.unet.effective_kernel_policy().describe())
        require(image_shape(cfg) == FULL_IMAGE,
                f"geometry {image_shape(cfg)} is not the full 512x512")
        if args.four_chips:
            run_four_chips(cfg)
        else:
            run_one_chip(cfg, build_config("reference"))
    except CheckFailed as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    except Exception:
        traceback.print_exc()
        return 1
    print(json.dumps({"ok": True, "device": dev}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
