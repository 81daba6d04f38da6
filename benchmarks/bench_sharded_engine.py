"""Data-parallel serving throughput: dp ∈ {1, 2, 4, 8} over a data mesh.

On an accelerator host the sweep runs in THIS process over the real
devices (a chip belongs to one process, so a child could not open it) and
stops at the device count.  With ``JAX_PLATFORMS=cpu`` each dp degree runs
in its OWN subprocess instead, since the fake-host-device count is an
``XLA_FLAGS`` decision made before jax initializes, like the dry-run.  The
choice is made before this process touches jax (``launch.platform``).
Each run is at a fixed per-device batch — weak scaling, the
serving-throughput question: "how many imgs/s do N chips sustain?".  Each
run also checks the §6 parity contract: engine output on the mesh vs the
unsharded engine at the same seed — bit-identical integer PSSA counters
(the ledger is drift-free by construction), images bit-identical at dp=1
and within float tolerance at dp>1 (XLA tiles per-shard batches
differently; recorded, not hidden).

Honest-reporting note: on a shared-memory CPU imgs/s scaling saturates at
the HOST's physical core count — data parallelism cannot mint compute —
so the json records ``host_cores`` and the core-ceiling-relative
efficiency alongside the raw ratios.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys

ROOT = os.path.join(os.path.dirname(__file__), "..")

PER_DEVICE_BATCH = 2
REQUEST_ROUNDS = 4      # requests = rounds * micro_batch (even: the padded
                        # -tail path is pinned by tests/test_sharded_engine;
                        # a pad-heavy tail call would understate imgs/s)
DP_DEGREES = (1, 2, 4, 8)


def measure(dp: int) -> dict:
    """Serve at dp over ``make_data_mesh(dp)`` and check parity, in-process."""
    import argparse

    import jax
    import numpy as np

    from repro.diffusion.engine import DiffusionEngine
    from repro.launch import serve_diffusion as S
    from repro.launch.mesh import make_data_mesh

    cfg = S.make_config(argparse.Namespace(
        smoke=True, steps=3, guidance=1.0, kernels="reference", tips="fixed"))
    mesh = make_data_mesh(dp) if dp > 1 else None
    mb = PER_DEVICE_BATCH * dp
    reqs = S.synthetic_requests(cfg, REQUEST_ROUNDS * mb)
    metrics = S.serve(cfg, reqs, mb, ledger=True, mesh=mesh)

    # parity vs the unsharded engine at the same seed (fixed latents)
    key = jax.random.PRNGKey(42)
    toks = S.synthetic_requests(cfg, mb, seed=5)
    lat = jax.random.normal(jax.random.PRNGKey(3),
                            (mb, cfg.unet.latent_size, cfg.unet.latent_size,
                             cfg.unet.in_channels))
    ref = DiffusionEngine(cfg, key=key).generate(toks, None,
                                                 latents=lat.copy())
    shd = DiffusionEngine(cfg, key=key, mesh=mesh).generate(
        toks, None, latents=lat.copy()) if mesh is not None else ref
    ri, si = np.asarray(ref.images), np.asarray(shd.images)
    metrics["parity"] = {
        "images_bit_identical": bool(np.array_equal(ri, si)),
        "images_max_abs_diff": float(np.abs(ri - si).max()),
        "stats_counters_bit_identical": bool(all(
            np.array_equal(np.asarray(x.nnz), np.asarray(y.nnz))
            and np.array_equal(np.asarray(x.bitmap_ones_xor),
                               np.asarray(y.bitmap_ones_xor))
            for x, y in zip(ref.stats.pssa, shd.stats.pssa))),
    }
    return metrics


_CHILD = r"""
import json, sys
dp = int(sys.argv[1])
if dp > 1:
    from repro.launch.mesh import simulate_host_devices
    simulate_host_devices(dp)
from benchmarks.bench_sharded_engine import measure
print("BENCH_JSON:" + json.dumps(measure(dp)))
"""


def _run_child(dp: int) -> dict:
    env = dict(os.environ, PYTHONPATH=os.pathsep.join(
        [os.path.join(ROOT, "src"), ROOT, os.environ.get("PYTHONPATH", "")]))
    r = subprocess.run([sys.executable, "-c", _CHILD, str(dp)],
                       env=env, capture_output=True, text=True, timeout=580)
    for line in r.stdout.splitlines():
        if line.startswith("BENCH_JSON:"):
            return json.loads(line[len("BENCH_JSON:"):])
    raise RuntimeError(f"dp={dp} child failed:\n{r.stdout}\n{r.stderr}")


def run() -> dict:
    from repro.launch.platform import cpu_forced

    cores = os.cpu_count() or 1
    if cpu_forced():
        per_dp = {dp: _run_child(dp) for dp in DP_DEGREES}
    else:
        import jax
        n = len(jax.devices())
        per_dp = {dp: measure(dp) for dp in DP_DEGREES if dp <= n}
    base = per_dp[1]["imgs_per_s"]
    scaling = {dp: m["imgs_per_s"] / max(base, 1e-9)
               for dp, m in per_dp.items()}
    return {
        "mode": "weak scaling (fixed per-device batch "
                f"{PER_DEVICE_BATCH}, smoke geometry, 3 steps)",
        "host_cores": cores,
        "imgs_per_s": {dp: m["imgs_per_s"] for dp, m in per_dp.items()},
        "iter_wall_ms": {dp: m["iter_wall_ms"] for dp, m in per_dp.items()},
        "scaling_vs_dp1": scaling,
        "scaling_dp4_over_dp1": scaling.get(4),
        # the dp degree this host can actually parallelize (dp threads
        # beyond the core count just time-slice)
        "scaling_at_host_core_dp": scaling.get(
            max(d for d in per_dp if d <= cores), scaling[1]),
        # dp cannot beat the physical core count on a shared-memory host
        "efficiency_vs_core_ceiling": {
            dp: scaling[dp] / max(min(dp, cores), 1)
            for dp in per_dp},
        "parity": {dp: m["parity"] for dp, m in per_dp.items()},
        "energy_headline_mj_per_iter": {
            dp: m["energy"]["mj_per_iter_with_ema"]
            for dp, m in per_dp.items() if "energy" in m},
        "padded_rows": {dp: m["padded_rows"] for dp, m in per_dp.items()},
    }


if __name__ == "__main__":
    print(json.dumps(run(), indent=2))
