"""Mesh construction (production + elastic variants).

All constructors are FUNCTIONS so importing this module never touches jax
device state (the dry-run must set XLA_FLAGS before first jax init).  Every
mesh has Auto axes: the engine and the models annotate their shardings
with ``NamedSharding`` and leave propagation to the compiler, which the
Explicit axes ``jax.make_mesh`` defaults to would refuse (sharding-typed
ops such as ``jnp.take`` on a sharded operand raise).
"""
from __future__ import annotations

import os

import jax

# the data-axis naming lives with the kernels that split over it
from repro.kernels.runtime import dp_axes_of, dp_size_of  # noqa: F401


def _auto_mesh(shape: tuple, axes: tuple):
    return jax.make_mesh(shape, axes, axis_types=(
        jax.sharding.AxisType.Auto,) * len(axes))


def simulate_host_devices(count: int) -> None:
    """Expose ``count`` fake host devices (CPU) to this process.

    Same ``XLA_FLAGS`` trick as the dry-run: must be called BEFORE the
    first jax backend init, so callers (``serve_diffusion --mesh N``,
    ``benchmarks/bench_sharded_engine``) invoke it from their entrypoint
    prior to any jax device use.  A pre-existing flag with a DIFFERENT
    count (e.g. exported by an earlier recipe) is replaced, not silently
    kept — the caller asked for ``count`` devices.
    """
    import re
    flag = f"--xla_force_host_platform_device_count={count}"
    cur = os.environ.get("XLA_FLAGS", "")
    if "--xla_force_host_platform_device_count" in cur:
        cur = re.sub(r"--xla_force_host_platform_device_count=\d+", flag,
                     cur)
        os.environ["XLA_FLAGS"] = cur
    else:
        os.environ["XLA_FLAGS"] = (cur + " " + flag).strip()


def mesh_signature(mesh) -> tuple | None:
    """Hashable identity of a mesh: axis names, sizes, and device ids.

    Used to key compiled-executable caches (``DiffusionEngine``): two
    meshes with the same signature shard a program identically, and an
    elastic relaunch onto different devices (or a reshaped mesh) must not
    reuse executables compiled for the old placement.
    """
    if mesh is None:
        return None
    return (tuple(mesh.axis_names),
            tuple(int(mesh.shape[a]) for a in mesh.axis_names),
            tuple(int(d.id) for d in mesh.devices.flat))


def make_production_mesh(*, multi_pod: bool = False, tp_size: int = 16):
    """16x16 single-pod (256 chips) or 2x16x16 multi-pod (512 chips).

    ``tp_size`` re-slices the same chips into (256/tp, tp) — the §Perf
    hillclimb uses tp=8 for archs whose head count does not divide 16
    (yi-34b: 56 heads -> GSPMD pads to 64 at tp=16; 56 % 8 == 0)."""
    per_pod = 256
    assert per_pod % tp_size == 0, tp_size
    dp = per_pod // tp_size
    shape = (2, dp, tp_size) if multi_pod else (dp, tp_size)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_data_mesh(dp: int):
    """(dp, 1) pure data-parallel mesh over the first ``dp`` live devices.

    Unlike ``make_elastic_mesh`` this does not insist on using every
    device — serving picks its dp degree (``serve_diffusion --mesh N``)
    and leaves the rest to other replicas.
    """
    import numpy as np
    devs = jax.devices()
    if len(devs) < dp:
        raise ValueError(f"--mesh {dp} needs {dp} devices, "
                         f"have {len(devs)}")
    return jax.sharding.Mesh(np.asarray(devs[:dp]).reshape(dp, 1),
                             ("data", "model"))


def make_elastic_mesh(tp_size: int = 16):
    """Build the largest (data, model) mesh from the LIVE device count.

    Elastic scaling: after losing hosts, relaunch calls this and gets a
    smaller-but-valid mesh (model axis preserved so param shards stay
    compatible; the data axis absorbs the loss).
    """
    n = len(jax.devices())
    tp = min(tp_size, n)
    while n % tp:
        tp -= 1
    return _auto_mesh((n // tp, tp), ("data", "model"))


def make_smoke_mesh():
    """1x1 mesh on the single CPU device (tests exercise the sharded code
    paths without fake devices)."""
    return _auto_mesh((1, 1), ("data", "model"))
