"""Shared runtime policy helpers for the Pallas op wrappers.

Four concerns every ``ops.py`` wrapper (and the autotuner) has in common:

* **interpret selection** — the kernels must run in Pallas interpret mode on
  CPU (the test/CI container) and compiled on a real accelerator.  The seed
  wrappers hardcoded ``interpret=True``, which made the "TPU-native" path
  permanently interpreted.  ``resolve_interpret(None)`` derives the right
  value from ``jax.default_backend()`` at trace time, so the same call site
  is TPU-real and CPU-testable.
* **block padding** — grids need block-divisible extents.  The seed
  fallback (``while t % blk: blk //= 2``) collapses to degenerate 1-wide
  blocks for non-power-of-two extents; ``pad_axis_to`` pads the operand up
  to the block multiple instead (callers slice the result back), matching
  what ``bitslice_matmul/ops.py`` always did.
* **data-parallel meshes** — the compiler cannot partition a Mosaic
  kernel over a sharded batch; ``data_parallel`` runs a kernel call per
  data shard under the context mesh instead.  Which mesh axes carry data
  (``DATA_AXES``, ``dp_axes_of``) is decided here, once, for the kernels
  and for the launchers that build the meshes.
* **min-of-k wall-clock** — the block autotuner (``kernels.autotune``) and
  every bench time jitted callables the same way: warm up outside the
  clock, then take the MINIMUM of k block-until-ready repetitions (one
  implementation here; ``benchmarks/timing.py`` re-exports it for the
  bench tree).
"""
from __future__ import annotations

import math
import time

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P


# backends with a real Pallas lowering: Mosaic on TPU, triton-pallas on
# GPU (jax.default_backend() has reported the CUDA platform as "gpu"
# historically and "cuda" in newer releases; ROCm reports "rocm")
COMPILING_BACKENDS = ("tpu", "gpu", "cuda", "rocm")


def default_interpret() -> bool:
    """Pallas interpret mode iff the default backend has no real lowering.

    TPU compiles via Mosaic and GPU via triton-pallas, so both run the
    kernels natively; only backends without a Pallas lowering (CPU — the
    test/CI container) fall back to the interpreter.  (The seed treated
    TPU as the only compiling backend, which forced interpret mode — and
    with it ``KernelPolicy.auto()``'s reference routing — on GPU.)
    """
    return jax.default_backend() not in COMPILING_BACKENDS


def resolve_interpret(interpret: bool | None) -> bool:
    """``None`` -> backend-derived default; explicit values pass through."""
    return default_interpret() if interpret is None else bool(interpret)


def round_up(n: int, mult: int) -> int:
    return ((n + mult - 1) // mult) * mult


def pad_axis_to(x: jax.Array, mult: int, axis: int) -> jax.Array:
    """Zero-pad ``axis`` up to the next multiple of ``mult`` (no-op if even)."""
    pad = (-x.shape[axis]) % mult
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


# mesh axes that split the batch; every other axis (``model``) splits weights
DATA_AXES = ("pod", "data")


def dp_axes_of(mesh) -> tuple:
    """The mesh's data axes, in mesh order."""
    return tuple(a for a in mesh.axis_names if a in DATA_AXES)


def dp_size_of(mesh) -> int:
    """Total data-parallel degree (product of the data axis sizes)."""
    return math.prod(int(mesh.shape[a]) for a in dp_axes_of(mesh))


def data_shards() -> int:
    """Devices the context mesh's data axes span (1 without a mesh)."""
    mesh = jax.sharding.get_abstract_mesh()
    return 1 if mesh.empty else dp_size_of(mesh)


def data_parallel(fn, batched: tuple, replicated: tuple = ()):
    """``fn(*batched, *replicated)``, split over the context mesh's data axes.

    Mosaic kernels cannot be partitioned by the compiler, so under a mesh
    set with ``jax.set_mesh`` whose data axes (``dp_axes_of``)
    span several devices, ``fn`` runs per shard through ``jax.shard_map``:
    the leading axis of every ``batched`` operand and of every result is
    split over those axes, and ``replicated`` operands are whole on every
    device.  The kernels are independent across their leading axis, so the
    results equal one unsplit call.  Without such a mesh this is ``fn``.
    """
    if data_shards() == 1:
        return fn(*batched, *replicated)
    spec = P(dp_axes_of(jax.sharding.get_abstract_mesh()))
    in_specs = (spec,) * len(batched) + (P(),) * len(replicated)
    return jax.shard_map(fn, in_specs=in_specs, out_specs=spec,
                         check_vma=False)(*batched, *replicated)


def timed(fn, *args, reps: int = 3, warmup: int = 1):
    """(last output, min wall seconds) of ``fn(*args)`` over ``reps``.

    ``warmup`` un-timed calls run first (the first one compiles); each
    timed call is bracketed by ``jax.block_until_ready`` so async
    dispatch never masquerades as execution.  Min — not mean — because
    the quantity under test is the compiled program's cost: everything
    that inflates a sample (GC, another process, lazy page-in) is
    one-sided noise, and a single post-compile sample drifts with
    machine warm-up across a sweep, biasing cross-config ratios.
    """
    out = None
    for _ in range(max(1, warmup)):
        out = fn(*args)
        jax.block_until_ready(out)
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        out = fn(*args)
        jax.block_until_ready(out)
        best = min(best, time.perf_counter() - t0)
    return out, best


def min_wall_s(fn, *args, reps: int = 3, warmup: int = 1) -> float:
    """Just the min wall seconds of ``timed`` (drop the output)."""
    return timed(fn, *args, reps=reps, warmup=warmup)[1]


def min_over(reps: int, sample) -> float:
    """Min of ``reps`` calls to ``sample()`` (a wall-seconds thunk).

    For callables that carry their own clock (e.g. the engine's
    ``last_wall_s``) where ``timed`` cannot bracket the work itself.
    """
    return min(sample() for _ in range(max(1, reps)))
