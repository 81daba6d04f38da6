"""Public temporal-reuse ops: patch delta + gather/scatter row plans.

``patch_delta`` is the dispatchable change-detection op (reference vs
Pallas kernel, selected by ``KernelPolicy.reuse``).  The plan helpers
below are pure index arithmetic shared by both routes — the model layer
(``diffusion.unet._transformer_block``) uses them to gather only the
active patch rows into the attention/FFN kernels and scatter the results
back over the cached activations.

Exactness: the plan orders ACTIVE patches first in ascending patch index
(stable argsort of the inverted bitmap), so an all-active row yields the
identity permutation and gather -> compute -> scatter returns the dense
result bit-for-bit (attention queries and FFN rows are row-independent;
the scatter is a pure copy).  When active patches exceed the static
capacity, the highest-index actives are dropped — deterministic, and
counted honestly by the gate (dropped patches fall back to the cache).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.patch_reuse.kernel import patch_delta_kernel
from repro.kernels.patch_reuse.ref import patch_delta_ref
from repro.kernels.runtime import data_parallel, pad_axis_to


@functools.partial(jax.jit, static_argnames=("patch", "threshold",
                                             "use_kernel", "interpret",
                                             "bp"))
def patch_delta(x: jax.Array, x_ref: jax.Array, patch: int,
                threshold: float, use_kernel: bool = True,
                interpret: bool | None = None, bp: int = 8):
    """(B, T, C) tokens vs cached reference -> (delta, active) per patch.

    ``delta`` is (B, T/patch) float32 max-abs difference; ``active`` the
    (B, T/patch) bool bitmap ``delta >= threshold``.  ``threshold=0``
    marks every patch active (dense bit-exactness).  ``patch`` must
    divide T.
    """
    b, t, c = x.shape
    assert t % patch == 0, (t, patch)
    if use_kernel:
        fold = lambda a: a.reshape(b, t // patch, patch * c)
        xf = pad_axis_to(fold(x), bp, 1)
        rf = pad_axis_to(fold(x_ref), bp, 1)
        kernel = functools.partial(patch_delta_kernel, bp=bp,
                                   interpret=interpret)
        delta = data_parallel(kernel, (xf, rf))[:, :t // patch]
    else:
        delta = patch_delta_ref(x, x_ref, patch)
    return delta, delta >= threshold


# ---------------------------------------------------------------------------
# Autotune hooks (repro.kernels.autotune): geometry = (b, t, c, patch)
# ---------------------------------------------------------------------------
AUTOTUNE_KNOBS = ("reuse_block_patches",)


def autotune_candidates(geom: tuple) -> tuple:
    """Patch-block candidates for a (b, t, c, patch) geometry."""
    b, t, c, patch = geom
    n_patches = t // patch
    sizes = sorted({min(s, n_patches) for s in (8, 16, 32, 64, 128)})
    return tuple({"reuse_block_patches": s} for s in sizes)


def autotune_probe(geom: tuple, blocks: dict, *,
                   interpret: bool | None = None):
    """(jitted fn, args) the autotuner times for one block config."""
    b, t, c, patch = geom
    x = jax.random.normal(jax.random.PRNGKey(0), (b, t, c), jnp.float32)
    x_ref = x + 1e-4 * jax.random.normal(jax.random.PRNGKey(1), (b, t, c),
                                         jnp.float32)
    fn = jax.jit(functools.partial(
        patch_delta, patch=patch, threshold=1e-3, interpret=interpret,
        bp=blocks["reuse_block_patches"]))
    return fn, (x, x_ref)


def reuse_plan(active: jax.Array, cap: int):
    """(B, P) active bitmap -> static-width gather plan (order, gate).

    ``order`` (B, cap) int32 lists patch indices with actives first in
    ascending index order (stable sort — all-active rows get the identity
    prefix); ``gate`` (B, cap) marks which plan slots hold a genuinely
    active patch (padding slots scatter nothing).
    """
    order = jnp.argsort(jnp.logical_not(active), axis=1,
                        stable=True)[:, :cap].astype(jnp.int32)
    gate = jnp.take_along_axis(active, order, axis=1)
    return order, gate


def plan_token_rows(order: jax.Array, patch: int):
    """Patch-index plan -> token-row indices (B, cap*patch), plan-major."""
    b, k = order.shape
    rows = order[:, :, None] * patch \
        + jnp.arange(patch, dtype=jnp.int32)[None, None, :]
    return rows.reshape(b, k * patch)


def gather_rows(x: jax.Array, rows: jax.Array) -> jax.Array:
    """(B, T, C) tokens + (B, R) row ids -> (B, R, C) gathered rows."""
    return jnp.take_along_axis(x, rows[:, :, None], axis=1)


def scatter_rows(base: jax.Array, rows: jax.Array, values: jax.Array,
                 gate_rows: jax.Array) -> jax.Array:
    """Write gated computed rows over the cached activations.

    ``base`` (B, T, C) is the cache; ``values`` (B, R, C) the rows
    computed on the gathered plan; ``gate_rows`` (B, R) masks plan
    padding (ungated slots keep the cache payload even though their row
    index aliases a real token).  Plan rows are unique per batch row, so
    the scatter is a deterministic copy.
    """
    cur = jnp.take_along_axis(base, rows[:, :, None], axis=1)
    vals = jnp.where(gate_rows[:, :, None], values, cur)
    bidx = jnp.arange(base.shape[0], dtype=jnp.int32)[:, None]
    return base.at[bidx, rows].set(vals)
