"""Per-patch change-bitmap Pallas kernel (temporal reuse front end).

Streams the current and cached token activations block-by-block and emits
the per-patch max-abs delta — the signal the reuse plan thresholds into an
active-patch bitmap.  One grid step owns ``bp`` patches of one batch row;
the patch's tokens and channels arrive pre-folded into the trailing axis
(``patch * C``), so the reduction is a single row-wise max and the block
is MXU/VPU-friendly (last dim is the wide one).

The wrapper (``ops.py``) pads the patch axis to the block multiple with
zeros on BOTH operands — padded patches read delta 0 and are sliced off,
so padding is exact.  The delta leaves the kernel as (B, P, 1) in
(1, bp, 1) blocks: a row-wise max lands one value per sublane, and a
trailing unit dimension is a block shape the TPU's (8, 128) tiling accepts
(``bp`` a multiple of 8, or the whole padded patch axis).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.runtime import resolve_interpret


def _kernel(x_ref, r_ref, o_ref):
    d = jnp.abs(x_ref[0].astype(jnp.float32) - r_ref[0].astype(jnp.float32))
    o_ref[0] = jnp.max(d, axis=-1, keepdims=True)


@functools.partial(jax.jit, static_argnames=("bp", "interpret"))
def patch_delta_kernel(xf: jax.Array, rf: jax.Array, bp: int = 8,
                       interpret: bool | None = None) -> jax.Array:
    """(B, P, patch*C) folded tokens/reference -> (B, P) max-abs delta.

    ``P`` must be a multiple of ``bp`` (the ops wrapper pads).
    ``interpret=None`` auto-selects from the backend.
    """
    b, p, w = xf.shape
    assert p % bp == 0, (p, bp)
    return pl.pallas_call(
        _kernel,
        grid=(b, p // bp),
        in_specs=[
            pl.BlockSpec((1, bp, w), lambda i, j: (i, j, 0)),
            pl.BlockSpec((1, bp, w), lambda i, j: (i, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, bp, 1), lambda i, j: (i, j, 0)),
        out_shape=jax.ShapeDtypeStruct((b, p, 1), jnp.float32),
        interpret=resolve_interpret(interpret),
    )(xf, rf).reshape(b, p)
