"""PSXU Pallas kernel (paper §III-B): bitmap generate + patch-XOR + popcount.

The Patch-Similarity XOR Unit takes one 64-wide row slab of the pruned SAS,
generates the sparsity bitmap (BGU), XORs horizontally-adjacent bitmap
patches (RXU, reconfigurable to 16/32/64-wide patches), and hands the result
to the CSR encoder.  The encoder's cost is fully determined by the per-patch
popcounts, so the kernel outputs:

  * the packed XOR'd bitmap (uint32 words, 32 lanes per word) — the payload a
    DMA engine would move, and
  * per-(row, patch) popcounts of the XOR'd bitmap — the CSR col_idx counts.

TPU mapping: the comparator bank and XOR tree are VPU-lane-parallel ops —
the XOR pairs each lane with the lane ``patch`` to its left by rotating the
row (``pltpu.roll``), so the bitmap keeps its (rows, Tk) shape.  Popcount
and bit-pack are matmuls of the 0/1 delta bitmap against constant
indicator matrices: a lane-to-patch matrix, and two lane-to-word matrices
holding the powers of two of each word's low and high 16 bits.  Every
operand is exact in bf16 and every sum stays below 2^16, so the MXU's
float32 accumulators are exact.  Grid tiles the query rows; the full key
row fits one block (SAS rows are <= 4096 in BK-SDM).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.runtime import resolve_interpret


def _indicators(tk: int, patch: int):
    """(Tk, Tk/patch) lane->patch, (Tk, Tk/32) low/high word-bit weights."""
    lane = jnp.arange(tk)[:, None]
    bit = lane % 32
    in_word = lane // 32 == jnp.arange(tk // 32)[None, :]
    weight = jnp.left_shift(1, bit % 16).astype(jnp.float32)
    seg = (lane // patch == jnp.arange(tk // patch)[None, :])
    low = jnp.where(in_word & (bit < 16), weight, 0.0)
    high = jnp.where(in_word & (bit >= 16), weight, 0.0)
    return tuple(m.astype(jnp.bfloat16) for m in (seg, low, high))


def _kernel(sas_ref, seg_ref, low_ref, high_ref, packed_ref, counts_ref, *,
            patch: int, threshold: float):
    s = sas_ref[...]                               # (br, Tk)
    br, tk = s.shape
    bits = (s >= threshold).astype(jnp.int32)      # BGU: bitmap generator bank

    # RXU: XOR each patch with its left neighbour (keep the first patch).
    if patch == tk:
        delta = bits
    else:
        left = pltpu.roll(bits, patch, 1)          # left[:, j] = bits[:, j-patch]
        lane = jax.lax.broadcasted_iota(jnp.int32, (br, tk), 1)
        delta = (bits != jnp.where(lane < patch, 0, left)).astype(jnp.int32)
    delta = delta.astype(jnp.float32).astype(jnp.bfloat16)

    def count(m_ref):
        return jnp.dot(delta, m_ref[...],
                       preferred_element_type=jnp.float32).astype(jnp.int32)

    # popcount per (row, patch) — drives the local CSR col_idx cost
    counts_ref[...] = count(seg_ref)
    # pack 32 lanes per 32-bit word (bit 31 wraps to the int32 sign bit;
    # the wrapper reinterprets the words as uint32)
    packed_ref[...] = count(low_ref) + jnp.left_shift(count(high_ref), 16)


@functools.partial(jax.jit, static_argnames=("patch", "threshold", "br",
                                             "interpret"))
def patch_bitmap_kernel(sas: jax.Array, patch: int, threshold: float,
                        br: int = 64, interpret: bool | None = None):
    """(R, Tk) pruned-SAS slab -> (packed (R, Tk/32) uint32, counts (R, Tk/patch))."""
    rows, tk = sas.shape
    assert tk % patch == 0 and tk % 32 == 0, (tk, patch)
    assert rows % br == 0, (rows, br)
    seg, low, high = _indicators(tk, patch)
    whole = lambda m: pl.BlockSpec(m.shape, lambda i: (0, 0))

    packed, counts = pl.pallas_call(
        functools.partial(_kernel, patch=patch, threshold=threshold),
        grid=(rows // br,),
        in_specs=[pl.BlockSpec((br, tk), lambda i: (i, 0)),
                  whole(seg), whole(low), whole(high)],
        out_specs=[
            pl.BlockSpec((br, tk // 32), lambda i: (i, 0)),
            pl.BlockSpec((br, tk // patch), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((rows, tk // 32), jnp.int32),
            jax.ShapeDtypeStruct((rows, tk // patch), jnp.int32),
        ],
        interpret=resolve_interpret(interpret),
    )(sas, seg, low, high)
    return jax.lax.bitcast_convert_type(packed, jnp.uint32), counts
