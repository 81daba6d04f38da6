"""DBSC bit-slice matmul Pallas kernel (paper §IV-B).

The Dual-mode Bit-Slice Core multiplies a 12-bit unsigned activation by an
8-bit signed weight as TWO int7 x int8 bit-slice products:

    x = hi * 2^6 + lo            (hi, lo in [0, 63])
    y = (hi @ w) << 6 + lo @ w

Rows flagged low-precision (TIPS INT6) live on a 64x-coarser grid, so their
``lo`` plane is all-zero and the silicon *skips the low-slice pass* — here the
skip is expressed by masking the ``lo`` operand with the precision flag, and
the energy model credits the skipped slice (energy.MAC_PJ['int6x8']).

Both slices lie in [0, 63] and the weights are signed INT8, so the wrapper
narrows every operand to int8 (after the precision mask) and the kernel runs
int8 x int8 -> int32 MXU matmuls: exact, and the only integer matmul the
TPU's MXU has (it rejects int32 x int32).

TPU mapping of the DBSC's dual *stationary* modes: both keep the full-K
stripe of the stationary operand resident in VMEM and sweep the other operand
with the innermost grid axis, so the stationary block's index map is constant
along the sweep (true reuse, no re-fetch):

  * ``weight_stationary`` (transformer/FFN mode): grid (N-blocks, M-blocks);
    the (K, bn) weight stripe is pinned while activations stream through.
  * ``input_stationary`` (CNN mode): grid (M-blocks, N-blocks); the (bm, K)
    activation stripe is pinned while weight columns stream through.

Each output block is visited exactly once (K is unrolled inside the kernel
with a fori_loop over bk-wide slabs), so there is no cross-iteration
accumulator hazard.  VMEM bound: (bm + bn) * K int8 operands — K <= 16k at
128-wide blocks.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.runtime import resolve_interpret


def _kernel(x_hi_ref, x_lo_ref, w_ref, o_ref, *, bk: int):
    kdim = x_hi_ref.shape[-1]
    nsteps = kdim // bk

    def body(s, acc):
        sl = pl.dslice(s * bk, bk)
        hi = x_hi_ref[:, sl]
        lo = x_lo_ref[:, sl]           # zero on INT6 rows (masked on entry)
        w = w_ref[sl, :]
        acc_hi = jnp.dot(hi, w, preferred_element_type=jnp.int32)
        acc_lo = jnp.dot(lo, w, preferred_element_type=jnp.int32)
        # bit-slice adder tree: shift-and-add recombine of the two slices
        return acc + (acc_hi << 6) + acc_lo

    o_ref[...] = jax.lax.fori_loop(
        0, nsteps, body, jnp.zeros_like(o_ref))


@functools.partial(jax.jit, static_argnames=("bm", "bn", "bk", "dataflow",
                                             "interpret"))
def bitslice_matmul_kernel(x_hi: jax.Array, x_lo: jax.Array, w: jax.Array,
                           prec: jax.Array,
                           bm: int = 128, bn: int = 128, bk: int = 128,
                           dataflow: str = "weight_stationary",
                           interpret: bool | None = None) -> jax.Array:
    """int32 bit-planes (M,K), weights (K,N), precision flags (M,1) -> (M,N).

    ``prec`` 0 rows drop their low slice before the kernel; every operand
    then enters the kernel as int8 (exact for [0, 63] slices and INT8
    weights) and the (M, N) accumulators are int32.
    """
    m, kdim = x_hi.shape
    _, n = w.shape
    assert m % bm == 0 and n % bn == 0 and kdim % bk == 0, (m, n, kdim)

    if dataflow == "weight_stationary":
        # FFN/transformer mode: weight stripe pinned, M innermost.
        grid = (n // bn, m // bm)
        xmap = lambda j, i: (i, 0)
        wmap = lambda j, i: (0, j)      # constant along the inner sweep
        omap = lambda j, i: (i, j)
    elif dataflow == "input_stationary":
        # CNN mode: activation stripe pinned, N innermost.
        grid = (m // bm, n // bn)
        xmap = lambda i, j: (i, 0)      # constant along the inner sweep
        wmap = lambda i, j: (0, j)
        omap = lambda i, j: (i, j)
    else:
        raise ValueError(dataflow)

    return pl.pallas_call(
        functools.partial(_kernel, bk=bk),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, kdim), xmap),
            pl.BlockSpec((bm, kdim), xmap),
            pl.BlockSpec((kdim, bn), wmap),
        ],
        out_specs=pl.BlockSpec((bm, bn), omap),
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.int32),
        interpret=resolve_interpret(interpret),
    )(x_hi.astype(jnp.int8), (x_lo * prec).astype(jnp.int8),
      w.astype(jnp.int8))
