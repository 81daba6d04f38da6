"""Public op: float-in/float-out DBSC matmul (quantize -> kernel -> rescale).

This is the wrapper the FFN layers call.  It performs the paper's full
datapath: INT12 activation quantization (on one shared scale, so TIPS rows
can drop to the INT6 grid), bit-slice split, the Pallas kernel, and the
output rescale that the SIMD core applies.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core import quant
from repro.kernels.bitslice_matmul.kernel import bitslice_matmul_kernel
from repro.kernels.bitslice_matmul.ref import (bitslice_matmul_int8,
                                               bitslice_matmul_ref)
from repro.kernels.runtime import data_parallel, data_shards


def _pad_to(x, mult, axis):
    pad = (-x.shape[axis]) % mult
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


@functools.partial(jax.jit, static_argnames=("dataflow", "use_kernel",
                                             "interpret", "quant_path"))
def bitslice_matmul(x: jax.Array, w: jax.Array,
                    important: jax.Array | None = None,
                    dataflow: str = "weight_stationary",
                    use_kernel: bool = True,
                    interpret: bool | None = None,
                    quant_path: str = "model") -> jax.Array:
    """``x (M,K) @ w (K,N)`` through the DBSC integer datapath.

    ``important``: bool (M,) TIPS mask; None -> all rows INT12.
    ``quant_path``: ``"model"`` runs the int32 simulation (Pallas kernel
    or jnp oracle per ``use_kernel``); ``"int8"`` runs the same integer
    semantics as two real int8 x int8 -> int32 ``lax.dot_general`` calls
    (XLA maps them onto the hardware integer units) — bit-identical
    accumulators, so every downstream counter and the rescaled float
    output match the model path exactly.
    """
    if quant_path not in ("model", "int8"):
        raise ValueError(f"bitslice_matmul quant_path={quant_path!r}: "
                         f"expected 'model' or 'int8'")
    m, k = x.shape
    _, n = w.shape
    qx = quant.quantize_act(x, quant.ACT_BITS_HIGH)
    qw = quant.quantize_weight(w)
    if important is None:
        vals = qx.values
        prec = jnp.ones((m, 1), jnp.int32)
    else:
        mixed = quant.mixed_precision_quantize(x, important, qx.scale)
        vals = mixed.values
        prec = important.astype(jnp.int32)[:, None]
    hi, lo = quant.bitslice_split(vals)

    if quant_path == "int8":
        acc = bitslice_matmul_int8(hi, lo, qw.values, prec)
    elif use_kernel:
        bm = bn = bk = 128
        rows = bm * data_shards()       # every data shard gets whole blocks
        hi_p = _pad_to(_pad_to(hi, rows, 0), bk, 1)
        lo_p = _pad_to(_pad_to(lo, rows, 0), bk, 1)
        w_p = _pad_to(_pad_to(qw.values, bk, 0), bn, 1)
        prec_p = _pad_to(prec, rows, 0)
        kernel = lambda h, lo_, p, w_: bitslice_matmul_kernel(
            h, lo_, w_, p, bm=bm, bn=bn, bk=bk, dataflow=dataflow,
            interpret=interpret)
        acc = data_parallel(kernel, (hi_p, lo_p, prec_p), (w_p,))[:m, :n]
    else:
        acc = bitslice_matmul_ref(hi, lo, qw.values, prec)
    return acc.astype(jnp.float32) * (qx.scale * qw.scale)
