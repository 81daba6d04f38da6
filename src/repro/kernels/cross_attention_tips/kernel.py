"""Cross-attention TIPS Pallas kernel (paper §IV-A).

Blocked pixel-query x text-key cross-attention that emits, alongside the
attention output, the per-query CLS attention score (CAS) — the quantity the
IPSU thresholds to spot prompt-tied pixels.  The reference implementation
materializes the full (B, H, Tq, Tk) probability tensor just to read its
CLS column; here the probabilities only ever exist one (bq, Tk) block at a
time in VMEM, and the CAS rides out as a side output — lane-dense (BH, 1, Tq)
in (1, 1, bq) blocks, the layout the TPU's (8, 128) tiling accepts,
reshaped to (BH, Tq) by the wrapper.

Unlike the PSSA self-attention kernel, the key extent is the TEXT length
(77 for CLIP, single digits at smoke geometry) — the whole K/V stripe of
one (batch, head) trivially fits in VMEM, so the softmax is single-pass
over the full (masked) row rather than a two-pass online rescale: no
cross-block reassociation ever touches the denominator.  The score matmul
keeps the leading size-1 batch dimension (``dot_general`` with a batch
dim, exactly the contraction the reference einsum lowers to) and divides
by sqrt(d) after, mirroring the reference operation for operation.

The CAS this computes is therefore *ulp-identical* to the reference — not
guaranteed bitwise, because the reference is not bitwise stable against
itself across execution contexts (XLA fuses the softmax differently under
``jax.jit`` than eagerly, reassociating the row sum).  The quantities the
energy ledger consumes — the importance mask (``cas < threshold``), the
low-precision ratio, and the FFN MAC split derived from it — ARE
bit-identical across routing: a threshold decision only flips on an exact
floating-point tie, and the parity tests pin exact equality on every
seeded geometry (DESIGN.md §7, same empirical contract as the PSSA
counter equality of §5).

``kv_len`` supports block-padded text keys: columns >= kv_len are masked
to -inf before the row statistics, so their probabilities are exactly zero
and padding to a sublane multiple (see ops.py) contributes nothing to the
output or any real query's CAS.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.runtime import resolve_interpret

NEG_INF = -1e30

# dot_general dimension numbers: contract the feature axis (2), batch the
# leading size-1 block axis (0) — the same contraction the reference
# einsum ("bhqd,bhkd->bhqk") performs per (batch, head) slice.
_QK_DIMS = (((2,), (2,)), ((0,), (0,)))
_PV_DIMS = (((2,), (1,)), ((0,), (0,)))


def _kernel(q_ref, k_ref, v_ref, o_ref, cas_ref, *, sm_denom: float,
            cls_index: int, kv_len: int):
    q = q_ref[...]                                # (1, bq, d)
    k = k_ref[...]                                # (1, tk_pad, d)
    v = v_ref[...]
    tk = k.shape[1]

    scores = jax.lax.dot_general(
        q, k, _QK_DIMS, preferred_element_type=jnp.float32) / sm_denom
    if kv_len < tk:                               # static: mask padded keys
        col = jax.lax.broadcasted_iota(jnp.int32, (1, 1, tk), 2)
        scores = jnp.where(col < kv_len, scores, NEG_INF)
    m = jnp.max(scores, axis=-1, keepdims=True)
    e = jnp.exp(scores - m)                       # padded cols: exactly 0
    p = e / jnp.sum(e, axis=-1, keepdims=True)    # (1, bq, tk) probs block
    o_ref[...] = jax.lax.dot_general(
        p, v, _PV_DIMS, preferred_element_type=jnp.float32)
    cas_ref[...] = p[:, :, cls_index].reshape(cas_ref.shape)


@functools.partial(jax.jit, static_argnames=("cls_index", "bq", "interpret",
                                             "kv_len"))
def cross_attention_tips_kernel(q: jax.Array, k: jax.Array, v: jax.Array,
                                cls_index: int = 0,
                                bq: int = 128,
                                interpret: bool | None = None,
                                kv_len: int | None = None):
    """(BH, Tq, d) q x (BH, Tk, d) text k/v -> (out, cas) per query row.

    ``out`` is (BH, Tq, d) float32; ``cas`` is (BH, Tq) float32 — the
    softmax probability mass the query puts on the ``cls_index`` text key.
    ``kv_len``: true text length when Tk is sublane-padded (default: Tk);
    ``cls_index`` must address a real (unpadded) key.  ``interpret=None``
    auto-selects from the backend (interpret only where Pallas has no real
    lowering).
    """
    bh, tq, d = q.shape
    tk = k.shape[1]
    kv_len = tk if kv_len is None else kv_len
    assert tq % bq == 0, (tq, bq)
    assert 0 < kv_len <= tk, (kv_len, tk)
    assert 0 <= cls_index < kv_len, (cls_index, kv_len)
    sm_denom = float(d) ** 0.5

    res = pl.pallas_call(
        functools.partial(_kernel, sm_denom=sm_denom, cls_index=cls_index,
                          kv_len=kv_len),
        grid=(bh, tq // bq),
        in_specs=[
            pl.BlockSpec((1, bq, d), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, tk, d), lambda b, i: (b, 0, 0)),
            pl.BlockSpec((1, tk, d), lambda b, i: (b, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, bq, d), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, 1, bq), lambda b, i: (b, 0, i)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, tq, d), jnp.float32),
            jax.ShapeDtypeStruct((bh, 1, tq), jnp.float32),
        ],
        interpret=resolve_interpret(interpret),
    )(q, k, v)
    out, cas = res
    return out, cas.reshape(bh, tq)
