"""PSSA self-attention Pallas kernel (paper §III).

Blocked pixel-wise self-attention whose post-softmax scores are pruned at a
fixed threshold before the value matmul — the on-chip half of PSSA (the SAS
the attention core would spill to DRAM is exactly the pruned matrix that the
PSXU compresses).  The kernel additionally emits the per-query count of
surviving scores and (optionally) the per-query popcount of the patch-XOR'd
sparsity bitmap — together the exact integer counters the PSSA byte
accounting needs, so the fused serving path never materializes the SAS.

Pruning on normalized scores inside a *blocked* softmax needs the final row
max/sum, so the kernel is two-pass (FlashAttention-2 style):

  pass 1: stream K blocks, maintain running (m, l) per query row;
  pass 2: stream K blocks again, p = exp(s - m)/l, zero p < tau, accumulate
          p @ V, popcount(p >= tau), and — when ``patch`` is set — the
          PSXU delta-bitmap popcount.  Each block's bitmap is XOR'd against
          itself rotated right by ``patch`` lanes, which pairs every patch
          with its left neighbour; the lanes that wrap around (the block's
          first patch) take the previous block's last patch instead, carried
          from the previous iteration's rotation.  The first patch overall
          XORs against zeros, i.e. is counted verbatim, matching
          ``core.pssa.patch_xor``.  Rotation keeps every array at the
          block's (bq, bk) shape, so no unaligned lane slice is needed.
          Both counters are carried as (bq, 128) lane-wise partials and
          reduced across lanes once per query block, not once per key
          block.

``kv_len`` supports block-padded operands: key columns >= kv_len are masked
to -inf before the softmax statistics and excluded from every counter, so
padding to the block multiple (see ops.py) is exact.

Grid: (batch*heads, Tq/bq); the full K/V stripe of one (batch, head) lives
in VMEM: K and V, each (T, d) padded to 128 lanes and double-buffered, take
8 MiB at T=4096 in float32, and the (bq, bk) temporaries of a block come on
top.  The per-query counters leave the kernel as lane-dense
(BH, 1, Tq) arrays in (1, 1, bq) blocks, the layout the TPU's (8, 128)
tiling accepts; the wrapper reshapes them to (BH, Tq).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.runtime import resolve_interpret

NEG_INF = -1e30
LANES = 128


def _lane_partial(x: jax.Array, w: int) -> jax.Array:
    """(bq, bk) int32 -> (bq, w): the sum of x's w-lane slices."""
    out = x[:, :w]
    for j in range(1, x.shape[1] // w):
        out = out + x[:, j * w:(j + 1) * w]
    return out


def _kernel(q_ref, k_ref, v_ref, o_ref, nnz_ref, *rest, bk: int,
            sm_scale: float, threshold: float, kv_len: int,
            patch: int | None):
    xor_ref = rest[0] if rest else None
    q = q_ref[0] * sm_scale                       # (bq, d)
    kdim = k_ref.shape[1]
    nk = kdim // bk
    bq = q.shape[0]
    padded = kv_len < kdim                        # static: mask the tail
    w = LANES if bk % LANES == 0 else bk          # counter partials' width

    def kv_valid(s):                              # (1, bk) bool, col < kv_len
        col = s * bk + jax.lax.broadcasted_iota(jnp.int32, (1, bk), 1)
        return col < kv_len

    def pass1(s, carry):
        m_prev, l_prev = carry
        kblk = k_ref[0, pl.dslice(s * bk, bk), :]           # (bk, d)
        scores = jnp.dot(q, kblk.T, preferred_element_type=jnp.float32)
        if padded:
            scores = jnp.where(kv_valid(s), scores, NEG_INF)
        m_cur = jnp.maximum(m_prev, jnp.max(scores, axis=-1))
        l_cur = l_prev * jnp.exp(m_prev - m_cur) + jnp.sum(
            jnp.exp(scores - m_cur[:, None]), axis=-1)
        return m_cur, l_cur

    m0 = jnp.full((bq,), NEG_INF, jnp.float32)
    l0 = jnp.zeros((bq,), jnp.float32)
    m, l = jax.lax.fori_loop(0, nk, pass1, (m0, l0))
    l = jnp.maximum(l, 1e-30)

    def pass2(s, carry):
        if patch is None:
            acc, nnz = carry
        else:
            acc, nnz, xor_cnt, prev = carry
        kblk = k_ref[0, pl.dslice(s * bk, bk), :]
        vblk = v_ref[0, pl.dslice(s * bk, bk), :]
        scores = jnp.dot(q, kblk.T, preferred_element_type=jnp.float32)
        if padded:
            scores = jnp.where(kv_valid(s), scores, NEG_INF)
        p = jnp.exp(scores - m[:, None]) / l[:, None]
        keep = p >= threshold
        if padded:                     # threshold == 0 keeps p == 0 columns
            keep = jnp.logical_and(keep, kv_valid(s))
        p = jnp.where(keep, p, 0.0)                # PSSA step 1: prune
        acc = acc + jnp.dot(p, vblk, preferred_element_type=jnp.float32)
        nnz = nnz + _lane_partial(keep.astype(jnp.int32), w)
        if patch is None:
            return acc, nnz
        # PSXU accounting: XOR each bitmap patch against its left neighbour.
        # rolled[:, j] = bits[:, j - patch]; its first `patch` lanes wrap to
        # this block's last patch and are replaced by the previous block's.
        bits = keep.astype(jnp.int32)
        rolled = bits if patch == bk else pltpu.roll(bits, patch, 1)
        lane = jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        delta = bits != jnp.where(lane < patch, prev, rolled)
        if padded:                     # patches past kv_len are padding
            delta = jnp.logical_and(delta, kv_valid(s))
        xor_cnt = xor_cnt + _lane_partial(delta.astype(jnp.int32), w)
        return acc, nnz, xor_cnt, rolled

    acc0 = jnp.zeros_like(o_ref[0])
    part0 = jnp.zeros((bq, w), jnp.int32)
    if patch is None:
        acc, nnz = jax.lax.fori_loop(0, nk, pass2, (acc0, part0))
    else:
        prev0 = jnp.zeros((bq, bk), jnp.int32)
        acc, nnz, xor_cnt, _ = jax.lax.fori_loop(
            0, nk, pass2, (acc0, part0, part0, prev0))
        xor_ref[0] = jnp.sum(xor_cnt, axis=-1).reshape(1, bq)
    o_ref[0] = acc
    nnz_ref[0] = jnp.sum(nnz, axis=-1).reshape(1, bq)


@functools.partial(jax.jit, static_argnames=("bq", "bk", "threshold",
                                             "interpret", "kv_len", "patch"))
def pssa_attention_kernel(q: jax.Array, k: jax.Array, v: jax.Array,
                          threshold: float, *, bq: int, bk: int,
                          interpret: bool | None = None,
                          kv_len: int | None = None,
                          patch: int | None = None):
    """(BH, Tq, d) q x (BH, Tk, d) k/v -> (out, nnz[, xor_ones]) per query.

    ``kv_len``: true key count when Tk is block-padded (default: Tk).
    ``patch``: PSXU patch width; when set, a third (BH, Tq) int32 output
    carries the per-query patch-XOR bitmap popcount (``kv_len`` and ``bk``
    must be patch multiples).  ``interpret=None`` auto-selects from the
    backend (interpret only where Pallas has no real lowering).
    """
    bh, tq, d = q.shape
    tk = k.shape[1]
    kv_len = tk if kv_len is None else kv_len
    assert tq % bq == 0 and tk % bk == 0, (tq, tk, bq, bk)
    assert 0 < kv_len <= tk, (kv_len, tk)
    if patch is not None:
        assert bk % patch == 0 and kv_len % patch == 0, (bk, kv_len, patch)
    sm_scale = 1.0 / (d ** 0.5)

    counter_spec = pl.BlockSpec((1, 1, bq), lambda b, i: (b, 0, i))
    counter_shape = jax.ShapeDtypeStruct((bh, 1, tq), jnp.int32)
    out_specs = [pl.BlockSpec((1, bq, d), lambda b, i: (b, i, 0)),
                 counter_spec]
    out_shape = [jax.ShapeDtypeStruct((bh, tq, d), jnp.float32),
                 counter_shape]
    if patch is not None:
        out_specs.append(counter_spec)
        out_shape.append(counter_shape)

    res = pl.pallas_call(
        functools.partial(_kernel, bk=bk, sm_scale=sm_scale,
                          threshold=threshold, kv_len=kv_len, patch=patch),
        grid=(bh, tq // bq),
        in_specs=[
            pl.BlockSpec((1, bq, d), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, tk, d), lambda b, i: (b, 0, 0)),
            pl.BlockSpec((1, tk, d), lambda b, i: (b, 0, 0)),
        ],
        out_specs=out_specs,
        out_shape=out_shape,
        interpret=resolve_interpret(interpret),
    )(q, k, v)
    return (res[0],) + tuple(c.reshape(bh, tq) for c in res[1:])
