"""Public op: PSSA attention over (B, H, T, d) with head folding + padding.

Block handling: instead of the seed's degenerate fallback (halving the block
until it divides T — which collapses to 1-wide blocks for non-power-of-two
T), operands are zero-padded up to the block multiple and the outputs sliced
back; the kernel masks padded key columns out of the softmax statistics and
every counter (``kv_len``), so padding is exact.

``patch`` switches on the fused PSSA accounting: a third (B, H, T) int32
output with the per-query patch-XOR bitmap popcount, accumulated inside the
kernel — the SAS never exists in memory.  The key block is rounded down to a
patch multiple (and floored at ``patch``) so the XOR carry stays
block-aligned; ``patch`` must divide T.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.pssa_attention.kernel import pssa_attention_kernel
from repro.kernels.pssa_attention.ref import (pssa_attention_ref,
                                              pssa_attention_stats_ref)
from repro.kernels.runtime import data_parallel, pad_axis_to


# Each key block pays a fixed amount of vector work besides its two small
# matmuls (row max and sum, the prune compare, the counter partials, the XOR
# roll and carry), so tiles are as large as fit: on a TPU v5e a T = 1024
# call ran in 0.89 ms at 512 x 1024 against 3.14 at 128 x 128, and a
# T = 4096 call in 10.6 against 44.4.  A 1024-row query block does not fit
# the default scoped VMEM beside the whole K/V stripe at T = 4096, so
# queries stop at 512.
MAX_BLOCK_Q = 512
MAX_BLOCK_K = 1024


def default_blocks(t: int) -> tuple[int, int]:
    """(bq, bk) for T tokens when the caller names no blocks."""
    return min(MAX_BLOCK_Q, t), min(MAX_BLOCK_K, t)


@functools.partial(jax.jit, static_argnames=("threshold", "patch",
                                             "use_kernel", "interpret",
                                             "bq", "bk"))
def pssa_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                   threshold: float,
                   patch: int | None = None,
                   use_kernel: bool = True, interpret: bool | None = None,
                   bq: int | None = None, bk: int | None = None):
    """(B, H, T, d) q/k/v -> ((B, H, T, d) out, (B, H, T) nnz counts).

    With ``patch`` set, returns a third (B, H, T) array of per-query
    patch-XOR bitmap popcounts (see ``core.pssa``).  ``interpret=None``
    auto-selects interpret mode from the backend.  ``bq``/``bk`` of None
    take ``default_blocks(T)``.
    """
    b, h, t, d = q.shape
    if patch is not None:
        assert t % patch == 0, (t, patch)
    fold = lambda x: x.reshape(b * h, t, x.shape[-1])
    qf, kf, vf = fold(q), fold(k), fold(v)
    if use_kernel:
        dq, dk = default_blocks(t)
        blk_q = min(dq if bq is None else bq, t)
        blk_k = min(dk if bk is None else bk, t)
        if patch is not None:
            blk_k = max(patch, blk_k - blk_k % patch)
        kernel = functools.partial(
            pssa_attention_kernel, threshold=threshold, bq=blk_q, bk=blk_k,
            interpret=interpret, kv_len=t, patch=patch)
        res = data_parallel(kernel, (pad_axis_to(qf, blk_q, 1),
                                     pad_axis_to(kf, blk_k, 1),
                                     pad_axis_to(vf, blk_k, 1)))
        res = tuple(x[:, :t] for x in res)          # drop padded query rows
    elif patch is None:
        res = pssa_attention_ref(qf, kf, vf, threshold)
    else:
        res = pssa_attention_stats_ref(qf, kf, vf, threshold, patch)
    out, counts = res[0], res[1:]
    return (out.reshape(b, h, t, d),) + tuple(
        c.reshape(b, h, t) for c in counts)


# ---------------------------------------------------------------------------
# Autotune hooks (repro.kernels.autotune): geometry = (b, h, t, d, patch)
# ---------------------------------------------------------------------------
AUTOTUNE_KNOBS = ("attn_block_q", "attn_block_k")
_PROBE_THRESHOLD = 1.0 / 8192.0       # the paper's PSSA operating point


def autotune_candidates(geom: tuple) -> tuple:
    """Block-dict candidates for a (b, h, t, d, patch) geometry.

    Square (bq, bk) pairs plus the asymmetric neighbours of each —
    capped at ``t`` (larger blocks would only pad) and deduplicated, so
    degenerate geometries sweep a short list.
    """
    b, h, t, d, patch = geom
    sizes = sorted({min(s, t) for s in (128, 256, 512, 1024)})
    cands = [(s, s) for s in sizes]
    cands += [(q, k) for q, k in zip(sizes, sizes[1:])]
    cands += [(q, k) for k, q in zip(sizes, sizes[1:])]
    seen, out = set(), []
    for bq, bk in cands:
        if (bq, bk) not in seen:
            seen.add((bq, bk))
            out.append({"attn_block_q": bq, "attn_block_k": bk})
    return tuple(out)


def autotune_probe(geom: tuple, blocks: dict, *,
                   interpret: bool | None = None):
    """(jitted fn, args) the autotuner times for one block config."""
    b, h, t, d, patch = geom
    q, k, v = (jax.random.normal(jax.random.PRNGKey(i), (b, h, t, d),
                                 jnp.float32) for i in range(3))
    fn = jax.jit(functools.partial(
        pssa_attention, threshold=_PROBE_THRESHOLD, patch=patch,
        interpret=interpret, bq=blocks["attn_block_q"],
        bk=blocks["attn_block_k"]))
    return fn, (q, k, v)
