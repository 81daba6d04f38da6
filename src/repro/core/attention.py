"""Attention modules with the paper's features folded in (pure JAX).

``self_attention_pssa``  — pixel-wise self-attention whose post-softmax score
matrix is threshold-pruned (PSSA step 1) before the value matmul, and whose
compression statistics are returned for the EMA ledger.

``cross_attention_tips`` — cross-attention that additionally emits the CLS
attention score per query (CAS) for the IPSU (TIPS spotting).

``self_attention_pssa_fused`` — the same contract through the blocked
Pallas kernel (``repro.kernels.pssa_attention``): the score matrix never
exists in memory, and the PSSA byte accounting is assembled from integer
counters the kernel accumulates per query row.  Selection between the two
lives in ``repro.kernels.dispatch`` (``KernelPolicy``).

``cross_attention_tips_fused`` — cross-attention through the blocked
Pallas kernel (``repro.kernels.cross_attention_tips``): the (B, H, Tq, Tk)
probability tensor never exists in memory; the per-head CAS rides out of
the kernel and importance spotting happens on it downstream, shared with
the reference path (``core.precision.spot_cas``).

``self_attention_pssa`` is deliberately materializing — that is the paper's
*baseline* dataflow (SAS spills to DRAM) and the thing PSSA compresses; it
stays the stats oracle the fused path is tested against.
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.core import pssa, precision as precision_mod, tips
from repro.kernels.cross_attention_tips.ops import cross_attention_cas
from repro.kernels.pssa_attention.ops import pssa_attention


class SelfAttnOut(NamedTuple):
    out: jax.Array
    stats: pssa.PSSAStats       # PSSARowCounters under ``row_stats``


def self_attention_pssa(q: jax.Array, k: jax.Array, v: jax.Array,
                        patch: int,
                        threshold: float = pssa.DEFAULT_THRESHOLD,
                        prune_scores: bool = True,
                        stats_rows: int | None = None,
                        reference_stats: bool = False,
                        row_stats: bool = False) -> SelfAttnOut:
    """(B, H, T, d) q/k/v -> (B, H, T, d); scores pruned at `threshold`.

    ``stats_rows`` limits the compression accounting to the first N batch
    rows (static).  The fused-CFG sampler sets it to the cond half: the
    energy ledger only ever consumes cond-prompt statistics, so skipping
    the uncond half keeps stats bit-identical to a cond-only call while
    halving the accounting cost per step.

    ``row_stats`` keeps the integer counters PER ROW instead of folding
    them: ``stats`` becomes a ``pssa.PSSARowCounters`` with (B,) leaves —
    the slot-serving runtime scatters them into per-iteration ledger
    buckets (rows sit at heterogeneous denoising steps).  Summing rows
    reproduces the folded counters bit-for-bit.
    """
    d = q.shape[-1]
    # per-row thresholds (phase-scheduled sampling): a (B,) array is
    # broadcast to (B, 1, 1, 1) — pruning and every counter stay the same
    # elementwise comparisons, and the stats slice carries its rows'
    # thresholds with it
    if getattr(threshold, "ndim", 0) == 1:
        threshold = threshold.reshape(threshold.shape[0], 1, 1, 1)
    scores = jnp.einsum("bhqd,bhkd->bhqk", q, k) / jnp.sqrt(float(d))
    probs = jax.nn.softmax(scores, axis=-1)
    if prune_scores:
        probs_used = pssa.prune(probs, threshold)
    else:
        probs_used = probs
    probs_stat = probs if stats_rows is None else probs[:stats_rows]
    thr_stat = threshold
    if stats_rows is not None and getattr(threshold, "ndim", 0) == 4:
        thr_stat = threshold[:stats_rows]
    if row_stats:
        stats = pssa.row_counters(probs_stat, patch, thr_stat)
    else:
        compress = (pssa.compress_stats_reference if reference_stats
                    else pssa.compress_stats)
        stats = compress(probs_stat, patch, thr_stat)
    out = jnp.einsum("bhqk,bhkd->bhqd", probs_used, v)
    return SelfAttnOut(out=out, stats=stats)


def self_attention_pssa_fused(q: jax.Array, k: jax.Array, v: jax.Array,
                              patch: int,
                              threshold: float = pssa.DEFAULT_THRESHOLD,
                              stats_rows: int | None = None,
                              interpret: bool | None = None,
                              bq: int | None = None, bk: int | None = None,
                              row_stats: bool = False) -> SelfAttnOut:
    """``self_attention_pssa`` through the blocked Pallas kernel.

    The (B, H, T, T) score matrix is never materialized: the kernel streams
    K blocks (two-pass online softmax), prunes at ``threshold`` before the
    value matmul, and accumulates the two PSSA counters — surviving-score
    count and patch-XOR bitmap popcount — per query row.  ``PSSAStats`` is
    assembled from those integer counters via ``pssa.stats_from_counters``,
    sharing the byte arithmetic with the materializing reference (equal
    counters => bit-identical stats).  ``stats_rows`` restricts accounting
    to the first N batch rows exactly as the reference does (row slices
    commute with the per-row counters).  Always prunes; callers wanting
    ``prune_scores=False`` or the seed stats oracle use the reference path
    (the dispatch layer downgrades those combinations).
    """
    b, h, t, d = q.shape
    out, nnz_rows, xor_rows = pssa_attention(
        q, k, v, threshold, patch=patch, interpret=interpret, bq=bq, bk=bk)
    rows = b if stats_rows is None else stats_rows
    x64 = bool(jax.config.read("jax_enable_x64"))
    int_dtype = jnp.int64 if x64 else jnp.int32
    if row_stats:
        # fold heads + query rows only: (B, H, T) -> (B,) per-row counters
        stats = pssa.PSSARowCounters(
            nnz=jnp.sum(nnz_rows[:rows], axis=(1, 2), dtype=int_dtype),
            ones_xor=jnp.sum(xor_rows[:rows], axis=(1, 2), dtype=int_dtype))
        return SelfAttnOut(out=out, stats=stats)
    nnz = jnp.sum(nnz_rows[:rows], dtype=int_dtype)
    ones_xor = jnp.sum(xor_rows[:rows], dtype=int_dtype)
    stats = pssa.stats_from_counters(nnz, ones_xor, lead=rows * h,
                                     tq=t, tk=t, patch=patch)
    return SelfAttnOut(out=out, stats=stats)


class CrossAttnOut(NamedTuple):
    out: jax.Array
    tips_result: tips.TIPSResult   # reported stats (cond rows under CFG);
    #                                TIPSRowCounters under ``row_stats``
    important_full: jax.Array      # full-batch mask for the FFN precision


def _spot_and_slice(cas: jax.Array, precision, stats_rows: int | None,
                    row_stats: bool = False, threshold_scale=None):
    """Shared spotting tail of both cross-attention implementations.

    ``cas`` is the head-averaged (B, Tq) CLS score; spotting (fixed or
    per-sample adaptive, per the ``PrecisionPolicy``) runs on it
    identically for the reference and fused paths, so routing parity
    reduces to CAS parity.  Returns (reported TIPSResult, full-batch
    importance mask) — with ``stats_rows`` the reported stats cover the
    first N rows only (the cond half under fused CFG), which commutes
    with spotting because both modes decide per sample.

    ``row_stats``: report a ``tips.TIPSRowCounters`` instead — the (B,)
    integer count of spotted-important tokens per row (slot-serving
    scatters these into per-iteration ledger buckets).

    ``threshold_scale`` (a (B,) float32 or None) is the phase-scheduled
    per-row scale on the spotting threshold (``precision.spot_cas``).
    """
    spotted = precision_mod.spot_cas(cas, precision,
                                     threshold_scale=threshold_scale)
    important_full = spotted.important
    if row_stats:
        imp = (spotted.important if stats_rows is None
               else spotted.important[:stats_rows])
        return tips.TIPSRowCounters(
            important=jnp.sum(imp, axis=-1, dtype=jnp.int32)), important_full
    if stats_rows is not None:
        imp = spotted.important[:stats_rows]
        spotted = tips.TIPSResult(
            important=imp, cas=spotted.cas[:stats_rows],
            low_precision_ratio=1.0 - jnp.mean(imp.astype(jnp.float32)))
    return spotted, important_full


def _as_precision_policy(precision, threshold, cls_index):
    """Legacy-call shim: a bare ``threshold`` means fixed spotting."""
    if precision is not None:
        if threshold is not None:
            raise ValueError(
                "pass either precision= or the legacy threshold=, not both "
                "(the policy carries the threshold)")
        return precision
    if threshold is None:
        raise ValueError("pass either precision= or threshold=")
    return precision_mod.PrecisionPolicy(threshold=threshold,
                                         cls_index=cls_index)


def cross_attention_tips(q: jax.Array, k_text: jax.Array, v_text: jax.Array,
                         threshold: float | None = None,
                         cls_index: int = 0,
                         stats_rows: int | None = None,
                         precision=None,
                         row_stats: bool = False,
                         threshold_scale=None) -> CrossAttnOut:
    """(B, H, Tq, d) pixel queries x (B, H, Tk, d) text keys, with TIPS.

    ``precision`` (a ``core.precision.PrecisionPolicy``) selects the
    spotting mode; passing only ``threshold`` keeps the legacy
    fixed-threshold behaviour.  The returned ``tips_result.important``
    always covers the FULL batch (the FFN precision mask needs every row);
    with ``stats_rows`` set, the *reported* CAS / low-precision ratio are
    restricted to the first N rows — the cond half under fused CFG —
    matching a cond-only call.
    """
    precision = _as_precision_policy(precision, threshold, cls_index)
    d = q.shape[-1]
    scores = jnp.einsum("bhqd,bhkd->bhqk", q, k_text) / jnp.sqrt(float(d))
    probs = jax.nn.softmax(scores, axis=-1)
    cas = jnp.mean(probs[..., :, precision.cls_index], axis=-2)   # (B, Tq)
    spotted, important_full = _spot_and_slice(cas, precision, stats_rows,
                                              row_stats, threshold_scale)
    out = jnp.einsum("bhqk,bhkd->bhqd", probs, v_text)
    return CrossAttnOut(out=out, tips_result=spotted,
                        important_full=important_full)


def cross_attention_tips_fused(q: jax.Array, k_text: jax.Array,
                               v_text: jax.Array,
                               threshold: float | None = None,
                               cls_index: int = 0,
                               stats_rows: int | None = None,
                               precision=None,
                               interpret: bool | None = None,
                               bq: int = 128,
                               row_stats: bool = False,
                               threshold_scale=None) -> CrossAttnOut:
    """``cross_attention_tips`` through the blocked Pallas kernel.

    The (B, H, Tq, Tk) probability tensor is never materialized: the
    kernel streams query blocks against the (small) text-key stripe and
    emits the per-head CAS directly (``repro.kernels.cross_attention_tips``).
    Spotting runs on the head-averaged CAS downstream, shared with the
    reference — the importance mask, low-precision ratio, and every ledger
    term derived from them are bit-identical to the reference path; the
    raw CAS is ulp-identical (the reference itself is not bitwise stable
    across jit contexts — DESIGN.md §7).
    """
    precision = _as_precision_policy(precision, threshold, cls_index)
    out, cas_bh = cross_attention_cas(q, k_text, v_text,
                                      cls_index=precision.cls_index,
                                      interpret=interpret, bq=bq)
    cas = jnp.mean(cas_bh, axis=-2)                               # (B, Tq)
    spotted, important_full = _spot_and_slice(cas, precision, stats_rows,
                                              row_stats, threshold_scale)
    return CrossAttnOut(out=out, tips_result=spotted,
                        important_full=important_full)
