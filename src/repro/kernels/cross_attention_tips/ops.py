"""Public op: cross-attention with CAS side output over (B, H, Tq, d).

Head folding + padding around ``cross_attention_tips_kernel``: query rows
are zero-padded up to the query-block multiple and sliced back; text keys
are zero-padded up to a sublane multiple with ``kv_len`` masking them out
of the softmax statistics inside the kernel (their probabilities are
exactly zero, so the padded value rows contribute nothing to the output
and the CAS of every real query is untouched).
"""
from __future__ import annotations

import functools

import jax

from repro.kernels.cross_attention_tips.kernel import (
    cross_attention_tips_kernel)
from repro.kernels.cross_attention_tips.ref import cross_attention_tips_ref
from repro.kernels.runtime import data_parallel, pad_axis_to

# text keys are sublane-padded to this multiple (77 -> 80; any Tk is legal)
_KV_PAD = 8


@functools.partial(jax.jit, static_argnames=("cls_index", "use_kernel",
                                             "interpret", "bq"))
def cross_attention_cas(q: jax.Array, k: jax.Array, v: jax.Array,
                        cls_index: int = 0,
                        use_kernel: bool = True,
                        interpret: bool | None = None,
                        bq: int = 128):
    """(B, H, Tq, d) q x (B, H, Tk, d) text k/v -> (out, cas).

    ``out`` is (B, H, Tq, d); ``cas`` is (B, H, Tq) — the per-head CLS
    attention score (softmax mass on text key ``cls_index``).  The
    (B, H, Tq, Tk) probability tensor never exists in memory on the kernel
    path.  ``interpret=None`` auto-selects interpret mode per backend.
    """
    b, h, tq, d = q.shape
    tk = k.shape[2]
    fold = lambda x: x.reshape(b * h, x.shape[2], x.shape[3])
    qf, kf, vf = fold(q), fold(k), fold(v)
    if use_kernel:
        blk_q = min(bq, tq)
        kernel = functools.partial(
            cross_attention_tips_kernel, cls_index=cls_index, bq=blk_q,
            interpret=interpret, kv_len=tk)
        out, cas = data_parallel(kernel, (pad_axis_to(qf, blk_q, 1),
                                          pad_axis_to(kf, _KV_PAD, 1),
                                          pad_axis_to(vf, _KV_PAD, 1)))
        out, cas = out[:, :tq], cas[:, :tq]        # drop padded query rows
    else:
        out, cas = cross_attention_tips_ref(qf, kf, vf, cls_index)
    return out.reshape(b, h, tq, d), cas.reshape(b, h, tq)


# ---------------------------------------------------------------------------
# Autotune hooks (repro.kernels.autotune): geometry = (b, h, tq, d, tk)
# ---------------------------------------------------------------------------
AUTOTUNE_KNOBS = ("cross_block_q",)


def autotune_candidates(geom: tuple) -> tuple:
    """Query-block candidates for a (b, h, tq, d, tk) geometry.

    The text keys are tiny (Tk=77) so the only knob is the query block;
    candidates cap at ``tq`` (larger blocks only pad).
    """
    b, h, tq, d, tk = geom
    sizes = sorted({min(s, tq) for s in (128, 256, 512, 1024, 2048)})
    return tuple({"cross_block_q": s} for s in sizes)


def autotune_probe(geom: tuple, blocks: dict, *,
                   interpret: bool | None = None):
    """(jitted fn, args) the autotuner times for one block config."""
    import jax.numpy as jnp
    b, h, tq, d, tk = geom
    q = jax.random.normal(jax.random.PRNGKey(0), (b, h, tq, d), jnp.float32)
    k = jax.random.normal(jax.random.PRNGKey(1), (b, h, tk, d), jnp.float32)
    v = jax.random.normal(jax.random.PRNGKey(2), (b, h, tk, d), jnp.float32)
    fn = jax.jit(functools.partial(
        cross_attention_cas, interpret=interpret,
        bq=blocks["cross_block_q"]))
    return fn, (q, k, v)
