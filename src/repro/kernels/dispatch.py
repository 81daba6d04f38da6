"""Kernel dispatch layer: one policy object routes every hot-path op.

The UNet's compute hot spots each exist twice in this repo — a pure-JAX
reference (materializing, CPU-friendly, the stats oracle) and a blocked
Pallas kernel (the paper's dataflow: the SAS never leaves on-chip memory,
the FFN runs the DBSC integer datapath).  ``KernelPolicy`` names which
implementation each op uses; the dispatch functions below are the single
call sites the model layers go through, so serving, benchmarks and tests
select reference vs. fused per-op with one config knob instead of scattered
``use_*_kernel`` flags and inline imports.

Ops and implementations (``DISPATCH_TABLE``):

  self_attention   reference | fused    PSSA-pruned self-attention + stats
  cross_attention  reference | fused    text cross-attention + TIPS CAS
  ffn              reference | dbsc     GEGLU FFN (TIPS mixed precision)
  bitmap           reference | kernel   PSXU bitmap / patch-XOR / popcount
  reuse            reference | kernel   temporal-reuse patch-delta bitmap

``interpret=None`` (the default) resolves per backend at trace time —
interpret mode only where Pallas has no real lowering (CPU) — so the same
policy object is TPU-real and CPU-testable.  The stats-parity contract
(DESIGN.md §5): for any policy, reported ``PSSAStats``/TIPS ratios are
bit-identical to the reference path, because every implementation reduces
to the same integer counters before the shared byte arithmetic.
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp

from repro.core import attention, tips
from repro.kernels.bitslice_matmul.ops import bitslice_matmul
from repro.kernels.patch_bitmap.ops import patch_bitmap as _patch_bitmap_op
from repro.kernels.patch_reuse.ops import patch_delta as _patch_delta_op
from repro.kernels.runtime import resolve_interpret

_CHOICES = {
    "self_attention": ("reference", "fused"),
    "cross_attention": ("reference", "fused"),
    "ffn": ("reference", "dbsc"),
    "bitmap": ("reference", "kernel"),
    "reuse": ("reference", "kernel"),
}
_PRESETS = ("reference", "fused", "auto", "autotuned")
_FFN_QUANT = ("model", "int8")

# op -> the KernelPolicy block fields its kernels consume (also the knob
# names the autotune table stores — kept identical on purpose)
_OP_KNOBS = {
    "self_attention": ("attn_block_q", "attn_block_k"),
    "cross_attention": ("cross_block_q",),
    "bitmap": ("bitmap_block_rows",),
    "reuse": ("reuse_block_patches",),
}


@dataclasses.dataclass(frozen=True)
class KernelPolicy:
    """Which implementation each hot-path op dispatches to.

    Frozen + hashable so it can live inside ``UNetConfig`` and flow through
    jit closures.  ``interpret=None`` auto-selects per backend; block sizes
    are forwarded to the Pallas wrappers (which pad-and-slice, so any
    geometry is legal).  The attention blocks default to None: the PSSA
    op then tiles by its operand shape (``pssa_attention.ops.default_blocks``).
    """
    self_attention: str = "reference"
    cross_attention: str = "reference"
    ffn: str = "reference"
    bitmap: str = "reference"
    reuse: str = "reference"
    interpret: bool | None = None
    attn_block_q: int | None = None
    attn_block_k: int | None = None
    cross_block_q: int = 128
    bitmap_block_rows: int = 64
    reuse_block_patches: int = 8
    # tuned=True: override the block fields above with the committed
    # autotune table's winners, looked up per (backend, op, geometry) AT
    # TRACE TIME from the static operand shapes (kernels.autotune).  The
    # table never joins an executable cache key — only this bool does —
    # so swapping tables cannot cause retracing churn.
    tuned: bool = False
    # ffn_quant="int8": the DBSC route's integer matmuls run as real
    # int8 x int8 -> int32 ``lax.dot_general`` (MXU/dp4a-mappable)
    # instead of the int32 simulation; integers are bit-identical.
    ffn_quant: str = "model"

    def __post_init__(self):
        for op, allowed in _CHOICES.items():
            val = getattr(self, op)
            if val not in allowed:
                raise ValueError(
                    f"KernelPolicy.{op}={val!r}: expected one of {allowed}")
        if self.ffn_quant not in _FFN_QUANT:
            raise ValueError(
                f"KernelPolicy.ffn_quant={self.ffn_quant!r}: expected one "
                f"of {_FFN_QUANT}")

    # -- presets ---------------------------------------------------------
    @classmethod
    def reference(cls) -> "KernelPolicy":
        """Pure-JAX everywhere (the seed's materializing path)."""
        return cls()

    @classmethod
    def fused(cls) -> "KernelPolicy":
        """Blocked Pallas attention (self + cross) + PSXU kernel: neither
        the SAS nor the cross-attention probability tensor ever hits HBM.

        The FFN stays on the float reference — the DBSC integer datapath is
        a *precision* feature (INT12/INT6), selected per-op via ``ffn``
        (or the legacy ``UNetConfig.use_dbsc_kernel``), not a prerequisite
        of the fused memory path.
        """
        return cls(self_attention="fused", cross_attention="fused",
                   bitmap="kernel", reuse="kernel")

    @classmethod
    def auto(cls) -> "KernelPolicy":
        """Backend-aware default: fused where Pallas compiles, reference
        where it would only interpret.

        On CPU the fused kernels run through the Pallas interpreter, which
        is SLOWER than the materializing XLA reference (the PR 4 serving
        note measured the interpret-mode cross-attention kernel at 0.76x
        reference wall-clock) — so interpret backends keep the reference
        implementations and compiled backends get ``fused()``.  Stats are
        bit-identical either way (DESIGN.md §5), so the choice is pure
        wall time; this is what the CLIs default to.
        """
        return cls.fused() if not resolve_interpret(None) else cls.reference()

    @classmethod
    def autotuned(cls) -> "KernelPolicy":
        """``fused()`` with the committed autotune table's block winners.

        Block sizes come from ``kernels.autotune``'s per-(backend, op,
        geometry) lookup at trace time; geometries the table has never
        seen silently keep the defaults, so this preset is always safe to
        select.  Routing (which impl runs) is identical to ``fused()`` —
        only block shapes differ, and stats/counters are block-invariant.
        """
        return cls(self_attention="fused", cross_attention="fused",
                   bitmap="kernel", reuse="kernel", tuned=True)

    @classmethod
    def parse(cls, spec: str) -> "KernelPolicy":
        """Build a policy from a CLI spec.

        ``spec`` is a preset name (``reference`` | ``fused`` | ``auto`` |
        ``autotuned`` — ``auto`` resolved from the backend at parse time)
        or a comma-separated list of ``op=impl`` /
        ``interpret={auto,true,false}`` / ``tuned={true,false}`` /
        ``ffn_quant={model,int8}`` overrides applied on top of the
        reference preset, e.g. ``"self_attention=fused,ffn=dbsc"``.
        """
        spec = spec.strip()
        if spec in _PRESETS:
            return getattr(cls, spec)()
        fields = {}
        for item in filter(None, (s.strip() for s in spec.split(","))):
            if "=" not in item:
                raise ValueError(
                    f"kernel policy spec {item!r}: expected op=impl or a "
                    f"preset in {_PRESETS}")
            op, impl = (s.strip() for s in item.split("=", 1))
            if op == "interpret":
                try:
                    fields[op] = {"auto": None, "true": True,
                                  "false": False}[impl.lower()]
                except KeyError:
                    raise ValueError(
                        f"kernel policy spec: interpret={impl!r} (expected "
                        f"auto, true or false)") from None
            elif op == "tuned":
                try:
                    fields[op] = {"true": True, "false": False}[impl.lower()]
                except KeyError:
                    raise ValueError(
                        f"kernel policy spec: tuned={impl!r} (expected "
                        f"true or false)") from None
            elif op == "ffn_quant" or op in _CHOICES:
                fields[op] = impl
            else:
                raise ValueError(f"kernel policy spec: unknown op {op!r} "
                                 f"(expected {tuple(_CHOICES)})")
        return cls(**fields)

    # -- views -----------------------------------------------------------
    def resolve_interpret(self) -> bool:
        return resolve_interpret(self.interpret)

    def describe(self) -> dict:
        """JSON-friendly view for serving metrics / benchmark records."""
        return {**{op: getattr(self, op) for op in _CHOICES},
                "interpret": ("auto" if self.interpret is None
                              else self.interpret),
                "interpret_resolved": self.resolve_interpret(),
                "backend": jax.default_backend(),
                "tuned": self.tuned,
                "ffn_quant": self.ffn_quant}


# ----------------------------------------------------------------------------
# Autotuned block resolution
# ----------------------------------------------------------------------------
def _blocks(policy: KernelPolicy, op: str, geom: tuple) -> dict:
    """Resolved block sizes for one dispatch call.

    Policy defaults, overridden by the committed autotune table's winner
    for this exact (backend, op, geometry) when ``policy.tuned`` — a
    TRACE-TIME lookup from static shapes (``geom`` is built from
    ``.shape`` tuples, never traced values), so the table feeds plain
    block arguments and only the hashable policy reaches cache keys.
    """
    blocks = {name: getattr(policy, name) for name in _OP_KNOBS[op]}
    if policy.tuned:
        from repro.kernels import autotune     # lazy: autotune imports ops
        won = autotune.lookup(op, geom)
        if won:
            blocks.update(won)
    return blocks


# ----------------------------------------------------------------------------
# Dispatch targets
# ----------------------------------------------------------------------------
def _ffn_mid_covered(precision, important):
    """Whether the TIPS mask also covers the second FFN matmul (ff_out)."""
    return (important is not None and precision is not None
            and precision.ffn_mid)


def _ffn_reference(policy: KernelPolicy, hn, p, important, precision=None):
    """GEGLU FFN, float matmuls; TIPS rows fake-quantized on entry.

    With ``precision.ffn_mid`` the mid activations (GEGLU output) of
    unimportant rows also round-trip the INT6 grid before the second
    matmul — the paper's "INT12 through the whole following FFN stack"
    coverage.
    """
    if important is not None:
        hn = tips.apply_precision_mask(hn, important)
    gu = jnp.einsum("btc,cd->btd", hn, p["ff_geglu"]["w"]) \
        + p["ff_geglu"]["b"]
    g, u = jnp.split(gu, 2, axis=-1)
    mid = jax.nn.gelu(g) * u
    if _ffn_mid_covered(precision, important):
        mid = tips.apply_precision_mask(mid, important)
    return jnp.einsum("btd,dc->btc", mid,
                      p["ff_out"]["w"]) + p["ff_out"]["b"]


def _ffn_dbsc(policy: KernelPolicy, hn, p, important, precision=None):
    """Both FFN matmuls through the DBSC bit-slice integer datapath.

    ``precision.ffn_mid`` extends the TIPS row mask to the second matmul:
    unimportant rows' mid activations enter the bit-slice PEs on the INT6
    grid (low 6 bits dropped on the shared scale), matching the
    reference's mid-activation fake-quant and the ledger's
    ``LedgerOptions.tips_mid`` MAC split.

    ``policy.ffn_quant`` picks the execution of those integer matmuls:
    ``model`` (the int32 simulation) or ``int8`` (real int8 x int8 ->
    int32 ``lax.dot_general``) — bit-identical accumulators either way,
    so routing never moves a counter or the energy ledger.
    """
    b, t, c = hn.shape
    bt = b * t
    imp_flat = important.reshape(bt) if important is not None else None
    gu = bitslice_matmul(hn.reshape(bt, c), p["ff_geglu"]["w"],
                         important=imp_flat,
                         interpret=policy.interpret,
                         quant_path=policy.ffn_quant).reshape(b, t, -1) \
        + p["ff_geglu"]["b"]
    g, u = jnp.split(gu, 2, axis=-1)
    mid = jax.nn.gelu(g) * u
    mid_imp = imp_flat if _ffn_mid_covered(precision, important) else None
    return bitslice_matmul(mid.reshape(bt, mid.shape[-1]), p["ff_out"]["w"],
                           important=mid_imp,
                           interpret=policy.interpret,
                           quant_path=policy.ffn_quant).reshape(b, t, c) \
        + p["ff_out"]["b"]


DISPATCH_TABLE = {
    "self_attention": {
        "reference": attention.self_attention_pssa,
        "fused": attention.self_attention_pssa_fused,
    },
    "cross_attention": {
        "reference": attention.cross_attention_tips,
        "fused": attention.cross_attention_tips_fused,
    },
    "ffn": {
        "reference": _ffn_reference,
        "dbsc": _ffn_dbsc,
    },
    "bitmap": {
        "reference": functools.partial(_patch_bitmap_op, use_kernel=False),
        "kernel": _patch_bitmap_op,
    },
    "reuse": {
        "reference": functools.partial(_patch_delta_op, use_kernel=False),
        "kernel": _patch_delta_op,
    },
}


# ----------------------------------------------------------------------------
# Dispatch entry points (the call sites model layers use)
# ----------------------------------------------------------------------------
def self_attention(policy: KernelPolicy, q, k, v, *, patch: int,
                   threshold, prune_scores: bool = True,
                   stats_rows: int | None = None,
                   reference_stats: bool = False,
                   row_stats: bool = False) -> attention.SelfAttnOut:
    """PSSA self-attention via the policy's implementation.

    Three combinations force the materializing reference regardless of
    policy: ``reference_stats`` (the seed's stats oracle, definitionally
    materializing), ``prune_scores=False`` (the paper-baseline ablation
    keeps sub-threshold scores in the value matmul; the fused kernel always
    prunes), and a PER-ROW ``threshold`` array (phase-scheduled sampling —
    the Pallas kernel bakes its scalar threshold into the kernel closure,
    so per-row thresholds take the broadcast-friendly reference; the
    support restriction is documented in DESIGN.md §10).  ``row_stats``
    reports per-row integer counters (``pssa.PSSARowCounters``) instead of
    folded byte stats — identical counters either way, so the
    slot-serving ledger stays bit-exact across implementations.
    """
    impl = policy.self_attention
    per_row_threshold = getattr(threshold, "ndim", 0) >= 1
    if impl == "fused" and (reference_stats or not prune_scores
                            or per_row_threshold):
        impl = "reference"
    if impl == "fused":
        blk = _blocks(policy, "self_attention", (*q.shape, patch))
        return attention.self_attention_pssa_fused(
            q, k, v, patch=patch, threshold=threshold,
            stats_rows=stats_rows, interpret=policy.interpret,
            bq=blk["attn_block_q"], bk=blk["attn_block_k"],
            row_stats=row_stats)
    return attention.self_attention_pssa(
        q, k, v, patch=patch, threshold=threshold,
        prune_scores=prune_scores, stats_rows=stats_rows,
        reference_stats=reference_stats, row_stats=row_stats)


def cross_attention(policy: KernelPolicy, q, k_text, v_text, *,
                    precision, stats_rows: int | None = None,
                    row_stats: bool = False,
                    threshold_scale=None) -> attention.CrossAttnOut:
    """Cross-attention + TIPS spotting via the policy's implementation.

    ``precision`` (a ``core.precision.PrecisionPolicy``) drives the
    spotting mode; it runs on the head-averaged CAS identically for both
    implementations, so routing never changes a precision decision (the
    importance mask / low ratio / ledger terms are bit-identical across
    ``reference`` and ``fused`` — DESIGN.md §7).  ``row_stats`` reports
    per-row important-token counts (``tips.TIPSRowCounters``).
    ``threshold_scale`` ((B,) or None) scales each row's spotting
    threshold (phase-scheduled sampling) — it lives downstream of both
    kernels, in the shared spotting tail, so either implementation
    honours it identically.
    """
    if policy.cross_attention == "fused":
        blk = _blocks(policy, "cross_attention",
                      (*q.shape, k_text.shape[2]))
        return attention.cross_attention_tips_fused(
            q, k_text, v_text, precision=precision, stats_rows=stats_rows,
            interpret=policy.interpret, bq=blk["cross_block_q"],
            row_stats=row_stats, threshold_scale=threshold_scale)
    return attention.cross_attention_tips(
        q, k_text, v_text, precision=precision, stats_rows=stats_rows,
        row_stats=row_stats, threshold_scale=threshold_scale)


def ffn_geglu(policy: KernelPolicy, hn, p, important, precision=None):
    """(B, T, C) normed hidden -> (B, T, C) FFN output (pre-residual).

    ``p`` carries ``ff_geglu``/``ff_out`` weights; ``important`` is the
    TIPS row mask (None -> all rows full precision); ``precision`` (a
    ``PrecisionPolicy``) extends the mask to the second matmul when its
    ``ffn_mid`` flag is set.
    """
    return DISPATCH_TABLE["ffn"][policy.ffn](policy, hn, p, important,
                                             precision)


def patch_bitmap(policy: KernelPolicy, sas, patch: int, threshold: float):
    """PSXU payload op: packed XOR bitmap + per-patch popcounts."""
    if policy.bitmap == "kernel":
        tk = sas.shape[-1]
        rows = sas.size // tk
        blk = _blocks(policy, "bitmap", (rows, tk, patch))
        return _patch_bitmap_op(sas, patch, threshold, use_kernel=True,
                                interpret=policy.interpret,
                                br=blk["bitmap_block_rows"])
    return _patch_bitmap_op(sas, patch, threshold, use_kernel=False)


def patch_delta(policy: KernelPolicy, x, x_ref, *, patch: int,
                threshold: float):
    """Temporal-reuse change detection via the policy's implementation.

    (B, T, C) tokens vs cached reference -> ((B, P) float32 max-abs patch
    delta, (B, P) bool active bitmap).  Reference and kernel reduce max
    over the same values (exactly commutative), so the bitmap — and every
    reuse counter downstream of it — is bit-identical across routing.
    """
    if policy.reuse == "kernel":
        blk = _blocks(policy, "reuse", (*x.shape, patch))
        return _patch_delta_op(x, x_ref, patch=patch, threshold=threshold,
                               use_kernel=True, interpret=policy.interpret,
                               bp=blk["reuse_block_patches"])
    return _patch_delta_op(x, x_ref, patch=patch, threshold=threshold,
                           use_kernel=False)


def support_matrix() -> list:
    """op x impl support rows (README kernel-support matrix source)."""
    rows = []
    for op, impls in DISPATCH_TABLE.items():
        for impl in impls:
            pallas = impl not in ("reference",)
            rows.append({
                "op": op, "impl": impl,
                "pallas": pallas,
                "cpu": "interpret" if pallas else "native",
                "tpu": "compiled" if pallas else "native (XLA)",
            })
    return rows
