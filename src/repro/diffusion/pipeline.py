"""End-to-end text-to-image pipeline (Fig. 1(a)) with the paper's features.

Stages: text encoding -> 25 iterative UNet denoising steps -> VAE decode.
The pipeline runs the reduced geometry on CPU and *measures* the quantities
the silicon measures — per-resolution PSSA compression ratios and
per-iteration TIPS low-precision ratios — then injects them into the
full-geometry analytic ledger to produce the paper's headline numbers
(EMA GB/iter, mJ/iter).  PSSA / TIPS / DBSC are feature toggles, so the
baseline-vs-optimized deltas of Figs. 5/9 fall out of the same code path.
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp

from repro.core import energy, pssa
from repro.core.tips import TIPS_ACTIVE_ITERS
from repro.diffusion import ledger as L
from repro.diffusion import solvers as solvers_mod
from repro.diffusion.sampler import DDIMConfig, sample
from repro.diffusion.stats import (UNetStats, attn_layer_order,
                                   coerce_per_step_stats)
from repro.diffusion.denoiser import make_denoiser
from repro.diffusion.text_encoder import (TextEncoderConfig,
                                          encode_text,
                                          init_text_encoder_params)
from repro.diffusion.unet import UNetConfig
from repro.diffusion.vae import VAEConfig, decode, init_vae_params


def _iter_layer_stats(stats_one_iter, kind: str):
    """Yield (resolution, per-layer stats) from either stats representation.

    ``kind`` is "pssa" or "tips".  Supports the ``UNetStats`` pytree (layer
    resolutions are static metadata) and the legacy string-keyed dict view
    (resolution parsed from the "tag@res" key).
    """
    if isinstance(stats_one_iter, UNetStats):
        entries = getattr(stats_one_iter, kind)
        for lk, st in zip(stats_one_iter.layers, entries):
            yield lk.resolution, st
        return
    for key, st in stats_one_iter.get(kind, {}).items():
        yield int(key.rsplit("@", 1)[1]), st


@dataclasses.dataclass(frozen=True)
class PipelineConfig:
    # ``unet`` holds the DENOISER config — any registered family
    # (``UNetConfig`` or ``dit.DiTConfig``); the field keeps its
    # historical name because every consumer reads policies/geometry
    # through it and both families expose the same contract hooks.
    unet: UNetConfig = UNetConfig()
    text: TextEncoderConfig = TextEncoderConfig()
    vae: VAEConfig = VAEConfig()
    ddim: DDIMConfig = DDIMConfig()

    @staticmethod
    def smoke() -> "PipelineConfig":
        return PipelineConfig(
            unet=UNetConfig().smoke(),
            text=TextEncoderConfig().smoke(),
            vae=VAEConfig().smoke(),
            ddim=DDIMConfig(num_inference_steps=3, guidance_scale=1.0,
                            tips_active_iters=2),
        )


class StableDiffusionPipeline:
    """Holds params + jitted stage functions; reusable across prompts.

    This is the per-step reference path (25 Python dispatches, two UNet
    calls per step under CFG).  The production path is
    ``repro.diffusion.engine.DiffusionEngine`` — one jitted
    encode -> scanned-sampler -> decode computation with fused CFG; both
    feed the same ``energy_report`` (stats representations are
    interchangeable via ``repro.diffusion.stats``).
    """

    def __init__(self, cfg: PipelineConfig, key=None):
        self.cfg = cfg
        key = key if key is not None else jax.random.PRNGKey(0)
        k1, k2, k3 = jax.random.split(key, 3)
        # context width must match: the towers' widths == unet context_dim
        assert cfg.text.context_dim == cfg.unet.context_dim, \
            (cfg.text.context_dim, cfg.unet.context_dim)
        self.denoiser = make_denoiser(cfg.unet)
        self.text_params = init_text_encoder_params(k1, cfg.text)
        self.unet_params = self.denoiser.init_params(k2)
        self.vae_params = init_vae_params(k3, cfg.vae)

        self._encode = jax.jit(
            lambda toks: encode_text(self.text_params, toks, cfg.text))
        self._unet = jax.jit(
            lambda lat, t, ctx, act: self.denoiser.apply(
                self.unet_params, lat, t, ctx, tips_active=act))
        self._decode = jax.jit(
            lambda lat: decode(self.vae_params, lat, cfg.vae))

    # ------------------------------------------------------------------
    def generate(self, prompt_tokens, key, uncond_tokens=None,
                 collect_stats: bool = True):
        """prompt_tokens (B, text_len) int32 -> (image, stats_per_iter)."""
        cfg = self.cfg
        context = self._encode(prompt_tokens)
        uncond = (self._encode(uncond_tokens)
                  if uncond_tokens is not None else None)
        b = prompt_tokens.shape[0]
        s = cfg.unet.latent_size
        latents = jax.random.normal(key, (b, s, s, cfg.unet.in_channels))
        latents, stats = sample(self._unet, latents, context, uncond,
                                cfg.ddim, collect_stats=collect_stats)
        image = self._decode(latents)
        return image, stats

    # ------------------------------------------------------------------
    # Measurement -> full-geometry ledger (delegates to module functions
    # so the engine/serving path can use them without a pipeline object)
    # ------------------------------------------------------------------
    def measured_sas_ratios(self, stats_one_iter) -> dict:
        return measured_sas_ratios(stats_one_iter)

    def measured_tips_ratio(self, stats_one_iter) -> float:
        return measured_tips_ratio(stats_one_iter)

    def energy_report(self, stats_per_iter, full_geometry: bool = True
                      ) -> "PipelineEnergyReport":
        return energy_report(self.cfg, stats_per_iter,
                             full_geometry=full_geometry)


def _sas_ratio_terms(stats_one_iter) -> dict:
    """Per-resolution (numerator, denominator) byte sums for the SAS ratio.

    Returned separately from the ratio so multi-batch serving can aggregate
    the terms across engine calls before dividing: the byte counters scale
    with the accounted row count (``stats_rows``), which makes the sums
    self-weighting by valid rows.
    """
    by_res: dict = {}
    for res, st in _iter_layer_stats(stats_one_iter, "pssa"):
        comp = float(st.bytes_pssa_total)
        base = float(st.bytes_baseline)
        num, den = by_res.get(res, (0.0, 0.0))
        by_res[res] = (num + comp, den + base)
    return by_res


def _tips_ratio_terms(stats_one_iter) -> tuple:
    """(numerator, denominator) of the workload-weighted INT6 fraction.

    The per-layer ``low_precision_ratio`` is a mean over the accounted
    batch rows, so the workload weight carries the row count (read from
    the ``important`` mask's static shape — no device transfer): batches
    with more valid rows count proportionally more when the terms are
    summed across engine calls.
    """
    num = den = 0.0
    for res, tr in _iter_layer_stats(stats_one_iter, "tips"):
        rows = float(tr.important.shape[0]) \
            if getattr(tr.important, "ndim", 0) >= 2 else 1.0
        work = float(res * res) * rows     # FFN MACs scale with token count
        num += float(tr.low_precision_ratio) * work
        den += work
    return num, den


def measured_sas_ratios(stats_one_iter) -> dict:
    """Per-resolution (compressed/dense) SAS ratio from PSSAStats.

    Accepts a single-step ``UNetStats`` pytree or the legacy
    ``{"pssa": {"tag@res": PSSAStats}}`` dict view.
    """
    return {res: num / max(den, 1e-12)
            for res, (num, den) in _sas_ratio_terms(stats_one_iter).items()}


def measured_tips_ratio(stats_one_iter) -> float:
    """Workload-weighted INT6 fraction across the iteration's FFNs."""
    num, den = _tips_ratio_terms(stats_one_iter)
    return num / max(den, 1e-12)


def energy_report(cfg: "PipelineConfig", stats_per_iter,
                  full_geometry: bool = True,
                  sampler_policy=None) -> "PipelineEnergyReport":
    """Headline numbers: EMA GB/iter + mJ/iter (Table I reproduction).

    ``stats_per_iter`` is either the stacked ``UNetStats`` a scanned
    engine run returns (leading axis = iterations) or the seed's list of
    per-iteration stats.  The reduced run's measured ratios drive the
    FULL BK-SDM-Tiny ledger (hardware adaptation note: patch locality is
    resolution-dependent, so per-resolution ratios transfer; DESIGN.md §2).
    A single-batch aggregation: delegates to :func:`energy_report_multi`.

    ``sampler_policy``: the ``solvers.SamplerPolicy`` the run used, when
    it is not the config's default schedule — the trajectory then carries
    ``policy.num_steps`` iterations and the TIPS-active window follows
    ``solvers.tips_active_schedule`` instead of ``tips_active_iters``.
    """
    return energy_report_multi(cfg, [stats_per_iter],
                               full_geometry=full_geometry,
                               sampler_policy=sampler_policy)


def energy_report_multi(cfg: "PipelineConfig", stats_per_batch,
                        full_geometry: bool = True,
                        sampler_policy=None) -> "PipelineEnergyReport":
    """Aggregate energy report across SEVERAL engine calls (serving).

    ``stats_per_batch``: one stats trajectory per engine call (stacked
    ``UNetStats`` or per-iteration list), each already restricted to its
    valid rows (``stats_rows`` masks padded tail rows out at the source).
    Per DDIM iteration, the SAS byte terms and the row-weighted TIPS terms
    are summed across batches BEFORE dividing, so every valid image row in
    the run — and no padded duplicate — contributes with equal weight.
    With a single entry this reduces exactly to :func:`energy_report`.

    With ``sampler_policy`` set, every trajectory must come from runs of
    that SAME policy (mixed-policy serving uses the banked accumulator
    path, :func:`energy_report_banked`).
    """
    fetched = []
    for s in stats_per_batch:
        if isinstance(s, UNetStats):
            s = s.ledger_fetch()        # one host transfer per engine call
        fetched.append(coerce_per_step_stats(s))
    if not fetched:
        raise ValueError("stats_per_batch is empty")
    n = (cfg.ddim.num_inference_steps if sampler_policy is None
         else sampler_policy.num_steps)
    tips_flags = (None if sampler_policy is None else
                  solvers_mod.tips_active_schedule(sampler_policy, cfg.ddim))
    for s in fetched:
        if len(s) != n:
            raise ValueError(
                f"stats trajectory has {len(s)} iterations, "
                f"{'policy' if sampler_policy else 'config'} says {n}")

    per_iter_terms = []
    for i in range(n):
        sas_terms: dict = {}
        tnum = tden = 0.0
        for s in fetched:
            for res, (num, den) in _sas_ratio_terms(s[i]).items():
                a, b = sas_terms.get(res, (0.0, 0.0))
                sas_terms[res] = (a + num, b + den)
            num, den = _tips_ratio_terms(s[i])
            tnum, tden = tnum + num, tden + den
        per_iter_terms.append((sas_terms, (tnum, tden)))
    return _report_from_terms(cfg, per_iter_terms,
                              full_geometry=full_geometry,
                              num_steps=n, tips_flags=tips_flags)


def _report_from_terms(cfg: "PipelineConfig", per_iter_terms,
                       full_geometry: bool = True,
                       num_steps: Optional[int] = None,
                       tips_flags=None) -> "PipelineEnergyReport":
    """Per-iteration aggregated terms -> the full-geometry ledger report.

    ``per_iter_terms``: one ``(sas_terms, (tips_num, tips_den))`` per DDIM
    iteration, where ``sas_terms`` maps resolution to summed
    (compressed, baseline) byte terms.  Shared tail of the batch-stats
    aggregation (:func:`energy_report_multi`) and the slot-serving
    accumulator path (:func:`energy_report_from_accum`) — both reduce to
    these terms, which is what makes the two serving modes' headlines
    comparable bit-for-bit.

    ``num_steps``: the trajectory length when a ``SamplerPolicy`` budget
    overrides the config's schedule (default: config steps).
    ``tips_flags``: per-iteration TIPS-active booleans for the same case
    (default: the config's ``i < tips_active_iters`` window); the
    ``cfg.unet.tips`` master toggle still gates both.
    """
    n = cfg.ddim.num_inference_steps if num_steps is None else num_steps
    if len(per_iter_terms) != n:
        raise ValueError(
            f"{len(per_iter_terms)} iteration terms, schedule says {n}")
    # contract hooks: full_geometry() is the family's analytic-ledger
    # extrapolation target, attn_resolutions() its measured-ratio remap
    # keys; the fallbacks reproduce the UNet formulas for plain configs
    if full_geometry:
        geom_fn = getattr(cfg.unet, "full_geometry", None)
        geom = geom_fn() if callable(geom_fn) else UNetConfig()
    else:
        geom = cfg.unet
    precision = cfg.unet.effective_precision()
    res_fn = getattr(geom, "attn_resolutions", None)
    geom_res = (list(res_fn()) if callable(res_fn) else
                sorted({geom.latent_size >> s
                        for s, a in enumerate(geom.down_attn) if a},
                       reverse=True))

    def remap(ratios: dict) -> dict:
        meas = sorted(ratios, reverse=True)
        return {g: ratios[m] for g, m in zip(geom_res, meas)}

    opts_per_iter = []
    for i, (sas_terms, (tnum, tden)) in enumerate(per_iter_terms):
        sas_ratio = {res: num / max(den, 1e-12)
                     for res, (num, den) in sas_terms.items()}
        tips_on = (i < cfg.ddim.tips_active_iters if tips_flags is None
                   else bool(tips_flags[i]))
        opts_per_iter.append(L.LedgerOptions(
            pssa=cfg.unet.pssa,
            tips=cfg.unet.tips and tips_on,
            sas_ratio=remap(sas_ratio),
            tips_low_ratio=tnum / max(tden, 1e-12),
            # MAC split mirrors the datapath's actual FFN mask coverage
            tips_mid=precision.ffn_mid,
        ))
    baseline_opts = [L.LedgerOptions()] * n
    return PipelineEnergyReport(
        optimized=L.generation_report(geom, opts_per_iter),
        baseline=L.generation_report(geom, baseline_opts),
        iterations=n,
    )


def ledger_terms_from_accum(cfg: "PipelineConfig", accum) -> list:
    """Per-iteration ledger terms from a slot-serving ``LedgerAccum``.

    The continuous-batching runtime accumulates INTEGER counters per DDIM
    iteration (``repro.diffusion.stats.LedgerAccum``); this assembles the
    same per-iteration (SAS byte, TIPS workload) terms that
    :func:`energy_report_multi` derives from per-call ``UNetStats`` —
    bit-identically, because both reduce the same integer counters through
    the same byte arithmetic (``pssa.stats_from_counters``) and the same
    float32 ratio step the device path uses.  Slot count, admission order,
    and occupancy cannot move a term: integer accumulation is exact.
    """
    nnz, ones_xor, imp, rows = _fetch_accum(accum)
    layers = attn_layer_order(cfg.unet)
    n = cfg.ddim.num_inference_steps
    if nnz.shape != (n, len(layers)):
        raise ValueError(f"accumulator shape {nnz.shape} does not match "
                         f"({n}, {len(layers)})")
    return _terms_from_counters(cfg, nnz, ones_xor, imp, rows, 0, n)


def _fetch_accum(accum):
    """One host transfer of the four SAS/TIPS counter planes."""
    import numpy as np

    return tuple(np.asarray(x) for x in jax.device_get(
        (accum.nnz, accum.ones_xor, accum.imp, accum.rows)))


def _terms_from_counters(cfg: "PipelineConfig", nnz, ones_xor, imp, rows,
                         start: int, n: int) -> list:
    """Bucket rows ``[start, start + n)`` -> per-iteration ledger terms.

    Shared by the legacy single-schedule accumulator (``start=0``) and the
    banked per-policy slices (``start = policy_index * bank_max_steps``):
    a policy's terms depend only on ITS buckets, so the same integers give
    the same floats no matter what else shared the slot batch.
    """
    layers = attn_layer_order(cfg.unet)
    heads_at = getattr(cfg.unet, "heads_at", lambda res: cfg.unet.num_heads)
    per_iter_terms = []
    for i in range(start, start + n):
        sas_terms: dict = {}
        tnum = tden = 0.0
        r = int(rows[i])
        for li, lk in enumerate(layers):
            if r == 0:
                continue                  # nothing accounted yet
            res = lk.resolution
            tq = res * res
            st = pssa.stats_from_counters(
                jnp.asarray(int(nnz[i, li])), jnp.asarray(int(ones_xor[i, li])),
                lead=r * heads_at(res), tq=tq, tk=tq,
                patch=cfg.unet.patch_size(res))
            num, den = sas_terms.get(res, (0.0, 0.0))
            sas_terms[res] = (num + float(st.bytes_pssa_total),
                              den + float(st.bytes_baseline))
            # the one-shot path sums (1 - imp_c/(rows_c*Tq)) * Tq * rows_c
            # per call; with exact per-call folds (power-of-two
            # rows_c * Tq) that telescopes to the INTEGER
            # Tq*rows - imp_total, so the accumulator reproduces the
            # aggregated term without ever dividing
            tnum += float(tq * r - int(imp[i, li]))
            tden += float(tq * r)
        per_iter_terms.append((sas_terms, (tnum, tden)))
    return per_iter_terms


def banked_ledger_terms(cfg: "PipelineConfig", accum, bank) -> list:
    """Per-policy per-iteration ledger terms from a BANKED ``LedgerAccum``.

    A banked slot state (``init_slots(bank=...)``) scatters counters into
    bucket ``p * N + i`` (N = bank max budget), so policy ``p``'s
    trajectory is the contiguous row block ``[p*N, p*N + budget_p)``.
    Returns one per-iteration term list per bank entry, in bank order —
    each the exact analogue of what :func:`ledger_terms_from_accum`
    produces for a single-schedule run of only that policy's requests.
    """
    bank = solvers_mod.as_bank(bank)
    nnz, ones_xor, imp, rows = _fetch_accum(accum)
    layers = attn_layer_order(cfg.unet)
    n_max = solvers_mod.bank_max_steps(bank)
    want = (len(bank) * n_max, len(layers))
    if nnz.shape != want:
        raise ValueError(f"accumulator shape {nnz.shape} does not match "
                         f"banked layout {want}")
    return [_terms_from_counters(cfg, nnz, ones_xor, imp, rows,
                                 p * n_max, pol.num_steps)
            for p, pol in enumerate(bank)]


def energy_report_banked(cfg: "PipelineConfig", accum, bank,
                         full_geometry: bool = True
                         ) -> "BankedEnergyReport":
    """Per-policy + aggregate energy report for a banked serving run.

    Each policy's buckets flow through the SAME term assembly and ledger
    as a dedicated single-policy run, so every per-policy headline is
    bit-identical to serving that policy's requests alone — and invariant
    to slot count and admission order (integer accumulation).  Policies
    whose buckets saw no work (``rows[p*N] == 0``) are reported with
    ``images == 0`` and excluded from the aggregate.

    The per-image energy honestly charges each tier its OWN step budget:
    ``mj_per_image = mj_per_iter_with_ema * num_steps`` — the quantity the
    step-budget sweep compares across tiers.
    """
    terms = banked_ledger_terms(cfg, accum, bank)
    bank = solvers_mod.as_bank(bank)
    _, _, _, rows = _fetch_accum(accum)
    n_max = solvers_mod.bank_max_steps(bank)
    entries = []
    for p, (pol, t) in enumerate(zip(bank, terms)):
        # every admitted request visits its step-0 bucket exactly once
        images = int(rows[p * n_max])
        report = None
        if images > 0:
            report = _report_from_terms(
                cfg, t, full_geometry=full_geometry,
                num_steps=pol.num_steps,
                tips_flags=solvers_mod.tips_active_schedule(pol, cfg.ddim))
        entries.append(BankedPolicyReport(policy=pol, images=images,
                                          report=report))
    return BankedEnergyReport(entries=tuple(entries))


def energy_report_from_accum(cfg: "PipelineConfig", accum,
                             full_geometry: bool = True
                             ) -> "PipelineEnergyReport":
    """Energy report for a drained slot-serving run (DESIGN.md §8).

    Bit-identical to :func:`energy_report_multi` over the same requests
    served one-shot whenever the per-call float folds are exact —
    power-of-two accounted rows per call, always true for the test/bench
    configurations and trivially true for the single-call oracle.
    """
    return _report_from_terms(cfg, ledger_terms_from_accum(cfg, accum),
                              full_geometry=full_geometry)


def merge_ledger_accums(accums) -> "LedgerAccum":
    """Sum per-replica ``LedgerAccum``s into one cluster accumulator.

    The multi-replica ledger primitive (DESIGN.md §13): every replica's
    slot runtime scatters INTEGER counters into the same per-iteration
    (or per-(policy, step)) bucket layout, and integer addition is exact,
    associative and commutative — so the merged accumulator, and every
    report derived from it, is bit-identical at ANY replica count,
    routing decision, or admission order that serves the same requests.
    This is the cluster-scale analogue of ``energy_report_multi``'s
    sum-before-divide rule for micro-batch serving.
    """
    accums = list(accums)
    if not accums:
        raise ValueError("merge_ledger_accums: no accumulators")
    shapes = {tuple(a.nnz.shape) for a in accums}
    if len(shapes) > 1:
        raise ValueError(
            f"merge_ledger_accums: mismatched bucket layouts {shapes} — "
            f"replicas must share one bank/schedule")
    merged = accums[0]
    for a in accums[1:]:
        merged = jax.tree_util.tree_map(lambda x, y: x + y, merged, a)
    return merged


def energy_report_cluster(cfg: "PipelineConfig", accums, bank=None,
                          full_geometry: bool = True):
    """Energy report for a multi-replica (cluster-router) serving run.

    ``accums``: one drained ``LedgerAccum`` per replica.  Merged with
    :func:`merge_ledger_accums`, then reported through the same tail as
    single-replica slot serving — :func:`energy_report_banked` when the
    replicas served a sampler ``bank``, :func:`energy_report_from_accum`
    otherwise — so the cluster headline is bit-identical to one replica,
    and to the same requests served one-shot.
    """
    merged = merge_ledger_accums(accums)
    if bank is not None:
        return energy_report_banked(cfg, merged, bank,
                                    full_geometry=full_geometry)
    return energy_report_from_accum(cfg, merged,
                                    full_geometry=full_geometry)


def phase_breakdown_from_accum(cfg: "PipelineConfig", accum, bank) -> list:
    """Per-policy, per-phase realized ratios from a banked accumulator.

    Groups each policy's per-iteration terms by its phase schedule
    (``solvers.phase_index_schedule``) and reduces terms WITHIN each phase
    before dividing — the phase-resolved view of what the phase-scheduled
    thresholds actually did to SAS compression and the INT6 fraction.
    Returns, per bank entry, ``{"policy", "phases": [{"phase", "iters",
    "sas_ratio", "tips_low_ratio"}, ...]}``.
    """
    out = []
    bank = solvers_mod.as_bank(bank)
    for pol, terms in zip(bank, banked_ledger_terms(cfg, accum, bank)):
        phase_ids = solvers_mod.phase_index_schedule(pol)
        groups: dict = {}
        for i, (sas_terms, (tnum, tden)) in enumerate(terms):
            g = groups.setdefault(phase_ids[i], [0, {}, 0.0, 0.0])
            g[0] += 1
            for res, (num, den) in sas_terms.items():
                a, b = g[1].get(res, (0.0, 0.0))
                g[1][res] = (a + num, b + den)
            g[2] += tnum
            g[3] += tden
        phases = []
        for ph in sorted(groups):
            iters, sas, tnum, tden = groups[ph]
            snum = sum(n for n, _ in sas.values())
            sden = sum(d for _, d in sas.values())
            phases.append({
                "phase": ph, "iters": iters,
                "sas_ratio": snum / max(sden, 1e-12),
                "tips_low_ratio": tnum / max(tden, 1e-12)})
        out.append({"policy": pol.key(), "phases": phases})
    return out


def tips_ratios_from_accum(cfg: "PipelineConfig", accum) -> list:
    """Per-iteration realized INT6 row fraction from the accumulator."""
    return [num / max(den, 1e-12)
            for _, (num, den) in ledger_terms_from_accum(cfg, accum)]


def aggregated_tips_ratios_per_iter(cfg: "PipelineConfig",
                                    stats_per_batch) -> list:
    """Row-weighted per-iteration TIPS low-precision ratios across calls.

    Feeds ``tips.workload_low_precision_fraction(..., ddim=cfg.ddim)`` so
    a serving run reports the INT6 workload fraction of ITS schedule.
    """
    fetched = [coerce_per_step_stats(
        s.ledger_fetch() if isinstance(s, UNetStats) else s)
        for s in stats_per_batch]
    out = []
    for i in range(cfg.ddim.num_inference_steps):
        num = den = 0.0
        for s in fetched:
            a, b = _tips_ratio_terms(s[i])
            num, den = num + a, den + b
        out.append(num / max(den, 1e-12))
    return out


def reuse_ratios_from_accum(cfg: "PipelineConfig", accum) -> list:
    """Per-iteration REALIZED temporal-reuse ratio from a ``LedgerAccum``.

    Ratio ``i`` is ``1 - computed/total`` over the iteration's reuse row
    counters summed across layers and accounted rows — the fraction of
    patch rows served from the cache instead of recomputed.  Integer
    counters in, one float division out, so the value is bit-identical
    across slot counts, admission orders, and data-parallel layouts (the
    same invariance the SAS/TIPS buckets carry).  Iterations with no
    accounted reuse work (dense runs, not-yet-reached steps) report 0.0.
    """
    import numpy as np

    comp, tot = (np.asarray(x) for x in jax.device_get(
        (accum.reuse_computed, accum.reuse_total)))
    out = []
    for i in range(cfg.ddim.num_inference_steps):
        t = float(tot[i].sum())
        out.append(0.0 if t == 0.0 else 1.0 - float(comp[i].sum()) / t)
    return out


def aggregated_reuse_ratios_per_iter(cfg: "PipelineConfig",
                                     stats_per_batch) -> list:
    """Per-iteration realized reuse ratio across scanned engine calls.

    ``stats_per_batch``: stacked ``UNetStats`` trajectories whose
    ``reuse`` counters carry a leading iteration axis (what
    ``sample_scan_reuse`` returns).  Terms are summed across batches and
    layers before dividing — same reduction as
    :func:`reuse_ratios_from_accum`, so slot serving and one-shot serving
    report identical ratios for the same work.  Dense trajectories
    (empty ``reuse`` tuple) contribute nothing; all-dense input yields
    zeros.
    """
    import numpy as np

    out = []
    for i in range(cfg.ddim.num_inference_steps):
        num = den = 0.0
        for s in stats_per_batch:
            reuse = s.reuse if isinstance(s, UNetStats) else ()
            for c in reuse:
                comp, tot = (np.asarray(x) for x in
                             jax.device_get((c.computed, c.total)))
                num += float(comp[i].sum())
                den += float(tot[i].sum())
        out.append(0.0 if den == 0.0 else 1.0 - num / den)
    return out


@dataclasses.dataclass
class PipelineEnergyReport:
    optimized: energy.EnergyReport
    baseline: energy.EnergyReport
    iterations: int

    @property
    def ema_gb_per_iter_baseline(self) -> float:
        return self.baseline.ema_bytes_total / self.iterations / 1e9

    @property
    def ema_reduction(self) -> float:
        return 1.0 - (self.optimized.ema_bytes_total
                      / self.baseline.ema_bytes_total)

    @property
    def mj_per_iter_with_ema(self) -> float:
        return self.optimized.total_mj / self.iterations

    @property
    def mj_per_iter_compute(self) -> float:
        return self.optimized.compute_energy_mj / self.iterations

    def summary(self) -> dict:
        return {
            "ema_gb_per_iter_baseline": self.ema_gb_per_iter_baseline,
            "ema_gb_per_iter_optimized":
                self.optimized.ema_bytes_total / self.iterations / 1e9,
            "total_ema_reduction": self.ema_reduction,
            "sas_fraction_of_ema_baseline": self.baseline.sas_fraction,
            "transformer_ema_fraction_baseline":
                self.baseline.stage_fraction("self_attn", "cross_attn",
                                             "ffn"),
            "self_attn_fraction_of_transformer":
                (self.baseline.ema_bytes_by_stage.get("self_attn", 0.0)
                 / max(sum(self.baseline.ema_bytes_by_stage.get(s, 0.0)
                           for s in ("self_attn", "cross_attn", "ffn")),
                       1e-12)),
            "mj_per_iter_compute": self.mj_per_iter_compute,
            "mj_per_iter_with_ema": self.mj_per_iter_with_ema,
        }


@dataclasses.dataclass
class BankedPolicyReport:
    """One bank entry's share of a banked serving run.

    ``images`` is the request count that ran under this policy (read from
    its step-0 bucket's row counter — every admitted request visits it
    exactly once).  ``report`` is ``None`` when the policy served nothing.
    """
    policy: object                            # solvers.SamplerPolicy
    images: int
    report: Optional[PipelineEnergyReport]

    @property
    def mj_per_image(self) -> float:
        """Modeled energy per image at THIS policy's step budget."""
        if self.report is None:
            return 0.0
        return self.report.mj_per_iter_with_ema * self.policy.num_steps


@dataclasses.dataclass
class BankedEnergyReport:
    """Per-policy energy reports + the images-weighted aggregate."""
    entries: tuple                            # of BankedPolicyReport

    @property
    def images(self) -> int:
        return sum(e.images for e in self.entries)

    @property
    def mj_per_image(self) -> float:
        """Images-weighted mean energy per image across the bank."""
        total = self.images
        if total == 0:
            return 0.0
        return sum(e.mj_per_image * e.images for e in self.entries) / total

    def summary(self) -> dict:
        return {
            "images": self.images,
            "mj_per_image_weighted": self.mj_per_image,
            "per_policy": [
                {"policy": e.policy.key(),
                 "tier": e.policy.name or None,
                 "num_steps": e.policy.num_steps,
                 "images": e.images,
                 "mj_per_image": e.mj_per_image,
                 **({} if e.report is None else e.report.summary())}
                for e in self.entries],
        }
