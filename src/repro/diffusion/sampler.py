"""DDIM sampler — 25 denoising iterations (the paper's operating point).

Deterministic DDIM (eta = 0) over a linear-beta DDPM schedule, with optional
classifier-free guidance.  TIPS is active for the first 20 of the 25
iterations (paper Fig. 9(b)): the last 5 are quantization-vulnerable and run
full INT12 — the sampler passes ``tips_active`` per step.

Two interchangeable loop implementations:

``sample``       — the seed's Python loop (25 dispatches, two UNet calls per
                   step under CFG).  Kept as the parity/reference path: its
                   per-iteration stats list is the ground truth the scanned
                   path is tested against.
``sample_scan``  — all 25 steps inside one ``jax.lax.scan`` with
                   ``tips_active`` as a per-step traced array, and cond +
                   uncond CFG fused into ONE batched UNet call (concatenate
                   along batch, split after).  Halves dispatch count, makes
                   the whole loop jittable (the ``DiffusionEngine`` wraps
                   encode -> scan -> decode in a single ``jax.jit``), and
                   returns the stats trajectory as a stacked ``UNetStats``
                   pytree (leading axis = iterations).
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from repro.core.tips import TIPS_ACTIVE_ITERS
from repro.diffusion import solvers as solvers_mod


@dataclasses.dataclass(frozen=True)
class DDIMConfig:
    num_train_steps: int = 1000
    num_inference_steps: int = 25        # paper: 25 UNet iterations
    beta_start: float = 0.00085
    beta_end: float = 0.012
    guidance_scale: float = 7.5
    tips_active_iters: int = TIPS_ACTIVE_ITERS


def alphas_cumprod(cfg: DDIMConfig):
    betas = jnp.linspace(cfg.beta_start ** 0.5, cfg.beta_end ** 0.5,
                         cfg.num_train_steps) ** 2
    return jnp.cumprod(1.0 - betas)


def timestep_schedule(cfg: DDIMConfig):
    """Descending DDIM timesteps, e.g. [960, 920, ..., 0] for 25 steps."""
    step = cfg.num_train_steps // cfg.num_inference_steps
    return jnp.arange(cfg.num_inference_steps - 1, -1, -1) * step


def ddim_step(latents, eps, t, t_prev, acp):
    """One deterministic DDIM update (eta = 0).

    ``t`` / ``t_prev`` are a scalar timestep (whole batch on one schedule)
    or (B,) per-row timesteps — continuous batching runs each slot at its
    own denoising iteration, so the alphas are gathered per row and
    broadcast over the spatial axes.  Per-row values equal to the scalar
    produce bit-identical updates (same elementwise arithmetic).
    """
    a_t = acp[t]
    a_prev = jnp.where(t_prev >= 0, acp[jnp.maximum(t_prev, 0)], 1.0)
    if jnp.ndim(a_t) == 1:
        shape = (latents.shape[0],) + (1,) * (latents.ndim - 1)
        a_t, a_prev = a_t.reshape(shape), a_prev.reshape(shape)
    return solvers_mod.ddim_transfer(latents, eps, a_t, a_prev)


def cfg_batch(latents, context, uncond_context):
    """Fuse cond + uncond into one batch: (B,...) -> (2B,...).

    Row layout is [cond | uncond] along the leading axis; undo with
    ``jnp.split(eps, 2)``.  Each half attends to its own context, so the
    fused call is arithmetically identical to two separate calls.
    """
    lat2 = jnp.concatenate([latents, latents], axis=0)
    return lat2, fuse_context(context, uncond_context)


def fuse_context(context, uncond_context):
    """[cond | uncond] rows of every leaf of the encoded text (a (B, T, d)
    array, or a pytree of them such as SDXL's (states, pooled text))."""
    return jax.tree.map(lambda c, u: jnp.concatenate([c, u], axis=0),
                        context, uncond_context)


def guided_eps(eps_fused, guidance_scale):
    """Split a fused [cond | uncond] eps and apply CFG."""
    eps_c, eps_u = jnp.split(eps_fused, 2, axis=0)
    return eps_u + guidance_scale * (eps_c - eps_u)


def sample(unet_apply, latents, context, uncond_context, cfg: DDIMConfig,
           collect_stats: bool = False):
    """Run the denoising loop as 25 Python-level dispatches (seed path).

    ``unet_apply(latents, timesteps, context, tips_active)`` -> (eps, stats).
    Kept for per-step inspectability and as the reference the scanned
    implementation is verified against (tests/test_engine.py).
    """
    acp = alphas_cumprod(cfg)
    ts = timestep_schedule(cfg)
    step = cfg.num_train_steps // cfg.num_inference_steps
    all_stats = []
    for i in range(cfg.num_inference_steps):
        t = ts[i]
        tips_active = i < cfg.tips_active_iters
        b = latents.shape[0]
        tvec = jnp.full((b,), t, jnp.int32)
        eps_c, stats = unet_apply(latents, tvec, context, tips_active)
        if cfg.guidance_scale != 1.0 and uncond_context is not None:
            eps_u, _ = unet_apply(latents, tvec, uncond_context, tips_active)
            eps = eps_u + cfg.guidance_scale * (eps_c - eps_u)
        else:
            eps = eps_c
        latents = ddim_step(latents, eps, t, t - step, acp)
        if collect_stats:
            all_stats.append(stats)
    return latents, all_stats


def denoise_step(unet_apply, latents, context, uncond_context, step_idx,
                cfg: DDIMConfig, stats_rows=None, active=None,
                row_stats: bool = False, reuse_cache=None,
                bank=None, policy_id=None, solver_hist=None):
    """ONE denoising update at PER-SLOT step indices (the scan body).

    ``step_idx`` is (B,) int32 — each batch row's DDIM iteration in
    ``[0, num_inference_steps)`` (a scalar is broadcast).  Rows may sit at
    *different* iterations: the DDIM alphas and the per-row TIPS activity
    flag are gathered per row, which is what lets a continuous-batching
    server interleave requests at heterogeneous steps in one batched UNet
    call.  With every row at the same index the arithmetic is elementwise
    identical to the homogeneous path, so ``sample_scan`` (whose scan body
    this is) produces bit-identical latents to the seed loop.

    Under CFG the cond and uncond UNet evaluations are fused into a single
    batched call with the shared prefix deduplicated; ``unet_apply`` must
    accept static ``stats_rows`` and ``cfg_dup`` keywords
    (``repro.diffusion.unet.unet_forward`` does) — stats restricted to the
    cond rows, latents carrying only the cond half.  ``stats_rows``
    (static) restricts the PSSA/TIPS accounting to the first N batch rows.

    ``active`` (B,) bool gates slot serving: inactive rows keep their
    latents unchanged (their UNet work is computed and discarded — the
    fixed-shape price of slot serving) and their step index is clipped
    into range; the CALLER must mask their stats out (``LedgerAccum``
    multiplies counters by the mask before the scatter).  ``row_stats``
    requests per-row integer counters (``SlotStats``) instead of folded
    stats; it is forwarded to ``unet_apply`` only when set, so legacy
    closures without the keyword keep working.

    ``reuse_cache`` (a ``core.reuse.ReuseCache``) threads the temporal
    patch-reuse reference through the UNet; ``unet_apply`` then returns a
    third element — the new cache — and so does this function:
    ``(latents, stats, new_cache)``.  Without it the two-tuple contract
    is unchanged.

    ``bank`` (static tuple of ``solvers.SamplerPolicy``) switches the
    update to the generalized per-row solver path: ``policy_id`` (B,)
    int32 selects each row's policy, step indices clip to PER-ROW budgets,
    timesteps / TIPS activity / solver coefficients are gathered from the
    bank's ``SolverTables``, phase-schedule threshold scales (when any
    bank policy schedules them) are resolved per row and passed to
    ``unet_apply`` as ``overrides``, and multistep solver history rides
    ``solver_hist`` (B, H, ...).  The banked return contract is always a
    4-tuple ``(latents, stats, new_cache_or_None, new_hist)``.  With
    ``bank=None`` every legacy contract above is unchanged, op for op.
    """
    acp = alphas_cumprod(cfg)
    ts = timestep_schedule(cfg)
    step = cfg.num_train_steps // cfg.num_inference_steps
    b = latents.shape[0]
    step_idx = jnp.asarray(step_idx, jnp.int32)
    if step_idx.ndim == 0:
        step_idx = jnp.full((b,), step_idx, jnp.int32)
    if bank is not None:
        bank = solvers_mod.as_bank(bank)
        tables = solvers_mod.solver_tables(bank, cfg)
        if policy_id is None:
            policy_id = jnp.zeros((b,), jnp.int32)
        policy_id = jnp.asarray(policy_id, jnp.int32)
        if solver_hist is None:
            solver_hist = solvers_mod.init_history(bank, b, latents.shape[1:])
        idx = jnp.clip(step_idx, 0, tables.budget[policy_id] - 1)
        t = tables.t[policy_id, idx]              # (B,) per-row timesteps
        tips_vec = tables.tips[policy_id, idx]    # (B,) per-row TIPS flag
    else:
        idx = jnp.clip(step_idx, 0, cfg.num_inference_steps - 1)
        t = ts[idx]                               # (B,) per-row timesteps
        tips_vec = idx < cfg.tips_active_iters    # (B,) per-row TIPS flag
    kw = {"row_stats": True} if row_stats else {}
    if reuse_cache is not None:
        kw["reuse_cache"] = reuse_cache
    if bank is not None:
        overrides = solvers_mod.gather_overrides(tables, bank, policy_id,
                                                 idx)
        if overrides is not None:
            kw["overrides"] = overrides

    use_cfg = cfg.guidance_scale != 1.0 and uncond_context is not None
    if use_cfg:
        # cfg_dup: latents stay at b rows — the UNet tiles the hidden
        # state to [cond | uncond] at the first cross-attention (the
        # halves are identical before it).  stats_rows defaults to b:
        # PSSA/TIPS accounted on the cond half only — the ledger never
        # consumes uncond stats (the two-call reference path computes
        # and discards them; the fused path skips them).
        ctx_fused = fuse_context(context, uncond_context)
        rows = b if stats_rows is None else stats_rows
        out = unet_apply(latents, t, ctx_fused, tips_vec,
                         stats_rows=rows, cfg_dup=True, **kw)
    else:
        out = unet_apply(latents, t, context, tips_vec,
                         stats_rows=stats_rows, **kw)
    if reuse_cache is not None:
        eps, stats, new_cache = out
    else:
        eps, stats = out
        new_cache = None
    if use_cfg:
        eps = guided_eps(eps, cfg.guidance_scale)
    if bank is not None:
        new_lat, new_hist = solvers_mod.solver_update(
            latents, eps, solver_hist, tables, bank, policy_id, idx)
    else:
        new_lat = ddim_step(latents, eps, t, t - step, acp)
        new_hist = None
    if active is not None:
        keep = active.reshape((b,) + (1,) * (latents.ndim - 1))
        new_lat = jnp.where(keep, new_lat, latents)
        if new_hist is not None and new_hist.shape[1] > 0:
            new_hist = jnp.where(keep[:, None], new_hist, solver_hist)
    if bank is not None:
        return new_lat, stats, new_cache, new_hist
    if reuse_cache is not None:
        return new_lat, stats, new_cache
    return new_lat, stats


def _resolve_bank(sampler_policy, sampler_bank):
    """(bank, num_scan_steps, policy_index) for the banked scan paths.

    Without ``sampler_bank`` the policy becomes its own single-entry
    bank.  With it, the scan runs under the full bank's structure but
    only for ``sampler_policy``'s own step budget, rows pinned to its
    index — mirroring what a slot row of that policy executes before
    retiring.
    """
    if sampler_bank is None:
        bank = solvers_mod.as_bank(sampler_policy)
        return bank, solvers_mod.bank_max_steps(bank), 0
    bank = solvers_mod.as_bank(sampler_bank)
    if sampler_policy not in bank:
        raise ValueError(
            f"sampler_policy {sampler_policy.key()} is not an entry of "
            f"sampler_bank {[p.key() for p in bank]}")
    return bank, sampler_policy.num_steps, bank.index(sampler_policy)


def sample_scan(unet_apply, latents, context, uncond_context,
                cfg: DDIMConfig, stats_rows=None, sampler_policy=None,
                sampler_bank=None, policy_id=None):
    """Run all denoising steps inside one ``jax.lax.scan``.

    The scan body is :func:`denoise_step` with every row at the same step
    index — the same executable building block the continuous-batching
    engine (``DiffusionEngine.slot_step``) runs standalone with
    heterogeneous per-slot indices, so the two paths cannot drift.
    Under CFG the cond and uncond UNet evaluations are fused into a
    single batched call per step with the shared prefix deduplicated.
    ``stats_rows`` (static) restricts the PSSA/TIPS accounting to the
    first N batch rows — the serving front-end sets it to the valid
    (non-padded) row count of a tail micro-batch so padded duplicate rows
    never leak into the energy ledger.
    Returns ``(latents,
    stacked_stats)`` where ``stacked_stats`` is a ``UNetStats`` whose
    leaves carry a leading ``num_inference_steps`` axis; reconstruct the
    per-step view with ``stacked_stats.step(i)`` / ``.unstack()``.

    ``sampler_policy`` (a ``solvers.SamplerPolicy``) swaps the solver and
    the step budget: the scan runs ``policy.num_steps`` iterations of the
    banked :func:`denoise_step` with a single-policy bank, multistep
    history in the carry.  A ``(ddim, num_inference_steps)`` policy is
    bit-identical to the default path (same gathered coefficients, same
    shared transfer arithmetic — tests/test_solvers.py pins it).

    ``sampler_bank`` (static tuple of policies containing
    ``sampler_policy``) traces the scan body under the FULL bank — full
    coefficient tables, full multistep-history depth, the complete
    per-row select structure — with every row pinned to
    ``sampler_policy``'s index.  XLA specializes fusion clusters (and
    hence FMA contraction) to the traced graph, so a collapsed
    single-policy program can drift ~1e-6 from the mixed-bank slot
    executable even for logically identical rows; sharing the bank
    structure is what makes the one-shot path a bit-exact oracle for
    mixed-tier slot serving (DESIGN.md §10).  ``policy_id`` (a (B,)
    int32 ARRAY of the policy's bank index) must then arrive as a traced
    runtime operand, not a trace-time constant — a constant lets XLA
    fold the per-row coefficient gathers into the UNet's fusion clusters
    and shift FMA contraction relative to the slot executable (whose
    ``policy_id`` lives in donated state).  The engine passes it through
    the jit boundary (``DiffusionEngine._get_compiled``).
    """
    b = latents.shape[0]
    if stats_rows is not None and not (0 < stats_rows <= b):
        raise ValueError(f"stats_rows={stats_rows} outside [1, {b}]")
    if sampler_bank is not None and sampler_policy is None:
        raise ValueError("sampler_bank requires sampler_policy (the "
                         "bank entry to run every row under)")
    if sampler_policy is not None:
        bank, n, pid0 = _resolve_bank(sampler_policy, sampler_bank)
        if policy_id is None:
            policy_id = jnp.full((b,), pid0, jnp.int32)

        def body(carry, i):
            lat, hist = carry
            lat, stats, _, hist = denoise_step(
                unet_apply, lat, context, uncond_context,
                jnp.full((b,), i, jnp.int32), cfg, stats_rows=stats_rows,
                bank=bank, policy_id=policy_id, solver_hist=hist)
            return (lat, hist), stats

        hist0 = solvers_mod.init_history(bank, b, latents.shape[1:])
        (latents, _), stacked = jax.lax.scan(body, (latents, hist0),
                                             jnp.arange(n))
        return latents, stacked

    n = cfg.num_inference_steps

    def body(lat, i):
        return denoise_step(unet_apply, lat, context, uncond_context,
                            jnp.full((b,), i, jnp.int32), cfg,
                            stats_rows=stats_rows)

    latents, stacked = jax.lax.scan(body, latents, jnp.arange(n))
    return latents, stacked


def sample_scan_reuse(unet_apply, latents, context, uncond_context,
                      cfg: DDIMConfig, reuse_cache=None, stats_rows=None,
                      base_caches=None, record_caches: bool = False,
                      sampler_policy=None, sampler_bank=None,
                      policy_id=None):
    """Scanned denoising loop with the temporal-reuse cache threaded.

    Two cache sources, mirroring the two ``ReusePolicy`` modes:

    * **temporal** — ``reuse_cache`` (typically all-invalid zeros from
      ``core.reuse.reuse_cache_zeros``) rides the scan carry: each step
      reuses the PREVIOUS step's activations.  ``record_caches=True``
      additionally stacks every step's emitted cache along a leading axis
      (the base-trace recorder for edit serving) and returns
      ``(latents, stats, caches)``.
    * **edit** — ``base_caches`` is such a recorded stack from a BASE
      request; step ``i`` reuses the base's step-``i`` activations
      (indexed from the stack, nothing carried), which is what makes
      ``capacity < 1`` safe: the reference is valid from step 0.

    Returns ``(latents, stacked_stats)`` (plus the recorded caches when
    asked); ``stacked_stats`` carries per-layer reuse counters.

    ``sampler_policy`` composes with both modes exactly as in
    :func:`sample_scan`: the banked :func:`denoise_step` with a
    single-policy bank, solver history alongside the cache in the carry.
    (Edit-mode ``base_caches`` must have been recorded with the same
    policy — the per-step references are indexed by step.)
    ``sampler_bank`` likewise mirrors :func:`sample_scan`: trace under
    the full bank with rows pinned to ``sampler_policy``'s index.
    """
    b = latents.shape[0]
    if stats_rows is not None and not (0 < stats_rows <= b):
        raise ValueError(f"stats_rows={stats_rows} outside [1, {b}]")
    if (reuse_cache is None) == (base_caches is None):
        raise ValueError(
            "pass exactly one of reuse_cache (temporal mode) or "
            "base_caches (edit mode)")
    if sampler_bank is not None and sampler_policy is None:
        raise ValueError("sampler_bank requires sampler_policy (the "
                         "bank entry to run every row under)")
    bank = None
    if sampler_policy is not None:
        bank, n, pid0 = _resolve_bank(sampler_policy, sampler_bank)
        if policy_id is None:
            policy_id = jnp.full((b,), pid0, jnp.int32)
        hist0 = solvers_mod.init_history(bank, b, latents.shape[1:])
    else:
        n = cfg.num_inference_steps

    if base_caches is not None:
        def body(carry, i):
            lat, hist = carry
            cache_i = jax.tree_util.tree_map(lambda x: x[i], base_caches)
            if bank is not None:
                lat, stats, _, hist = denoise_step(
                    unet_apply, lat, context, uncond_context,
                    jnp.full((b,), i, jnp.int32), cfg,
                    stats_rows=stats_rows, reuse_cache=cache_i,
                    bank=bank, policy_id=policy_id, solver_hist=hist)
            else:
                lat, stats, _ = denoise_step(
                    unet_apply, lat, context, uncond_context,
                    jnp.full((b,), i, jnp.int32), cfg,
                    stats_rows=stats_rows, reuse_cache=cache_i)
            return (lat, hist), stats

        hist_init = hist0 if bank is not None else jnp.zeros((b, 0))
        (latents, _), stacked = jax.lax.scan(body, (latents, hist_init),
                                             jnp.arange(n))
        return latents, stacked

    def body(carry, i):
        lat, cache, hist = carry
        if bank is not None:
            lat, stats, cache, hist = denoise_step(
                unet_apply, lat, context, uncond_context,
                jnp.full((b,), i, jnp.int32), cfg, stats_rows=stats_rows,
                reuse_cache=cache, bank=bank, policy_id=policy_id,
                solver_hist=hist)
        else:
            lat, stats, cache = denoise_step(
                unet_apply, lat, context, uncond_context,
                jnp.full((b,), i, jnp.int32), cfg, stats_rows=stats_rows,
                reuse_cache=cache)
        ys = (stats, cache) if record_caches else stats
        return (lat, cache, hist), ys

    hist_init = hist0 if bank is not None else jnp.zeros((b, 0))
    (latents, _, _), ys = jax.lax.scan(
        body, (latents, reuse_cache, hist_init), jnp.arange(n))
    if record_caches:
        stacked, caches = ys
        return latents, stacked, caches
    return latents, ys
