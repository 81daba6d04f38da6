"""Shared CLI wiring for the serving-policy surface (DESIGN.md §13).

``serve_diffusion``, ``examples/generate_image.py`` and the cluster
router (``repro.launch.router``) all expose the same policy flags —
``--model --kernels --tips --reuse --solver --tiers``.  Before
``ServePolicies`` each CLI registered and parsed them independently and
they drifted (the example lacked ``--reuse``; help strings disagreed).
This module is the single registration + parsing point:

* :func:`add_policy_args` registers the flags on an ``ArgumentParser``;
* :func:`policies_from_args` turns the parsed namespace into one
  ``core.policies.ServePolicies`` bundle (with the serving
  reuse-capacity clamp);
* :func:`config_from_args` builds the ``PipelineConfig`` (geometry,
  denoiser family, schedule) with the bundle's per-axis policies
  installed.

A CLI that consumes these three cannot drift from the others — new
policy axes land here once.
"""
from __future__ import annotations

import dataclasses


def add_policy_args(ap, tiers: bool = True):
    """Register the shared policy flags on ``ap``.

    ``tiers=False`` omits ``--tiers`` for single-request CLIs (a bank is
    meaningless when exactly one request is in flight).  Returns ``ap``.
    """
    ap.add_argument("--model", choices=("unet", "dit", "sdxl"),
                    default="unet",
                    help="denoiser (DESIGN.md §11): the BK-SDM UNet "
                         "(default), the DiT-S/2 transformer, or SDXL "
                         "base 1.0 (repro.configs.sdxl_base: its UNet, "
                         "two text towers and VAE); all serve through "
                         "the same engine/scheduler spine and kernel "
                         "dispatch table")
    ap.add_argument("--kernels", default="auto",
                    help="kernel policy: 'auto' (fused on compiled "
                         "backends, reference on interpret backends), "
                         "'reference', 'fused', 'autotuned' (fused with "
                         "the committed block-size table), or per-op "
                         "overrides like 'self_attention=fused,ffn=dbsc,"
                         "ffn_quant=int8' "
                         "(see repro.kernels.dispatch.KernelPolicy)")
    ap.add_argument("--tips", default="fixed",
                    help="precision policy: 'fixed', 'adaptive', or field "
                         "overrides like 'adaptive,target=0.5,mid=true' "
                         "(see repro.core.precision.PrecisionPolicy)")
    ap.add_argument("--reuse", default="off",
                    help="temporal patch-reuse policy: 'off', 'temporal', "
                         "or overrides like 'temporal,threshold=0.1' "
                         "(see repro.core.reuse.ReusePolicy)")
    ap.add_argument("--solver", default="",
                    help="sampler policy for EVERY request: a tier name "
                         "('draft'|'balanced'|'quality'), a solver "
                         "('ddim'|'plms'|'dpm2m'), or overrides like "
                         "'dpm2m,steps=10,phases=detail_guard' "
                         "(see repro.diffusion.solvers.SamplerPolicy); "
                         "empty = the config's DDIM schedule")
    if tiers:
        ap.add_argument("--tiers", nargs="+", default=None,
                        help="mixed quality-tier serving bank: one "
                             "SamplerPolicy spec per tier (e.g. --tiers "
                             "draft balanced quality); requests cycle "
                             "through the tiers round-robin inside one "
                             "step executable")
    return ap


def policies_from_args(args, clamp_reuse_capacity: bool = True):
    """Parsed namespace -> one frozen ``ServePolicies`` bundle.

    ``clamp_reuse_capacity`` (default): serving engines run the TEMPORAL
    reuse path (cache starts invalid), where a sub-1.0 static gather
    capacity is illegal — clamp to 1.0 so ``--reuse edit,threshold=...``
    selects the edit threshold defaults while serving stays exact.
    """
    from repro.core.policies import ServePolicies

    pol = ServePolicies.parse(kernels=getattr(args, "kernels", "auto"),
                              tips=getattr(args, "tips", "fixed"),
                              reuse=getattr(args, "reuse", "off"),
                              solver=getattr(args, "solver", ""),
                              tiers=getattr(args, "tiers", None))
    if (clamp_reuse_capacity and pol.reuse.enabled
            and pol.reuse.capacity < 1.0):
        pol = dataclasses.replace(
            pol, reuse=dataclasses.replace(pol.reuse, capacity=1.0))
    return pol


def config_from_args(args, policies=None, steps=None, guidance=None):
    """Build the ``PipelineConfig`` a CLI run serves.

    Geometry from ``--smoke`` (absent = smoke, the CLI-demo default),
    denoiser family from ``--model`` (``sdxl``: the whole SDXL preset),
    schedule from ``--steps`` / ``--guidance`` (overridable via the
    keyword args), and the policy
    bundle's kernel/precision/reuse axes installed via
    ``ServePolicies.apply``.  ``policies=None`` parses the bundle from
    ``args`` (:func:`policies_from_args`).
    """
    from repro.diffusion.pipeline import PipelineConfig
    from repro.diffusion.sampler import DDIMConfig

    smoke = getattr(args, "smoke", True)
    cfg = PipelineConfig.smoke() if smoke else PipelineConfig()
    if getattr(args, "model", "unet") == "sdxl":
        from repro.configs import sdxl_base
        cfg = sdxl_base.SMOKE if smoke else sdxl_base.CONFIG
    elif getattr(args, "model", "unet") == "dit":
        # swap the denoiser family; the engine/sampler/serving spine is
        # family-agnostic through the denoiser contract (DESIGN.md §11)
        from repro.diffusion.dit import DiTConfig
        dit = DiTConfig()
        cfg = dataclasses.replace(cfg, unet=dit.smoke() if smoke else dit)
    steps = steps if steps is not None else getattr(args, "steps", 5)
    guidance = (guidance if guidance is not None
                else getattr(args, "guidance", 1.0))
    cfg = dataclasses.replace(
        cfg,
        ddim=DDIMConfig(num_inference_steps=steps,
                        guidance_scale=guidance,
                        tips_active_iters=max(1, steps * 20 // 25)))
    if policies is None:
        policies = policies_from_args(args)
    return policies.apply(cfg)
