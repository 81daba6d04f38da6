"""Denoiser contract: the model-agnostic interface the runtime serves.

The engine, sampler, stats pytrees, energy reports, and serving CLIs were
grown around one network — the BK-SDM-Tiny UNet.  None of the runtime
machinery actually *needs* a UNet: the paper's three features (PSSA on
self-attention, TIPS text-conditioned precision, the DBSC FFN datapath)
are properties of the transformer blocks, and everything downstream of the
forward pass consumes only

  * an eps prediction shaped like the latents,
  * a stats pytree whose STATIC layer order is derived from the config
    (``cfg.layer_order()``), and
  * an optional per-layer reuse cache in that same order.

``Denoiser`` freezes that interface.  It is a frozen/hashable dataclass —
``(family, cfg)`` — so it can sit inside jit-cache keys exactly like the
policy objects do, and the registry maps each frozen config class to its
family implementation.  ``repro.diffusion.unet`` (the original network)
and ``repro.diffusion.dit`` (patchify -> N adaLN-zero transformer blocks
-> unpatchify) each register themselves on import; ``make_denoiser(cfg)``
resolves lazily so this module stays import-cycle-free.

Contract (see DESIGN.md §11 for the full statement):

``init_params(key)``
    Fresh parameter pytree for ``cfg``.

``apply(params, latents, timesteps, context, **kw)``
    Pure forward.  ``latents`` (B, S, S, C), ``timesteps`` (B,),
    ``context`` the encoded text as ``text_encoder.encode_text`` gives
    it: a (B or 2B, T_text, ctx_dim) array, or a pytree of such leaves
    where the family reads more than the text states (SDXL's "text_time"
    UNet takes (states, pooled text)); the runtime fuses, slices and
    stores it leaf by leaf.  Keywords — all optional, all with
    UNet-identical semantics:

    - ``tips_active``: scalar or (B,) per-row TIPS activity;
    - ``stats_rows`` (static): restrict stats to the first N rows;
    - ``cfg_dup`` (static): shared-prefix CFG dedup — latents carry the
      cond half only, context carries [cond | uncond]; the hidden state
      is tiled to 2B rows before the first operation that reads the
      text (the first cross-attention, or the first resnet where the
      time embedding holds pooled text) and ``eps`` comes back with 2B
      rows (split by ``sampler.guided_eps``);
    - ``row_stats`` (static): per-row integer counters (``SlotStats``)
      instead of folded stats;
    - ``reuse_cache``: a ``core.reuse.ReuseCache`` with one
      ``LayerReuseCache`` per entry of ``layer_order()``; when given and
      ``cfg.reuse_policy.enabled``, the return gains a third element (the
      new cache);
    - ``overrides``: per-row phase threshold scales
      (``solvers.PhaseOverrides``) or None.

    Returns ``(eps, stats)`` or ``(eps, stats, new_cache)``.

``layer_order()``
    The static ``stats.LayerKey`` tuple — the canonical leaf order of
    every stats pytree, ``LedgerAccum`` column order, and reuse-cache
    layer order.  Must depend only on the (hashable) config.

Config hooks the runtime may call on ANY registered config (duck-typed,
with UNet-formula fallbacks for plain configs):

    ``cfg.layer_order()``       -> tuple[LayerKey, ...]
    ``cfg.channels_at(res)``    -> token width at a feature-map resolution
    ``cfg.full_geometry()``     -> the full-size config of the same family
                                   (analytic-ledger extrapolation target)
    ``cfg.attn_resolutions()``  -> distinct attention resolutions, sorted
                                   descending (measured-ratio remap keys)
"""
from __future__ import annotations

import dataclasses
from typing import Callable, NamedTuple


class FamilySpec(NamedTuple):
    """One registered denoiser family (resolved by frozen config class)."""
    family: str
    config_cls: type
    init_params: Callable       # (key, cfg) -> params pytree
    forward: Callable           # (params, lat, t, ctx, cfg, **kw) -> tuple
    abstract_params: Callable   # (cfg) -> jax.eval_shape pytree


_REGISTRY: dict = {}            # family name -> FamilySpec
_BY_CONFIG: dict = {}           # config class -> FamilySpec

#: CLI vocabulary: ``--model`` flag values, in presentation order.
FAMILIES = ("unet", "dit")


def register_family(spec: FamilySpec) -> None:
    """Called at import time by each family module (unet.py, dit.py)."""
    _REGISTRY[spec.family] = spec
    _BY_CONFIG[spec.config_cls] = spec


def _ensure_registered() -> None:
    # Lazy: importing the family modules here (not at module top) keeps
    # denoiser.py importable from stats/engine/sampler without cycles.
    import repro.diffusion.unet    # noqa: F401  (registers "unet")
    import repro.diffusion.dit     # noqa: F401  (registers "dit")


def family_of(cfg) -> str:
    """The family name a (frozen) denoiser config belongs to."""
    _ensure_registered()
    spec = _BY_CONFIG.get(type(cfg))
    if spec is None:
        known = sorted(c.__name__ for c in _BY_CONFIG)
        raise TypeError(f"no denoiser family registered for "
                        f"{type(cfg).__name__}; known configs: {known}")
    return spec.family


@dataclasses.dataclass(frozen=True)
class Denoiser:
    """Frozen, hashable handle pairing a family with its config.

    Everything the runtime needs from a model flows through this object;
    ``engine.DiffusionEngine`` and ``pipeline.StableDiffusionPipeline``
    hold one instead of importing ``unet_forward`` directly.
    """
    family: str
    cfg: object                  # a frozen config dataclass (hashable)

    def _spec(self) -> FamilySpec:
        _ensure_registered()
        return _REGISTRY[self.family]

    def init_params(self, key):
        return self._spec().init_params(key, self.cfg)

    def apply(self, params, latents, timesteps, context, **kw):
        return self._spec().forward(params, latents, timesteps, context,
                                    self.cfg, **kw)

    def layer_order(self):
        from repro.diffusion.stats import attn_layer_order
        return attn_layer_order(self.cfg)

    def abstract_params(self):
        return self._spec().abstract_params(self.cfg)


def make_denoiser(cfg) -> Denoiser:
    """Resolve a config to its registered family's ``Denoiser``."""
    return Denoiser(family=family_of(cfg), cfg=cfg)
