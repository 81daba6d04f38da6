"""DiT denoiser: patchify -> N adaLN transformer blocks -> unpatchify.

Second model family behind the denoiser contract
(``repro.diffusion.denoiser``): a diffusion transformer in the DiT-S shape
(Peebles & Xie, 2023) with the cross-attention text conditioning the
serving stack assumes.  The paper's three features are properties of the
transformer blocks, not of the UNet that hosted them — so each DiT block
IS ``unet._transformer_block``, with adaLN timestep conditioning supplied
through its ``modulation`` hook:

  * self-attention routes through the PSSA fused kernel (score pruning +
    patch-XOR bitmap compression, integer counters bit-identical across
    ``reference|fused``);
  * cross-attention emits the CLS attention score TIPS thresholds;
  * the GEGLU FFN runs the DBSC mixed-precision path under the TIPS mask;

all via the UNCHANGED ``kernels.dispatch`` table, which is what makes the
banked ledger, quality tiers, temporal reuse, and continuous batching work
on DiT for free.

Geometry: latents (B, S, S, C) are patchified with stride ``patch`` into a
``(S/patch)``-sided token GRID — kept 2D, (B, g, g, D), because that is
exactly the feature-map shape ``_transformer_block`` and the patch-reuse
kernels operate on.  One token resolution for the whole network, so
``layer_order()`` is ``block{i}@g`` for i in range(depth).

adaLN: per block, ``silu(temb)`` maps through a per-block linear to 9
modulation vectors — (shift, scale, gate) per (self-attn, cross-attn, FFN)
stage.  Weights are randomly initialized like every other projection (this
is an inference-side reproduction; DiT's zero-init of the adaLN output is
a training-time device, and zero gates would switch the attention/FFN
stages out of the eps path entirely).  The final layer applies
(shift, scale) adaLN to the last norm, projects to patch pixels, and
unpatchifies.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from repro.core.precision import PrecisionPolicy
from repro.core.reuse import ReuseCache, ReusePolicy
from repro.diffusion.stats import LayerKey, SlotStats, UNetStats, \
    attn_layer_order
from repro.diffusion.unet import (_lin_p, _norm_p, _transformer_block,
                                  _transformer_p, layer_norm,
                                  timestep_embedding)
from repro.kernels.dispatch import KernelPolicy


@dataclasses.dataclass(frozen=True)
class DiTConfig:
    """DiT-S/2-shaped text-conditioned diffusion transformer."""
    in_channels: int = 4
    out_channels: int = 4
    latent_size: int = 32              # 256x256 images -> 32x32x4 latents
    patch: int = 2                     # patchify stride (DiT-S/2)
    hidden_size: int = 384             # DiT-S width
    depth: int = 12                    # DiT-S depth
    num_heads: int = 6                 # DiT-S heads
    context_dim: int = 768             # CLIP ViT-L/14 text width
    text_len: int = 77
    time_dim: int = 384
    groups: int = 32                   # block entry GroupNorm (gcd'd)
    ffn_mult: int = 4                  # GEGLU hidden = 4 * hidden_size

    # --- paper features (same toggles/policies as UNetConfig) ---
    pssa: bool = True
    tips: bool = True
    dbsc: bool = True
    pssa_threshold: float = 1.0 / 8192.0
    pssa_stats_reference: bool = False
    kernel_policy: KernelPolicy = KernelPolicy()
    precision: PrecisionPolicy = PrecisionPolicy()
    reuse_policy: ReusePolicy = ReusePolicy()

    dtype: str = "float32"

    @property
    def token_res(self) -> int:
        """Side of the (square) token grid: latent_size / patch."""
        assert self.latent_size % self.patch == 0, \
            (self.latent_size, self.patch)
        return self.latent_size // self.patch

    def patch_size(self, resolution: int) -> int:
        """PSXU patch width at a feature-map resolution (same rule as the
        UNet — the PSSA bitmap geometry is a property of the kernel)."""
        return min(64, max(16, resolution))

    def effective_kernel_policy(self) -> KernelPolicy:
        return self.kernel_policy

    def effective_precision(self) -> PrecisionPolicy:
        return self.precision

    def smoke(self) -> "DiTConfig":
        """Reduced config that runs a full fwd pass on CPU in seconds."""
        return dataclasses.replace(
            self,
            latent_size=16,
            hidden_size=64,
            depth=4,
            num_heads=4,
            context_dim=32,
            text_len=8,
            time_dim=64,
            groups=8,
        )

    # --- denoiser-contract hooks (repro.diffusion.denoiser) ---
    def layer_order(self) -> tuple:
        """Canonical stats layer order: ``block{i}`` at the token res."""
        return tuple(LayerKey(f"block{i}", self.token_res)
                     for i in range(self.depth))

    def channels_at(self, resolution: int) -> int:
        """Token width at a feature-map resolution (single-res network)."""
        assert resolution == self.token_res, (resolution, self.token_res)
        return self.hidden_size

    def full_geometry(self) -> "DiTConfig":
        """Full DiT-S — the analytic-ledger extrapolation target."""
        return DiTConfig()

    def attn_resolutions(self) -> tuple:
        return (self.token_res,)


DIT_S2 = DiTConfig()


# ----------------------------------------------------------------------------
# Parameter init
# ----------------------------------------------------------------------------
def init_dit_params(key, cfg: DiTConfig):
    dtype = jnp.dtype(cfg.dtype)
    d = cfg.hidden_size
    pe = cfg.patch * cfg.patch * cfg.in_channels
    po = cfg.patch * cfg.patch * cfg.out_channels
    keys = iter(jax.random.split(key, 8 + 2 * cfg.depth))
    p = {
        "patch_embed": _lin_p(next(keys), pe, d, dtype),
        "time_mlp1": _lin_p(next(keys), d, cfg.time_dim, dtype),
        "time_mlp2": _lin_p(next(keys), cfg.time_dim, cfg.time_dim, dtype),
        "blocks": [
            {"attn": _transformer_p(next(keys), d, cfg, dtype),
             # 9 modulation vectors: (shift, scale, gate) x (sa, ca, ffn)
             "ada": _lin_p(next(keys), cfg.time_dim, 9 * d, dtype)}
            for _ in range(cfg.depth)
        ],
        "final_norm": _norm_p(d, dtype),
        "final_ada": _lin_p(next(keys), cfg.time_dim, 2 * d, dtype),
        "final_out": _lin_p(next(keys), d, po, dtype),
    }
    return p


# ----------------------------------------------------------------------------
# Forward
# ----------------------------------------------------------------------------
def _patchify(latents, patch: int):
    """(B, S, S, C) -> (B, S/p, S/p, p*p*C) token grid."""
    b, s, _, c = latents.shape
    g = s // patch
    x = latents.reshape(b, g, patch, g, patch, c)
    return x.transpose(0, 1, 3, 2, 4, 5).reshape(b, g, g, patch * patch * c)


def _unpatchify(tokens, patch: int, out_channels: int):
    """(B, T, p*p*C) tokens (square T) -> (B, S, S, C)."""
    b, t, _ = tokens.shape
    g = int(round(t ** 0.5))
    x = tokens.reshape(b, g, g, patch, patch, out_channels)
    return x.transpose(0, 1, 3, 2, 4, 5).reshape(
        b, g * patch, g * patch, out_channels)


def dit_forward(params, latents, timesteps, context, cfg: DiTConfig,
                tips_active=True, stats_rows=None, cfg_dup: bool = False,
                row_stats: bool = False, reuse_cache=None, overrides=None):
    """latents (B, S, S, C), timesteps (B,), context (B|2B, Ttext, ctx).

    Same signature and keyword semantics as ``unet.unet_forward`` — the
    denoiser contract (see ``repro.diffusion.denoiser``).  Returns
    ``(eps, stats)`` (+ ``new_cache`` under temporal reuse) with one
    PSSA/TIPS entry per DiT block in ``cfg.layer_order()``.

    ``cfg_dup`` tiles the hidden state at block 0's cross-attention —
    block 0 is the first divergence point under fused CFG, exactly the
    UNet's first attention block (the reuse-cache pre-dup geometry
    matches for the same reason).
    """
    b = latents.shape[0]
    g = cfg.token_res
    tips_active = jnp.asarray(tips_active)
    policy = cfg.effective_kernel_policy()
    precision = cfg.effective_precision()
    reuse_pol = cfg.reuse_policy
    reuse_on = reuse_pol.enabled and reuse_cache is not None
    needs_dup = cfg_dup
    if cfg_dup:
        assert context.shape[0] == 2 * latents.shape[0], \
            (context.shape, latents.shape)

    temb = timestep_embedding(timesteps, cfg.hidden_size)
    temb = jnp.einsum("bd,dc->bc", temb, params["time_mlp1"]["w"]) \
        + params["time_mlp1"]["b"]
    temb = jnp.einsum("bd,dc->bc", jax.nn.silu(temb),
                      params["time_mlp2"]["w"]) + params["time_mlp2"]["b"]

    x = _patchify(latents, cfg.patch)
    h = jnp.einsum("bhwc,cd->bhwd", x, params["patch_embed"]["w"]) \
        + params["patch_embed"]["b"]

    pssa_stats: list = []
    tips_stats: list = []
    reuse_stats: list = []
    new_layer_caches: list = []
    for i, bp in enumerate(params["blocks"]):
        # per-block adaLN from the (possibly not-yet-tiled) time embedding;
        # (B, 1, D) vectors broadcast over tokens, and the block tiles
        # them to [cond | uncond] rows post-dup via its _per_rows rule
        ada = jnp.einsum("bd,dc->bc", jax.nn.silu(temb), bp["ada"]["w"]) \
            + bp["ada"]["b"]
        mod = tuple(m[:, None, :] for m in jnp.split(ada, 9, axis=-1))
        reuse_arg = None
        if reuse_on:
            reuse_arg = (reuse_pol, reuse_cache.layers[i], reuse_cache.valid)
        h, sa, ca, ru = _transformer_block(h, bp["attn"], context, cfg,
                                           tips_active, stats_rows,
                                           dup_after_self=needs_dup,
                                           policy=policy,
                                           precision=precision,
                                           row_stats=row_stats,
                                           reuse=reuse_arg,
                                           overrides=overrides,
                                           modulation=mod)
        if needs_dup:
            temb = jnp.concatenate([temb, temb], axis=0)
            needs_dup = False
        pssa_stats.extend(sa)
        tips_stats.extend(ca)
        if reuse_on:
            new_layer_caches.append(ru[0])
            reuse_stats.append(ru[1])

    if needs_dup:                      # depth == 0: tile eps like the UNet
        h = jnp.concatenate([h, h], axis=0)
        temb = jnp.concatenate([temb, temb], axis=0)

    bb = h.shape[0]                    # 2B under cfg_dup
    tokens = h.reshape(bb, g * g, cfg.hidden_size)
    ada = jnp.einsum("bd,dc->bc", jax.nn.silu(temb),
                     params["final_ada"]["w"]) + params["final_ada"]["b"]
    shift, scale = jnp.split(ada, 2, axis=-1)
    hn = layer_norm(tokens, params["final_norm"]["scale"],
                    params["final_norm"]["bias"])
    hn = hn * (1.0 + scale[:, None, :]) + shift[:, None, :]
    out = jnp.einsum("btd,dc->btc", hn, params["final_out"]["w"]) \
        + params["final_out"]["b"]
    eps = _unpatchify(out, cfg.patch, cfg.out_channels)

    stats_cls = SlotStats if row_stats else UNetStats
    stats = stats_cls.from_layer_list(attn_layer_order(cfg), pssa_stats,
                                      tips_stats,
                                      reuse=tuple(reuse_stats))
    if reuse_on:
        new_cache = ReuseCache(valid=jnp.ones_like(reuse_cache.valid),
                               layers=tuple(new_layer_caches))
        return eps, stats, new_cache
    return eps, stats


def abstract_dit_params(cfg: DiTConfig):
    return jax.eval_shape(lambda: init_dit_params(jax.random.PRNGKey(0),
                                                  cfg))


# --- denoiser-contract registration (repro.diffusion.denoiser) ---
from repro.diffusion import denoiser as _denoiser  # noqa: E402

_denoiser.register_family(_denoiser.FamilySpec(
    family="dit",
    config_cls=DiTConfig,
    init_params=init_dit_params,
    forward=dit_forward,
    abstract_params=abstract_dit_params,
))
