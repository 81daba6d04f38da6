"""BK-SDM-style UNet (paper's workload) with PSSA / TIPS / DBSC folded in.

Architecturally compressed Stable-Diffusion UNet following BK-SDM (Kim et
al., 2023 — the paper's evaluation network): SD-v1 block layout with one
resnet + one transformer stack per down stage, two per up stage, and the
mid-block removed.  The default geometry is BK-SDM-Small's (482.3M
parameters); BK-SDM-Tiny (0.32B) also drops the innermost stage pair.  The
same module serves SDXL base (``configs.sdxl_base``): a transformer depth
and head count per stage, a mid block, and the "text_time" added
conditioning.  Each block is

    CNN stage          — two 3x3 convs (resnet, GroupNorm + SiLU, time-embed
                         FiLM add), input-stationary on the DBSC;
    transformer stage  — GroupNorm + proj_in, ``transformer_depth`` basic
                         blocks, proj_out + residual (SD's Transformer2DModel
                         with a linear projection).  A basic block is
                         self-attention (pixel-wise; PSSA prunes + compresses
                         the score matrix on its way to DRAM), cross-attention
                         over the text keys (emits the CLS attention score
                         that TIPS thresholds), and a GEGLU FFN whose rows run
                         INT12/INT6 mixed-precision per the TIPS mask.

The module is pure JAX and runs at reduced size on CPU (tests/examples); the
full BK-SDM geometry is exercised analytically by ``diffusion.ledger``
(bytes/MACs) and by shape-level ``jax.eval_shape`` checks — matching how the
paper itself evaluates (energy / EMA / throughput, not accuracy).

Forward returns ``(eps, stats)`` where ``stats`` is a ``UNetStats`` pytree
(fixed config-derived layer order — see ``repro.diffusion.stats``) carrying
per-layer PSSA compression statistics and per-cross-attn TIPS ratios for the
energy ledger.  Being a registered pytree with static layer keys, it flows
through ``jax.lax.scan``/``jax.jit`` unchanged — the property the jitted
``DiffusionEngine`` builds on.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Optional

import jax
import jax.numpy as jnp

from repro.core.precision import PrecisionPolicy
from repro.core.reuse import (LayerReuseCache, ReuseCache, ReusePolicy,
                              ReuseRowCounters, window_patch_mask)
from repro.diffusion.stats import (SlotStats, UNetStats, attn_layer_order,
                                   _unet_attn_layer_order)
from repro.kernels import dispatch
from repro.kernels.dispatch import KernelPolicy
from repro.kernels.patch_reuse import ops as reuse_ops


@dataclasses.dataclass(frozen=True)
class UNetConfig:
    # the defaults are BK-SDM-Small's geometry (482.3M parameters), which
    # the program has long called BK-SDM-Tiny; Tiny (0.32B) also drops the
    # innermost 1280-channel stage and its up stage
    in_channels: int = 4
    out_channels: int = 4
    block_channels: tuple = (320, 640, 1280, 1280)
    down_attn: tuple = (True, True, True, False)
    resnets_per_down: int = 1          # BK-SDM-Small/Tiny: 1 (base SD: 2)
    resnets_per_up: int = 2            # BK-SDM-Small/Tiny: 2 (base SD: 3)
    has_mid_block: bool = False        # removed in BK-SDM-Small/Tiny
    # basic blocks per transformer stack and attention heads: one int for
    # every stage, or a tuple with one entry per stage (SDXL: depth
    # (1, 2, 10), heads (5, 10, 20)); the mid block takes the last stage's
    transformer_depth: int | tuple = 1
    num_heads: int | tuple = 8
    context_dim: int = 768             # CLIP ViT-L/14 text width
    text_len: int = 77
    time_dim: int = 1280
    latent_size: int = 64              # 512x512 images -> 64x64x4 latents
    groups: int = 32
    ffn_mult: int = 4                  # GEGLU hidden = 4 * channels
    # SDXL's added conditioning: "text_time" takes the context as
    # (text states, pooled text) and adds MLP([pooled, sinusoid of each of
    # the six size/crop ids]) to the time embedding; "none" reads only
    # the text states
    addition_embed: str = "none"       # "none" | "text_time"
    addition_time_dim: int = 256
    pooled_dim: int = 1280
    # original (h, w), crop (top, left), target (h, w)
    addition_time_ids: tuple = (1024, 1024, 0, 0, 1024, 1024)

    # --- paper features ---
    pssa: bool = True
    tips: bool = True
    dbsc: bool = True
    # legacy FFN-only kernel toggle; folded into kernel_policy (ffn="dbsc")
    # by effective_kernel_policy() — prefer setting kernel_policy directly
    use_dbsc_kernel: bool = False
    pssa_threshold: float = 1.0 / 8192.0
    # legacy fixed CAS threshold; folded into `precision` by
    # effective_precision() — prefer setting the policy directly
    tips_threshold: float = 0.05
    # route PSSA accounting through the seed's materializing reference
    # implementation (benchmark baseline / oracle; see core.pssa)
    pssa_stats_reference: bool = False
    # per-op kernel routing (repro.kernels.dispatch): which implementation
    # self-attention / cross-attention / FFN / bitmap use, interpret
    # auto-selection, blocks
    kernel_policy: KernelPolicy = KernelPolicy()
    # TIPS/DBSC precision runtime (repro.core.precision): spotting mode,
    # thresholds, second-matmul coverage — the single source of precision
    # truth the engine keys its executable cache on
    precision: PrecisionPolicy = PrecisionPolicy()
    # temporal patch reuse (repro.core.reuse): SIGE-style gather/scatter of
    # changed patches over cached previous-step activations; takes effect
    # when a ReuseCache is threaded into unet_forward
    reuse_policy: ReusePolicy = ReusePolicy()

    dtype: str = "float32"

    def __post_init__(self):
        for name in ("transformer_depth", "num_heads"):
            v = getattr(self, name)
            if not isinstance(v, int) and len(v) != len(self.block_channels):
                raise ValueError(f"UNetConfig.{name}={v!r}: one entry per "
                                 f"stage of {self.block_channels}")
        if self.addition_embed not in ("none", "text_time"):
            raise ValueError(f"UNetConfig.addition_embed="
                             f"{self.addition_embed!r}: 'none' or "
                             f"'text_time'")
        # the legacy fold-in knobs are deprecated aliases of the policy
        # objects (DESIGN.md §13): warn at the spelling site — the
        # construction that sets a non-default value — not in the
        # effective_* reads, which internal code calls on every trace.
        # Function-local import: core.policies imports diffusion.solvers,
        # and this module loads first in the package __init__.
        legacy_default = next(f.default for f in dataclasses.fields(self)
                              if f.name == "tips_threshold")
        if self.use_dbsc_kernel:
            from repro.core.policies import legacy_warning
            legacy_warning(
                "UNetConfig.use_dbsc_kernel is a deprecated alias — set "
                "kernel_policy=KernelPolicy(ffn='dbsc') (or "
                "ServePolicies(kernels=...)); the cache key and ledger "
                "are identical either way")
        if self.tips_threshold != legacy_default:
            from repro.core.policies import legacy_warning
            legacy_warning(
                "UNetConfig.tips_threshold is a deprecated alias — set "
                "precision=PrecisionPolicy(threshold=...) (or "
                "ServePolicies(precision=...)); the cache key and ledger "
                "are identical either way")

    def stage_depth(self, stage: int) -> int:
        """Basic blocks in stage ``stage``'s transformer stacks."""
        d = self.transformer_depth
        return d if isinstance(d, int) else d[stage]

    def stage_heads(self, stage: int) -> int:
        """Attention heads of stage ``stage``'s transformer blocks."""
        h = self.num_heads
        return h if isinstance(h, int) else h[stage]

    def heads_at(self, resolution: int) -> int:
        """Attention heads of the blocks at a feature-map resolution."""
        return self.stage_heads(
            (self.latent_size // resolution).bit_length() - 1)

    def patch_size(self, resolution: int) -> int:
        """PSXU patch width at a given feature-map resolution (16/32/64)."""
        return min(64, max(16, resolution))

    def effective_kernel_policy(self) -> KernelPolicy:
        """``kernel_policy`` with the legacy ``use_dbsc_kernel`` folded in."""
        pol = self.kernel_policy
        if self.use_dbsc_kernel and pol.ffn == "reference":
            pol = dataclasses.replace(pol, ffn="dbsc")
        return pol

    def effective_precision(self) -> PrecisionPolicy:
        """``precision`` with the legacy ``tips_threshold`` folded in.

        A non-default ``tips_threshold`` on an otherwise-default
        fixed-spotting policy overrides the policy threshold (mirrors the
        ``use_dbsc_kernel`` fold); an explicitly-configured policy wins.
        """
        pol = self.precision
        legacy_default = next(f.default for f in dataclasses.fields(self)
                              if f.name == "tips_threshold")
        if (self.tips_threshold != legacy_default
                and pol.spotting == "fixed"
                and pol.threshold == PrecisionPolicy().threshold):
            pol = dataclasses.replace(pol, threshold=self.tips_threshold)
        return pol

    def smoke(self) -> "UNetConfig":
        """Reduced config that runs a full fwd pass on CPU in seconds."""
        return dataclasses.replace(
            self,
            block_channels=(32, 64, 64, 64),
            num_heads=4,
            context_dim=32,
            text_len=8,
            time_dim=64,
            latent_size=16,
            groups=8,
        )

    # --- denoiser-contract hooks (repro.diffusion.denoiser) ---
    def layer_order(self) -> tuple:
        """Canonical stats layer order for this config (contract hook)."""
        return _unet_attn_layer_order(self)

    def channels_at(self, resolution: int) -> int:
        """Token width at a feature-map resolution (contract hook)."""
        stage = (self.latent_size // resolution).bit_length() - 1
        return self.block_channels[stage]

    def full_geometry(self) -> "UNetConfig":
        """Full-size config of this family — the analytic-ledger
        extrapolation target (contract hook)."""
        return UNetConfig()

    def attn_resolutions(self) -> tuple:
        """Distinct attention resolutions, sorted descending (contract
        hook; measured-ratio remap keys for the energy ledger)."""
        return tuple(sorted({self.latent_size >> s
                             for s, a in enumerate(self.down_attn) if a},
                            reverse=True))

    @property
    def num_down_attn_layers(self) -> int:
        return sum(self.resnets_per_down * self.stage_depth(i)
                   for i, a in enumerate(self.down_attn) if a)


BK_SDM_TINY = UNetConfig()           # BK-SDM-Small's geometry (see above)


# ----------------------------------------------------------------------------
# Primitive layers
# ----------------------------------------------------------------------------
def conv2d(x, w, b=None, stride: int = 1, padding: int = 1):
    """NHWC conv with HWIO weights (cast to ``x``'s type: weights may be
    stored narrower than the activations)."""
    y = jax.lax.conv_general_dilated(
        x, w.astype(x.dtype), window_strides=(stride, stride),
        padding=((padding, padding), (padding, padding)),
        dimension_numbers=("NHWC", "HWIO", "NHWC"))
    if b is not None:
        y = y + b
    return y


def group_norm(x, scale, bias, groups: int, eps: float = 1e-5):
    n, h, w, c = x.shape
    g = math.gcd(groups, c)
    xg = x.reshape(n, h, w, g, c // g).astype(jnp.float32)
    mean = jnp.mean(xg, axis=(1, 2, 4), keepdims=True)
    var = jnp.var(xg, axis=(1, 2, 4), keepdims=True)
    xg = (xg - mean) * jax.lax.rsqrt(var + eps)
    return (xg.reshape(n, h, w, c) * scale + bias).astype(x.dtype)


def layer_norm(x, scale, bias, eps: float = 1e-5):
    mean = jnp.mean(x.astype(jnp.float32), axis=-1, keepdims=True)
    var = jnp.var(x.astype(jnp.float32), axis=-1, keepdims=True)
    y = (x.astype(jnp.float32) - mean) * jax.lax.rsqrt(var + eps)
    return (y * scale + bias).astype(x.dtype)


def timestep_embedding(t, dim: int, max_period: float = 10000.0):
    """Sinusoidal (B,) int timesteps -> (B, dim)."""
    half = dim // 2
    freqs = jnp.exp(-math.log(max_period)
                    * jnp.arange(half, dtype=jnp.float32) / half)
    ang = t.astype(jnp.float32)[:, None] * freqs[None]
    return jnp.concatenate([jnp.cos(ang), jnp.sin(ang)], axis=-1)


# ----------------------------------------------------------------------------
# Parameter init
# ----------------------------------------------------------------------------
def _conv_p(key, kh, kw, cin, cout, dtype):
    s = 1.0 / math.sqrt(kh * kw * cin)
    k1, k2 = jax.random.split(key)
    return {"w": (jax.random.uniform(k1, (kh, kw, cin, cout), jnp.float32,
                                     -s, s)).astype(dtype),
            "b": jnp.zeros((cout,), dtype)}


def _lin_p(key, cin, cout, dtype, bias=True):
    s = 1.0 / math.sqrt(cin)
    p = {"w": (jax.random.uniform(key, (cin, cout), jnp.float32,
                                  -s, s)).astype(dtype)}
    if bias:
        p["b"] = jnp.zeros((cout,), dtype)
    return p


def _norm_p(c, dtype):
    return {"scale": jnp.ones((c,), dtype), "bias": jnp.zeros((c,), dtype)}


def _resnet_p(key, cin, cout, tdim, dtype):
    ks = jax.random.split(key, 4)
    p = {
        "norm1": _norm_p(cin, dtype),
        "conv1": _conv_p(ks[0], 3, 3, cin, cout, dtype),
        "time": _lin_p(ks[1], tdim, cout, dtype),
        "norm2": _norm_p(cout, dtype),
        "conv2": _conv_p(ks[2], 3, 3, cout, cout, dtype),
    }
    if cin != cout:
        p["skip"] = _conv_p(ks[3], 1, 1, cin, cout, dtype)
    return p


def _basic_block_p(ks, c, cfg: UNetConfig, dtype):
    """One basic block (self-attn, cross-attn, GEGLU FFN) from 10 keys."""
    dff = cfg.ffn_mult * c
    return {
        "ln1": _norm_p(c, dtype),
        "sa_q": _lin_p(ks[0], c, c, dtype, bias=False),
        "sa_k": _lin_p(ks[1], c, c, dtype, bias=False),
        "sa_v": _lin_p(ks[2], c, c, dtype, bias=False),
        "sa_o": _lin_p(ks[3], c, c, dtype),
        "ln2": _norm_p(c, dtype),
        "ca_q": _lin_p(ks[4], c, c, dtype, bias=False),
        "ca_k": _lin_p(ks[5], cfg.context_dim, c, dtype, bias=False),
        "ca_v": _lin_p(ks[6], cfg.context_dim, c, dtype, bias=False),
        "ca_o": _lin_p(ks[7], c, c, dtype),
        "ln3": _norm_p(c, dtype),
        "ff_geglu": _lin_p(ks[8], c, 2 * dff, dtype),
        "ff_out": _lin_p(ks[9], dff, c, dtype),
    }


def _transformer_p(key, c, cfg: UNetConfig, dtype, depth: int = 1):
    """A transformer stack: GroupNorm, proj_in, ``depth`` basic blocks,
    proj_out.  A depth-1 stack holds its block's weights beside its own;
    a deeper one lists its blocks under ``blocks``."""
    ks = jax.random.split(key, 12)
    p = {"norm_in": _norm_p(c, dtype),
         "proj_in": _lin_p(ks[0], c, c, dtype),
         "proj_out": _lin_p(ks[11], c, c, dtype)}
    if depth == 1:
        return {**p, **_basic_block_p(ks[1:11], c, cfg, dtype)}
    p["blocks"] = [_basic_block_p(jax.random.split(k, 10), c, cfg, dtype)
                   for k in jax.random.split(ks[1], depth)]
    return p


def init_unet_params(key, cfg: UNetConfig):
    dtype = jnp.dtype(cfg.dtype)
    chans = cfg.block_channels
    keys = iter(jax.random.split(key, 256))
    p = {
        "time_mlp1": _lin_p(next(keys), chans[0], cfg.time_dim, dtype),
        "time_mlp2": _lin_p(next(keys), cfg.time_dim, cfg.time_dim, dtype),
        "conv_in": _conv_p(next(keys), 3, 3, cfg.in_channels, chans[0], dtype),
    }
    if cfg.addition_embed == "text_time":
        add_in = cfg.pooled_dim + (len(cfg.addition_time_ids)
                                   * cfg.addition_time_dim)
        p["add_mlp1"] = _lin_p(next(keys), add_in, cfg.time_dim, dtype)
        p["add_mlp2"] = _lin_p(next(keys), cfg.time_dim, cfg.time_dim, dtype)
    # --- down path (track the skip-channel stack exactly as forward pushes) ---
    down = []
    skip_channels = [chans[0]]          # conv_in output
    cin = chans[0]
    for i, cout in enumerate(chans):
        stage = {"resnets": [], "attns": []}
        for _ in range(cfg.resnets_per_down):
            stage["resnets"].append(
                _resnet_p(next(keys), cin, cout, cfg.time_dim, dtype))
            if cfg.down_attn[i]:
                stage["attns"].append(_transformer_p(
                    next(keys), cout, cfg, dtype, cfg.stage_depth(i)))
            cin = cout
            skip_channels.append(cout)
        if i < len(chans) - 1:
            stage["down"] = _conv_p(next(keys), 3, 3, cout, cout, dtype)
            skip_channels.append(cout)
        down.append(stage)
    p["down"] = down

    if cfg.has_mid_block:
        c = chans[-1]
        p["mid"] = {
            "res1": _resnet_p(next(keys), c, c, cfg.time_dim, dtype),
            "attn": _transformer_p(next(keys), c, cfg, dtype,
                                   cfg.stage_depth(len(chans) - 1)),
            "res2": _resnet_p(next(keys), c, c, cfg.time_dim, dtype),
        }

    # --- up path (pops the skip stack in reverse; widths vary across
    #     stage boundaries, so cin comes from the tracked stack) ---
    up = []
    rev = list(reversed(range(len(chans))))
    cin = chans[-1]
    for j, i in enumerate(rev):
        cout = chans[i]
        stage = {"resnets": [], "attns": []}
        for r in range(cfg.resnets_per_up):
            skip_c = skip_channels.pop()
            stage["resnets"].append(_resnet_p(
                next(keys), cin + skip_c, cout, cfg.time_dim, dtype))
            if cfg.down_attn[i]:
                stage["attns"].append(_transformer_p(
                    next(keys), cout, cfg, dtype, cfg.stage_depth(i)))
            cin = cout
        if j < len(chans) - 1:
            stage["up"] = _conv_p(next(keys), 3, 3, cout, cout, dtype)
        up.append(stage)
    assert not skip_channels, f"unbalanced skips: {skip_channels}"
    p["up"] = up

    p["norm_out"] = _norm_p(chans[0], dtype)
    p["conv_out"] = _conv_p(next(keys), 3, 3, chans[0], cfg.out_channels,
                            dtype)
    return p


# ----------------------------------------------------------------------------
# Blocks
# ----------------------------------------------------------------------------
def _resnet(x, p, temb, groups):
    h = group_norm(x, p["norm1"]["scale"], p["norm1"]["bias"], groups)
    h = conv2d(jax.nn.silu(h), p["conv1"]["w"], p["conv1"]["b"])
    t = jnp.einsum("bd,dc->bc", jax.nn.silu(temb), p["time"]["w"]) \
        + p["time"]["b"]
    h = h + t[:, None, None, :]
    h = group_norm(h, p["norm2"]["scale"], p["norm2"]["bias"], groups)
    h = conv2d(jax.nn.silu(h), p["conv2"]["w"], p["conv2"]["b"])
    skip = x if "skip" not in p else conv2d(x, p["skip"]["w"], p["skip"]["b"],
                                            padding=0)
    return skip + h


def _attn_heads(x, w, heads):
    b, t, _ = x.shape
    y = jnp.einsum("btc,cd->btd", x, w)
    return y.reshape(b, t, heads, -1).transpose(0, 2, 1, 3)


def _merge_heads(x):
    b, h, t, d = x.shape
    return x.transpose(0, 2, 1, 3).reshape(b, t, h * d)


def _transformer_block(x2d, p, context, cfg: UNetConfig, tips_active,
                       stats_rows=None, dup_after_self: bool = False,
                       policy: KernelPolicy | None = None,
                       precision: PrecisionPolicy | None = None,
                       row_stats: bool = False, reuse=None,
                       overrides=None, modulation=None, heads=None):
    """One transformer stack, x2d: (B, H, W, C) ->
    (out, (PSSAStats, ...), (TIPSResult, ...), reuse_out).

    The stack is GroupNorm + ``proj_in``, its basic blocks (one for the
    flat depth-1 layout, else ``p["blocks"]``), ``proj_out`` and the
    residual; the stats tuples hold one entry per basic block.  ``heads``
    is the blocks' head count (None: ``cfg.num_heads``).

    ``tips_active`` is a scalar flag (whole-batch schedule) or a (B,) row
    vector — continuous batching runs slots at heterogeneous denoising
    iterations, so each row carries its own activity bit.  ``row_stats``
    reports per-row integer counters instead of folded stats (the slot
    runtime scatters them into per-iteration ledger buckets).

    ``policy`` selects the per-op kernel implementation (reference vs
    Pallas) via ``repro.kernels.dispatch``; ``precision`` the TIPS
    spotting mode / FFN coverage; None falls back to the config's
    effective policies.

    ``stats_rows`` (static) restricts the returned stats to the first N
    batch rows — the cond half under a fused-CFG batch.

    ``dup_after_self``: CFG prefix deduplication.  Under fused CFG, the
    cond and uncond halves are IDENTICAL until the first cross-attention
    (only the text context differs), so the fused path runs everything up
    to and including this stack's first self-attention on the cond half
    alone and tiles the hidden state to both halves there — exact, and it
    halves the most expensive self-attention in the network (the first
    block sits at the highest resolution).  ``x2d`` then has half as many
    rows as ``context``.

    ``reuse``: ``None`` (dense path, ``reuse_out`` is None) or a
    ``(ReusePolicy, LayerReuseCache, valid)`` triple.  With it, the block
    thresholds the per-patch delta of its token input against the cached
    reference, gathers only active patch rows into the self-attention
    queries / cross-attention queries / FFN rows (K/V, norms, and
    projections stay dense), and scatters the stage outputs over the
    cached activations; ``reuse_out`` is then
    ``(new LayerReuseCache, ReuseRowCounters)``.  At threshold 0 (or an
    invalid cache row) every patch is active, the plan is the identity,
    and the block is bit-identical to the dense path (DESIGN.md §9).

    ``overrides`` (a ``solvers.PhaseOverrides`` or None) carries per-row
    phase-scheduled threshold SCALES ((B,) request-row arrays, tiled to
    [cond | uncond] where the hidden state was); each lane is None when
    the sampler bank never schedules it, which keeps the unscheduled
    trace — and its kernel routing — exactly the legacy one.

    ``modulation``: adaLN-zero timestep conditioning (the DiT family).
    ``None`` — what every UNet call passes — leaves the trace exactly as
    before.  Otherwise a 9-tuple of (B, 1, C)-broadcastable arrays,
    ``(shift, scale, gate)`` per stage in (self-attn, cross-attn, FFN)
    order: after each stage's ``layer_norm`` the hidden state becomes
    ``hn * (1 + scale) + shift``, and the stage's projection is
    multiplied by ``gate`` before the residual add (and before any reuse
    scatter, so the cache holds gated activations like it holds projected
    ones).  Arrays carry request rows and are tiled to [cond | uncond]
    by the same ``_per_rows`` rule as the override lanes.
    """
    b, hgt, wid, c = x2d.shape
    res = hgt  # feature-map resolution
    if heads is None:
        heads = cfg.num_heads
    blocks = p["blocks"] if "blocks" in p else (p,)
    if policy is None:
        policy = cfg.effective_kernel_policy()
    if precision is None:
        precision = cfg.effective_precision()

    def _per_rows(vec, nrows):
        # override lanes are per REQUEST row; tile to [cond | uncond]
        # rows where the hidden state was tiled (same precedent as
        # tips_active / valid below)
        if vec is not None and vec.shape[0] != nrows:
            vec = jnp.concatenate([vec, vec], axis=0)
        return vec

    rows = gate_rows = cache = None
    if reuse is not None:
        rp, cache, valid = reuse
        tokens_in = x2d.reshape(b, hgt * wid, c)
        patch_r = cfg.patch_size(res)
        if rp.apriori_window is not None:
            # the edit region is known up front: patch activity is a
            # compile-time constant — the patch-delta kernel is skipped
            # entirely (the win of an a-priori reuse plan)
            mask = window_patch_mask(rp.apriori_window, res, patch_r,
                                     cfg.latent_size)
            changed = jnp.broadcast_to(jnp.asarray(mask, bool)[None, :],
                                       (b, len(mask)))
        else:
            reuse_scale = (None if overrides is None
                           else _per_rows(overrides.reuse_scale, b))
            if reuse_scale is not None:
                # per-row thresholds: compute the raw per-patch delta
                # (threshold 0 — same values regardless) and compare at
                # the call site
                delta, _ = dispatch.patch_delta(policy, tokens_in,
                                                cache.ref, patch=patch_r,
                                                threshold=0.0)
                changed = delta >= (rp.threshold
                                    * reuse_scale)[:, None]
            else:
                _, changed = dispatch.patch_delta(policy, tokens_in,
                                                  cache.ref,
                                                  patch=patch_r,
                                                  threshold=rp.threshold)
        vrow = valid
        if vrow.shape[0] != b:
            # post-dup layers carry [cond | uncond] rows; validity is per
            # request row, so tile it like the hidden state was
            vrow = jnp.concatenate([vrow, vrow], axis=0)
        act = jnp.logical_or(changed, jnp.logical_not(vrow)[:, None])
        npatch = tokens_in.shape[1] // patch_r
        order, gate = reuse_ops.reuse_plan(act, rp.cap_patches(npatch))
        rows = reuse_ops.plan_token_rows(order, patch_r)
        gate_rows = jnp.repeat(gate, patch_r, axis=1)
        sr = b if stats_rows is None else stats_rows
        counters = ReuseRowCounters(
            computed=jnp.sum(gate.astype(jnp.int32), axis=1)[:sr],
            total=jnp.full((b,), npatch, jnp.int32)[:sr])

    h = group_norm(x2d, p["norm_in"]["scale"], p["norm_in"]["bias"],
                   cfg.groups)
    h = h.reshape(b, hgt * wid, c)
    h = jnp.einsum("btc,cd->btd", h, p["proj_in"]["w"]) + p["proj_in"]["b"]
    sa_stats, tips_results = [], []
    for n, bp in enumerate(blocks):
        dup_here = dup_after_self and n == 0
        resid = h

        # --- self-attention (PSSA) ---
        hn = layer_norm(h, bp["ln1"]["scale"], bp["ln1"]["bias"])
        if modulation is not None:
            hn = hn * (1.0 + _per_rows(modulation[1], hn.shape[0])) \
                + _per_rows(modulation[0], hn.shape[0])
        # reuse: queries gathered to the active patch rows, K/V stay
        # dense — every gathered query still attends over the full tokens
        hn_q = hn if reuse is None else reuse_ops.gather_rows(hn, rows)
        q = _attn_heads(hn_q, bp["sa_q"]["w"], heads)
        k = _attn_heads(hn, bp["sa_k"]["w"], heads)
        v = _attn_heads(hn, bp["sa_v"]["w"], heads)
        patch = cfg.patch_size(res)
        sa_threshold = cfg.pssa_threshold
        if overrides is not None and overrides.pssa_scale is not None:
            # self-attention runs on the cond half pre-dup (b == request
            # rows) and on [cond | uncond] post-dup — tile to match
            sa_threshold = cfg.pssa_threshold * _per_rows(
                overrides.pssa_scale, q.shape[0])
        sa = dispatch.self_attention(policy, q, k, v, patch=patch,
                                     threshold=sa_threshold,
                                     prune_scores=cfg.pssa,
                                     stats_rows=None if dup_here
                                     else stats_rows,
                                     reference_stats=(
                                         cfg.pssa_stats_reference),
                                     row_stats=row_stats)
        sa_proj = jnp.einsum("btd,dc->btc", _merge_heads(sa.out),
                             bp["sa_o"]["w"]) + bp["sa_o"]["b"]
        if modulation is not None:
            sa_proj = sa_proj * _per_rows(modulation[2], sa_proj.shape[0])
        if reuse is not None:
            sa_proj = reuse_ops.scatter_rows(cache.sa, rows, sa_proj,
                                             gate_rows)
        sa_full = sa_proj
        h = resid + sa_proj

        if dup_here:
            # tile [cond] -> [cond | uncond]; divergence starts at the
            # cross-attention
            h = jnp.concatenate([h, h], axis=0)
            x2d = jnp.concatenate([x2d, x2d], axis=0)
            b = x2d.shape[0]
            if reuse is not None:
                # the plan was computed on the cond half; both halves
                # share it
                rows = jnp.concatenate([rows, rows], axis=0)
                gate_rows = jnp.concatenate([gate_rows, gate_rows], axis=0)

        # --- cross-attention (TIPS CAS source) ---
        resid = h
        hn = layer_norm(h, bp["ln2"]["scale"], bp["ln2"]["bias"])
        if modulation is not None:
            hn = hn * (1.0 + _per_rows(modulation[4], hn.shape[0])) \
                + _per_rows(modulation[3], hn.shape[0])
        hn_q = hn if reuse is None else reuse_ops.gather_rows(hn, rows)
        q = _attn_heads(hn_q, bp["ca_q"]["w"], heads)
        kt = _attn_heads(context, bp["ca_k"]["w"], heads)
        vt = _attn_heads(context, bp["ca_v"]["w"], heads)
        tips_scale = (None if overrides is None
                      else _per_rows(overrides.tips_scale, h.shape[0]))
        ca = dispatch.cross_attention(policy, q, kt, vt,
                                      precision=precision,
                                      stats_rows=stats_rows,
                                      row_stats=row_stats,
                                      threshold_scale=tips_scale)
        ca_proj = jnp.einsum("btd,dc->btc", _merge_heads(ca.out),
                             bp["ca_o"]["w"]) + bp["ca_o"]["b"]
        if modulation is not None:
            ca_proj = ca_proj * _per_rows(modulation[5], ca_proj.shape[0])
        if reuse is not None:
            ca_proj = reuse_ops.scatter_rows(cache.ca, rows, ca_proj,
                                             gate_rows)
        ca_full = ca_proj
        h = resid + ca_proj

        # --- FFN (GEGLU) with TIPS mixed precision ---
        resid = h
        hn = layer_norm(h, bp["ln3"]["scale"], bp["ln3"]["bias"])
        if modulation is not None:
            hn = hn * (1.0 + _per_rows(modulation[7], hn.shape[0])) \
                + _per_rows(modulation[6], hn.shape[0])
        hn_f = hn if reuse is None else reuse_ops.gather_rows(hn, rows)
        if cfg.tips:
            active = tips_active
            if getattr(active, "ndim", 0) == 1:
                # per-row activity (continuous batching): broadcast over
                # tokens; under cfg_dup the rows doubled at the
                # cross-attention, tile to match
                if active.shape[0] != h.shape[0]:
                    active = jnp.concatenate([active, active], axis=0)
                active = active[:, None]
            # ca.important_full already lives on the gathered rows (the
            # cross-attention queries were gathered with the same plan)
            important = jnp.logical_or(ca.important_full,
                                       jnp.logical_not(active))
        else:
            important = None
        ffn = dispatch.ffn_geglu(policy, hn_f, bp, important,
                                 precision=precision)
        if modulation is not None:
            ffn = ffn * _per_rows(modulation[8], ffn.shape[0])
        if reuse is not None:
            ffn = reuse_ops.scatter_rows(cache.ffn, rows, ffn, gate_rows)
        ffn_full = ffn
        h = resid + ffn
        sa_stats.append(sa.stats)
        tips_results.append(ca.tips_result)

    h = jnp.einsum("btc,cd->btd", h, p["proj_out"]["w"]) + p["proj_out"]["b"]
    out = x2d + h.reshape(b, hgt, wid, c)
    sa_stats, tips_results = tuple(sa_stats), tuple(tips_results)
    if reuse is None:
        return out, sa_stats, tips_results, None
    new_cache = LayerReuseCache(ref=tokens_in, sa=sa_full, ca=ca_full,
                                ffn=ffn_full)
    return out, sa_stats, tips_results, (new_cache, counters)


def _added_embedding(params, pooled, cfg: UNetConfig):
    """SDXL's "text_time" embedding: MLP([pooled text, the sinusoid of each
    size/crop id]), (rows, pooled_dim) -> (rows, time_dim)."""
    ids = timestep_embedding(jnp.asarray(cfg.addition_time_ids),
                             cfg.addition_time_dim).reshape(1, -1)
    x = jnp.concatenate(
        [pooled, jnp.broadcast_to(ids, (pooled.shape[0], ids.shape[1]))],
        axis=-1)
    x = jnp.einsum("bd,dc->bc", x, params["add_mlp1"]["w"]) \
        + params["add_mlp1"]["b"]
    return jnp.einsum("bd,dc->bc", jax.nn.silu(x),
                      params["add_mlp2"]["w"]) + params["add_mlp2"]["b"]


def _downsample(x, p):
    return conv2d(x, p["w"], p["b"], stride=2)


def _upsample(x, p):
    b, h, w, c = x.shape
    x = jax.image.resize(x, (b, 2 * h, 2 * w, c), "nearest")
    return conv2d(x, p["w"], p["b"])


# ----------------------------------------------------------------------------
# Forward
# ----------------------------------------------------------------------------
def unet_forward(params, latents, timesteps, context, cfg: UNetConfig,
                 tips_active: bool | jax.Array = True,
                 stats_rows: Optional[int] = None,
                 cfg_dup: bool = False,
                 row_stats: bool = False,
                 reuse_cache: Optional[ReuseCache] = None,
                 overrides=None):
    """latents (B, S, S, 4), timesteps (B,), context (B, Ttext, ctx_dim).

    Under ``cfg.addition_embed == "text_time"`` the context is the pair
    (text states (B, Ttext, ctx_dim), pooled text (B, pooled_dim)).

    Returns (eps-prediction (B, S, S, 4), ``UNetStats`` pytree) with one
    PSSA/TIPS entry per transformer block in ``attn_layer_order(cfg)``.
    ``stats_rows`` (static) restricts stats to the first N batch rows; the
    fused-CFG path sets it to the cond half so accounting matches a
    cond-only call at half the cost.

    ``tips_active`` accepts a scalar (whole batch on one schedule) or a
    (B,) per-row vector — continuous batching runs each slot at its own
    denoising iteration.  ``row_stats`` (static) switches the stats
    container to a ``SlotStats`` of per-row integer counters (same layer
    order) for scatter into per-iteration ledger buckets.

    ``cfg_dup``: fused-CFG prefix deduplication.  ``latents``/``timesteps``
    carry ONLY the cond half (B rows) while ``context`` carries
    ``[cond | uncond]`` (2B rows); everything before the first operation
    that reads text — identical for both halves — runs once on B rows and
    the hidden state is tiled to 2B there: the first cross-attention, or
    under "text_time" (whose time embedding holds the pooled text) the
    first resnet, right after ``conv_in``.  ``eps`` comes back with 2B
    rows, split by ``sampler.guided_eps``.

    ``reuse_cache`` (a ``core.reuse.ReuseCache`` built for this batch/CFG
    geometry) switches on temporal patch reuse when
    ``cfg.reuse_policy.enabled``: each transformer block gathers only the
    patches whose input delta against the cache reaches the policy
    threshold and scatters over the cached activations.  The return then
    gains a third element — the NEW cache (this step's activations, all
    rows valid) — and ``stats`` carries per-layer ``ReuseRowCounters``.

    ``overrides`` (a ``solvers.PhaseOverrides``) threads phase-scheduled
    per-row threshold scales to every transformer block; None — the
    default, and what every unscheduled sampler bank produces — leaves
    each block's trace exactly as before.
    """
    pssa_stats: list = []
    tips_stats: list = []
    reuse_stats: list = []
    new_layer_caches: list = []
    tips_active = jnp.asarray(tips_active)
    policy = cfg.effective_kernel_policy()
    precision = cfg.effective_precision()
    reuse_pol = cfg.reuse_policy
    reuse_on = reuse_pol.enabled and reuse_cache is not None
    needs_dup = cfg_dup
    text_time = cfg.addition_embed == "text_time"
    if text_time:
        context, pooled = context
    if cfg_dup:
        assert context.shape[0] == 2 * latents.shape[0], \
            (context.shape, latents.shape)
    if reuse_on and (text_time or any(
            cfg.stage_depth(i) > 1 for i in range(len(cfg.block_channels)))):
        raise ValueError("temporal patch reuse serves depth-1 transformer "
                         "stacks without added conditioning only")

    temb = timestep_embedding(timesteps, cfg.block_channels[0])
    temb = jnp.einsum("bd,dc->bc", temb, params["time_mlp1"]["w"]) \
        + params["time_mlp1"]["b"]
    temb = jnp.einsum("bd,dc->bc", jax.nn.silu(temb),
                      params["time_mlp2"]["w"]) + params["time_mlp2"]["b"]
    if text_time:
        if cfg_dup:
            temb = jnp.concatenate([temb, temb], axis=0)
        temb = temb + _added_embedding(params, pooled, cfg)

    def attn_block(h, bp, stage):
        nonlocal temb, needs_dup
        reuse_arg = None
        if reuse_on:
            reuse_arg = (reuse_pol, reuse_cache.layers[len(pssa_stats)],
                         reuse_cache.valid)
        h, sa, ca, ru = _transformer_block(h, bp, context, cfg, tips_active,
                                           stats_rows,
                                           dup_after_self=needs_dup,
                                           policy=policy,
                                           precision=precision,
                                           row_stats=row_stats,
                                           reuse=reuse_arg,
                                           overrides=overrides,
                                           heads=cfg.stage_heads(stage))
        if needs_dup:
            # downstream resnets now see [cond | uncond] rows
            temb = jnp.concatenate([temb, temb], axis=0)
            needs_dup = False
        pssa_stats.extend(sa)
        tips_stats.extend(ca)
        if reuse_on:
            new_layer_caches.append(ru[0])
            reuse_stats.append(ru[1])
        return h

    def pop_skip(h):
        skip = skips.pop()
        if skip.shape[0] != h.shape[0]:   # recorded before duplication
            skip = jnp.concatenate([skip, skip], axis=0)
        return skip

    h = conv2d(latents, params["conv_in"]["w"], params["conv_in"]["b"])
    if needs_dup and text_time:
        # the pooled text is in the time embedding: the halves differ from
        # the first resnet on
        h = jnp.concatenate([h, h], axis=0)
        needs_dup = False
    skips = [h]

    last = len(cfg.block_channels) - 1
    for i, stage in enumerate(params["down"]):
        for r, rp in enumerate(stage["resnets"]):
            h = _resnet(h, rp, temb, cfg.groups)
            if stage["attns"]:
                h = attn_block(h, stage["attns"][r], i)
            skips.append(h)
        if "down" in stage:
            h = _downsample(h, stage["down"])
            skips.append(h)

    if cfg.has_mid_block:
        mp = params["mid"]
        h = _resnet(h, mp["res1"], temb, cfg.groups)
        h = attn_block(h, mp["attn"], last)
        h = _resnet(h, mp["res2"], temb, cfg.groups)

    for j, stage in enumerate(params["up"]):
        for r, rp in enumerate(stage["resnets"]):
            skip = pop_skip(h)
            h = _resnet(jnp.concatenate([h, skip], axis=-1), rp, temb,
                        cfg.groups)
            if stage["attns"]:
                h = attn_block(h, stage["attns"][r], last - j)
        if "up" in stage:
            h = _upsample(h, stage["up"])

    if needs_dup:                     # no cross-attention anywhere: tile eps
        h = jnp.concatenate([h, h], axis=0)

    h = group_norm(h, params["norm_out"]["scale"], params["norm_out"]["bias"],
                   cfg.groups)
    eps = conv2d(jax.nn.silu(h), params["conv_out"]["w"],
                 params["conv_out"]["b"])
    stats_cls = SlotStats if row_stats else UNetStats
    stats = stats_cls.from_layer_list(attn_layer_order(cfg), pssa_stats,
                                      tips_stats,
                                      reuse=tuple(reuse_stats))
    if reuse_on:
        new_cache = ReuseCache(valid=jnp.ones_like(reuse_cache.valid),
                               layers=tuple(new_layer_caches))
        return eps, stats, new_cache
    return eps, stats


def abstract_unet_params(cfg: UNetConfig):
    return jax.eval_shape(lambda: init_unet_params(jax.random.PRNGKey(0),
                                                   cfg))


# --- denoiser-contract registration (repro.diffusion.denoiser) ---
from repro.diffusion import denoiser as _denoiser  # noqa: E402

_denoiser.register_family(_denoiser.FamilySpec(
    family="unet",
    config_cls=UNetConfig,
    init_params=init_unet_params,
    forward=unet_forward,
    abstract_params=abstract_unet_params,
))
