"""CLIP-style text encoder (stage 1 of the SD flow, Fig. 1(a)).

Bidirectional pre-LN transformer over the caption tokens with the CLS token
*first* — the position TIPS relies on (paper §IV-A cites BERT/Evo-ViT for the
CLS-first convention).  Full size mirrors CLIP ViT-L/14's text tower
(12L, d=768, 77 tokens); tests run the reduced config.

SDXL conditions on two towers (``configs.sdxl_base``): CLIP ViT-L/14 and
OpenCLIP ViT-bigG/14 (``TextEncoderConfig.second``), each read at its
penultimate layer and concatenated along features, plus bigG's pooled,
projected text.  Both towers keep this module's conventions
(bidirectional attention, CLS first, tanh-GELU); the pooled position is
the CLS token.

No pretrained weights offline — the encoder produces structurally-correct
context embeddings; the paper's evaluation (energy/EMA/throughput) does not
depend on caption semantics.
"""
from __future__ import annotations

import dataclasses
import math

import jax
import jax.numpy as jnp


@dataclasses.dataclass(frozen=True)
class TextTowerConfig:
    vocab_size: int = 49408
    max_len: int = 77
    d_model: int = 768
    num_layers: int = 12
    num_heads: int = 12
    d_ff: int = 3072
    # "final": the last layer's states through the final LayerNorm;
    # "penultimate": the states the last layer reads, without it (SDXL)
    output: str = "final"
    # > 0: also a pooled output, the final LayerNorm of the last layer at
    # the CLS position through a (d_model, projection_dim) projection
    projection_dim: int = 0


@dataclasses.dataclass(frozen=True)
class TextEncoderConfig(TextTowerConfig):
    dtype: str = "float32"
    # a second tower whose states are concatenated to the first's along
    # features (SDXL's OpenCLIP ViT-bigG/14); num_layers 0: none
    second: TextTowerConfig = TextTowerConfig(num_layers=0)

    def __post_init__(self):
        for t in self.towers():
            if t.output not in ("final", "penultimate"):
                raise ValueError(f"text output {t.output!r}: 'final' or "
                                 f"'penultimate'")
        if self.has_second and self.second.max_len != self.max_len:
            raise ValueError("the two text towers must read the same "
                             "number of tokens")

    @property
    def has_second(self) -> bool:
        return self.second.num_layers > 0

    def towers(self) -> tuple:
        return (self, self.second) if self.has_second else (self,)

    @property
    def context_dim(self) -> int:
        """Width of the encoded text states (both towers)."""
        return sum(t.d_model for t in self.towers())

    @property
    def pooled_dim(self) -> int:
        """Width of the pooled output; 0 where no tower projects one."""
        return sum(t.projection_dim for t in self.towers())

    def smoke(self) -> "TextEncoderConfig":
        return dataclasses.replace(self, vocab_size=256, max_len=8,
                                   d_model=32, num_layers=2, num_heads=4,
                                   d_ff=64)


CLIP_TEXT = TextEncoderConfig()


def _tower_params(key, cfg: TextTowerConfig, dtype):
    d, dff = cfg.d_model, cfg.d_ff
    ks = jax.random.split(key, 2 + cfg.num_layers)
    s = d ** -0.5

    def layer(k):
        k1, k2, k3, k4 = jax.random.split(k, 4)
        return {
            "ln1": jnp.ones((d,), dtype), "ln1_b": jnp.zeros((d,), dtype),
            "wqkv": (jax.random.normal(k1, (d, 3 * d)) * s).astype(dtype),
            "wo": (jax.random.normal(k2, (d, d)) * s).astype(dtype),
            "ln2": jnp.ones((d,), dtype), "ln2_b": jnp.zeros((d,), dtype),
            "w1": (jax.random.normal(k3, (d, dff)) * s).astype(dtype),
            "w2": (jax.random.normal(k4, (dff, d))
                   * dff ** -0.5).astype(dtype),
        }

    p = {
        "embed": (jax.random.normal(ks[0], (cfg.vocab_size, d))
                  * 0.02).astype(dtype),
        "pos": (jax.random.normal(ks[1], (cfg.max_len, d))
                * 0.01).astype(dtype),
        "layers": [layer(k) for k in ks[2:]],
        "ln_f": jnp.ones((d,), dtype),
        "ln_f_b": jnp.zeros((d,), dtype),
    }
    if cfg.projection_dim:
        p["proj"] = (jax.random.normal(jax.random.fold_in(key, 1),
                                       (d, cfg.projection_dim))
                     * s).astype(dtype)
    return p


def init_text_encoder_params(key, cfg: TextEncoderConfig):
    """The first tower's weights; a second tower's under ``second``."""
    dtype = jnp.dtype(cfg.dtype)
    p = _tower_params(key, cfg, dtype)
    if cfg.has_second:
        p["second"] = _tower_params(jax.random.fold_in(key, 2), cfg.second,
                                    dtype)
    return p


def _ln(x, scale, bias, eps=1e-5):
    m = jnp.mean(x.astype(jnp.float32), -1, keepdims=True)
    v = jnp.var(x.astype(jnp.float32), -1, keepdims=True)
    return ((x.astype(jnp.float32) - m) * jax.lax.rsqrt(v + eps)
            * scale + bias).astype(x.dtype)


def _encode_tower(params, tokens, cfg: TextTowerConfig):
    """-> (states (B, T, d), pooled (B, projection_dim) or None)."""
    b, t = tokens.shape
    # float32 states whatever the weights are stored in
    h = (jnp.take(params["embed"], tokens, axis=0)
         + params["pos"][None, :t]).astype(jnp.float32)
    nh, hd = cfg.num_heads, cfg.d_model // cfg.num_heads
    states = h
    for lp in params["layers"]:
        states = h
        x = _ln(h, lp["ln1"], lp["ln1_b"])
        qkv = jnp.einsum("btd,dk->btk", x, lp["wqkv"])
        q, k, v = jnp.split(qkv, 3, axis=-1)
        q = q.reshape(b, t, nh, hd)
        k = k.reshape(b, t, nh, hd)
        v = v.reshape(b, t, nh, hd)
        scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) / math.sqrt(hd)
        probs = jax.nn.softmax(scores.astype(jnp.float32), -1).astype(h.dtype)
        o = jnp.einsum("bhqk,bkhd->bqhd", probs, v).reshape(b, t, -1)
        h = h + jnp.einsum("btd,dk->btk", o, lp["wo"])
        x = _ln(h, lp["ln2"], lp["ln2_b"])
        h = h + jnp.einsum(
            "btf,fd->btd",
            jax.nn.gelu(jnp.einsum("btd,df->btf", x, lp["w1"])), lp["w2"])
    final = None
    if cfg.output == "final" or cfg.projection_dim:
        final = _ln(h, params["ln_f"], params["ln_f_b"])
    pooled = None
    if cfg.projection_dim:
        pooled = jnp.einsum("bd,dp->bp", final[:, 0], params["proj"])
    return (final if cfg.output == "final" else states), pooled


def encode_text(params, tokens, cfg: TextEncoderConfig):
    """tokens (B, T) int32, CLS at position 0 -> the encoded text.

    One tower without a projection gives its (B, T, d) states; otherwise
    the pair (states of every tower concatenated (B, T, context_dim),
    pooled text (B, pooled_dim)).
    """
    outs = [_encode_tower(params, tokens, cfg)]
    if cfg.has_second:
        outs.append(_encode_tower(params["second"], tokens, cfg.second))
    states = [s for s, _ in outs]
    context = states[0] if len(states) == 1 else jnp.concatenate(states, -1)
    pooled = [p for _, p in outs if p is not None]
    if not pooled:
        return context
    return context, (pooled[0] if len(pooled) == 1
                     else jnp.concatenate(pooled, -1))


def encoded_zeros(cfg: TextEncoderConfig, batch: int):
    """Zeros shaped like ``encode_text``'s output for ``batch`` prompts."""
    context = jnp.zeros((batch, cfg.max_len, cfg.context_dim))
    if not cfg.pooled_dim:
        return context
    return context, jnp.zeros((batch, cfg.pooled_dim))
