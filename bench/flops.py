"""Operations and bytes of the served pipeline, counted from shapes.

A multiply-add is 2 FLOPs.  Only matrix products and convolutions are
counted (norms, softmax and elementwise work are a few percent of these
and no peak bounds them).  Sizes come from the configuration file's
``text``, ``denoiser`` and ``vae`` groups.

``image_flops`` is what one served image requires of the chip: the text
encode of its prompt and of the unconditional prompt, ``num_inference_steps``
guided denoiser forwards as the served program computes them (the cond and
uncond rows share everything before the first cross-attention, so that
prefix runs once), and the VAE decode.

``attention_calls`` lists the calls one slot step makes of the two fused
attention kernels, with the FLOPs and HBM bytes the attention itself
requires: ``4 B H Tq Tk d`` FLOPs (QK and PV) and the float32 Q, K, V and O
operands plus the per-query side outputs (PSSA's two counters, TIPS's CAS).
The PSSA kernel's second QK pass and its counter work are not required
work, so they show as the distance from the roofline.

This is the default counts module of a configuration (``config_modules.py``
states the interface): ``image_flops(cfg)`` and ``attention_calls(cfg,
slots)``.  ``IMPLEMENTS`` declares the configuration keys it counts: a UNet
with one transformer block after each resnet, no mid block and one head
count, under classifier-free guidance.
"""
from __future__ import annotations

F32 = 4


def _guided(scale) -> bool:
    """a number other than 1: the counts hold classifier-free guidance's
    uncond rows"""
    return isinstance(scale, (int, float)) and scale != 1


_NUMBER = (int, float)
_SHARED = {"in_channels": int, "out_channels": int, "num_heads": int,
           "context_dim": int, "text_len": int, "time_dim": int,
           "latent_size": int, "groups": int, "ffn_mult": int,
           "pssa_threshold": _NUMBER, "tips_threshold": _NUMBER}
IMPLEMENTS = {
    "denoiser": {
        "unet": {**_SHARED, "family": ["unet"], "block_channels": list,
                 "down_attn": list, "resnets_per_down": int,
                 "resnets_per_up": int, "has_mid_block": [False],
                 "transformer_depth": [1]},
        "dit": {**_SHARED, "family": ["dit"], "patch": int,
                "hidden_size": int, "depth": int},
    },
    "text": {"vocab_size": int, "max_len": int, "d_model": int,
             "num_layers": int, "num_heads": int, "d_ff": int},
    "vae": {"latent_channels": int, "out_channels": int, "channels": list,
            "resnets_per_stage": int, "groups": int, "scale_factor": _NUMBER},
    "sampler": {"num_train_steps": int, "num_inference_steps": int,
                "beta_start": _NUMBER, "beta_end": _NUMBER,
                "guidance_scale": _guided, "tips_active_iters": int},
}


def matmul(m: int, k: int, n: int) -> int:
    return 2 * m * k * n


def conv(res: int, k: int, cin: int, cout: int) -> int:
    """A k x k convolution producing a res x res map."""
    return 2 * res * res * k * k * cin * cout


def text_flops(text: dict) -> int:
    """One prompt through the text tower."""
    t, d, f = text["max_len"], text["d_model"], text["d_ff"]
    layer = (matmul(t, d, 3 * d) + 2 * matmul(t, d, t) + matmul(t, d, d)
             + matmul(t, d, f) + matmul(t, f, d))
    return text["num_layers"] * layer


def block_flops(tokens: int, c: int, den: dict, text_len: int) -> dict:
    """One transformer block on one row: prefix (through self-attention)
    and the rest, so that the CFG-shared prefix can be counted once."""
    t, ctx = tokens, den["context_dim"]
    dff = den["ffn_mult"] * c
    prefix = (matmul(t, c, c)                       # proj_in
              + 3 * matmul(t, c, c)                 # q, k, v
              + 2 * matmul(t, c, t)                 # QK, PV over all heads
              + matmul(t, c, c))                    # out projection
    rest = (2 * matmul(t, c, c)                     # ca q, ca out
            + 2 * matmul(text_len, ctx, c)          # ca k, v
            + 2 * matmul(t, c, text_len)            # QK, PV over the text
            + matmul(t, c, 2 * dff) + matmul(t, dff, c)   # GEGLU FFN
            + matmul(t, c, c))                      # proj_out
    return {"prefix": prefix, "rest": rest}


def _resnet(res: int, cin: int, cout: int, tdim: int) -> int:
    f = conv(res, 3, cin, cout) + conv(res, 3, cout, cout)
    f += matmul(1, tdim, cout) if tdim else 0
    return f + (conv(res, 1, cin, cout) if cin != cout else 0)


def unet_flops(den: dict, text_len: int) -> dict:
    """One UNet forward on one row: {'prefix', 'rest'} as above."""
    chans, lat = den["block_channels"], den["latent_size"]
    tdim = den["time_dim"]
    prefix = rest = 0
    seen_attn = False

    def add(f):
        nonlocal prefix, rest
        if seen_attn:
            rest += f
        else:
            prefix += f

    def attn(res, c):
        nonlocal seen_attn, prefix, rest
        b = block_flops(res * res, c, den, text_len)
        if seen_attn:
            rest += b["prefix"] + b["rest"]
        else:
            prefix += b["prefix"]
            rest += b["rest"]
            seen_attn = True

    add(matmul(1, chans[0], tdim) + matmul(1, tdim, tdim))   # time MLP
    add(conv(lat, 3, den["in_channels"], chans[0]))
    skips, cin, res = [chans[0]], chans[0], lat
    for i, c in enumerate(chans):
        for _ in range(den["resnets_per_down"]):
            add(_resnet(res, cin, c, tdim))
            if den["down_attn"][i]:
                attn(res, c)
            cin = c
            skips.append(c)
        if i < len(chans) - 1:
            res //= 2
            add(conv(res, 3, c, c))
            skips.append(c)
    for j, i in enumerate(reversed(range(len(chans)))):
        c = chans[i]
        for _ in range(den["resnets_per_up"]):
            add(_resnet(res, cin + skips.pop(), c, tdim))
            if den["down_attn"][i]:
                attn(res, c)
            cin = c
        if j < len(chans) - 1:
            res *= 2
            add(conv(res, 3, c, c))
    add(conv(lat, 3, chans[0], den["out_channels"]))
    return {"prefix": prefix, "rest": rest}


def dit_flops(den: dict, text_len: int) -> dict:
    """One DiT forward on one row: {'prefix', 'rest'}."""
    d, p, tdim = den["hidden_size"], den["patch"], den["time_dim"]
    g = den["latent_size"] // p
    t = g * g
    b = block_flops(t, d, den, text_len)
    ada = matmul(1, tdim, 9 * d)
    prefix = (matmul(1, d, tdim) + matmul(1, tdim, tdim)
              + matmul(t, p * p * den["in_channels"], d)
              + ada + b["prefix"])
    rest = (b["rest"] + (den["depth"] - 1) * (ada + b["prefix"] + b["rest"])
            + matmul(1, tdim, 2 * d)
            + matmul(t, d, p * p * den["out_channels"]))
    return {"prefix": prefix, "rest": rest}


def vae_flops(vae: dict, latent_size: int) -> int:
    chans, res = vae["channels"], latent_size
    f = conv(res, 3, vae["latent_channels"], chans[0])
    cin = chans[0]
    for i, c in enumerate(chans):
        for _ in range(vae["resnets_per_stage"]):
            f += _resnet(res, cin, c, 0)
            cin = c
        if i < len(chans) - 1:
            res *= 2
            f += conv(res, 3, c, c)
    return f + conv(res, 3, chans[-1], vae["out_channels"])


def denoiser_flops(den: dict, text_len: int) -> dict:
    fn = {"unet": unet_flops, "dit": dit_flops}[den["family"]]
    return fn(den, text_len)


def image_flops(cfg: dict) -> int:
    """FLOPs one guided image requires, as the served program computes it."""
    den, text = cfg["denoiser"], cfg["text"]
    f = denoiser_flops(den, text["max_len"])
    steps = cfg["sampler"]["num_inference_steps"]
    return (2 * text_flops(text) + steps * (f["prefix"] + 2 * f["rest"])
            + vae_flops(cfg["vae"], den["latent_size"]))


def attention_calls(cfg: dict, slots: int) -> list:
    """[(kind, flops, bytes)] of the fused attention calls of one slot step.

    Rows are ``slots`` for the first self-attention (before the CFG
    duplication) and ``2 * slots`` for every other call.
    """
    den, tk = cfg["denoiser"], cfg["text"]["max_len"]
    heads = den["num_heads"]
    if den["family"] == "unet":
        lat = den["latent_size"]
        layers = []
        for i, c in enumerate(den["block_channels"]):
            if den["down_attn"][i]:
                layers += [(lat >> i, c)] * den["resnets_per_down"]
        for i in reversed(range(len(den["block_channels"]))):
            if den["down_attn"][i]:
                layers += [(lat >> i, den["block_channels"][i])] \
                    * den["resnets_per_up"]
        layers = [(r * r, c) for r, c in layers]
    else:
        g = den["latent_size"] // den["patch"]
        layers = [(g * g, den["hidden_size"])] * den["depth"]
    calls = []
    for n, (t, c) in enumerate(layers):
        d = c // heads
        rows = slots if n == 0 else 2 * slots
        bh = rows * heads
        calls.append(("self", 4 * bh * t * t * d,
                      F32 * bh * (4 * t * d + 2 * t)))
        rows = 2 * slots
        bh = rows * heads
        calls.append(("cross", 4 * bh * t * tk * d,
                      F32 * bh * (2 * t * d + 2 * tk * d + t)))
    return calls


def least_time_s(flops: float, nbytes: float, peaks: dict) -> float:
    """Roofline floor: the larger of compute time and memory time."""
    return max(flops / peaks["bf16_flops_per_s"],
               nbytes / peaks["hbm_bytes_per_s"])
