"""Operations and bytes of SDXL base 1.0 as the program serves it, counted
from shapes with ``flops.py``'s rules (a multiply-add is 2 FLOPs; matrix
products and convolutions only).

``image_flops``: the two text encodes of an admission under CFG (each
tower to the layer its output needs: CLIP-L's last layer feeds nothing
and is not run, bigG's feeds the pooled projection), 25 guided UNet steps
and the 1024x1024 decode.  Under "text_time" the two CFG halves differ
from the first resnet on, so only the time MLP and ``conv_in`` run once a
step and every other layer, the added embedding included, runs on both
rows.

``attention_calls``: the 70 fused self-attention and 70 cross-attention
calls of one slot step (every stack of every stage and the mid block, one
call each per basic block), all on the 2S [cond | uncond] rows, with the
FLOPs and bytes ``flops.attention_calls`` counts for a call.

``IMPLEMENTS`` declares the configuration keys it counts.
"""
from __future__ import annotations

import flops
import reference_sdxl
from flops import F32, _resnet, conv, matmul

IMPLEMENTS = {**reference_sdxl.IMPLEMENTS,
              "sampler": {**reference_sdxl.IMPLEMENTS["sampler"],
                          "guidance_scale": flops._guided}}


def _stage(v, i: int) -> int:
    return v if isinstance(v, int) else v[i]


def _towers(text: dict) -> list:
    return [text] + ([text["second"]] if "second" in text else [])


def text_flops(text: dict) -> int:
    """One prompt through both towers, each to the layer it needs."""
    f = 0
    for t in _towers(text):
        proj = t.get("projection_dim", 0)
        layers = t["num_layers"] - (t.get("output") == "penultimate"
                                    and not proj)
        f += flops.text_flops({**t, "num_layers": layers})
        f += matmul(1, t["d_model"], proj) if proj else 0
    return f


def stack_flops(tokens: int, c: int, depth: int, den: dict,
                text_len: int) -> int:
    """A transformer stack on one row: proj_in, ``depth`` basic blocks,
    proj_out."""
    b = flops.block_flops(tokens, c, den, text_len)
    io = 2 * matmul(tokens, c, c)
    return io + depth * (b["prefix"] + b["rest"] - io)


def unet_flops(den: dict, text_len: int) -> dict:
    """One UNet forward on one row: {'prefix': once a step, 'rest': on
    both CFG rows}."""
    chans, lat, tdim = den["block_channels"], den["latent_size"], \
        den["time_dim"]
    last = len(chans) - 1
    prefix = (matmul(1, chans[0], tdim) + matmul(1, tdim, tdim)
              + conv(lat, 3, den["in_channels"], chans[0]))
    add_in = den["pooled_dim"] + (len(den["addition_time_ids"])
                                  * den["addition_time_dim"])
    rest = matmul(1, add_in, tdim) + matmul(1, tdim, tdim)

    def attn(res, i):
        return stack_flops(res * res, chans[i],
                           _stage(den["transformer_depth"], i), den,
                           text_len)

    skips, cin, res = [chans[0]], chans[0], lat
    for i, c in enumerate(chans):
        for _ in range(den["resnets_per_down"]):
            rest += _resnet(res, cin, c, tdim)
            rest += attn(res, i) if den["down_attn"][i] else 0
            cin = c
            skips.append(c)
        if i < last:
            res //= 2
            rest += conv(res, 3, c, c)
            skips.append(c)
    if den["has_mid_block"]:
        rest += 2 * _resnet(res, cin, cin, tdim) + attn(res, last)
    for j, i in enumerate(reversed(range(len(chans)))):
        c = chans[i]
        for _ in range(den["resnets_per_up"]):
            rest += _resnet(res, cin + skips.pop(), c, tdim)
            rest += attn(res, i) if den["down_attn"][i] else 0
            cin = c
        if j < last:
            res *= 2
            rest += conv(res, 3, c, c)
    rest += conv(lat, 3, chans[0], den["out_channels"])
    return {"prefix": prefix, "rest": rest}


def image_flops(cfg: dict) -> int:
    """FLOPs one guided image requires, as the served program computes it."""
    den, text = cfg["denoiser"], cfg["text"]
    f = unet_flops(den, text["max_len"])
    steps = cfg["sampler"]["num_inference_steps"]
    return (2 * text_flops(text) + steps * (f["prefix"] + 2 * f["rest"])
            + flops.vae_flops(cfg["vae"], den["latent_size"]))


def attention_layers(den: dict) -> list:
    """(tokens, channels, heads) of every basic block in forward order."""
    chans, lat = den["block_channels"], den["latent_size"]
    last = len(chans) - 1

    def stack(i):
        res = lat >> i
        return [(res * res, chans[i], _stage(den["num_heads"], i))] \
            * _stage(den["transformer_depth"], i)

    out = []
    for i in range(len(chans)):
        if den["down_attn"][i]:
            out += stack(i) * den["resnets_per_down"]
    if den["has_mid_block"]:
        out += stack(last)
    for i in reversed(range(len(chans))):
        if den["down_attn"][i]:
            out += stack(i) * den["resnets_per_up"]
    return out


def attention_calls(cfg: dict, slots: int) -> list:
    """[(kind, flops, bytes)] of the fused attention calls of one slot
    step: one self and one cross call per basic block, on 2S rows."""
    tk = cfg["text"]["max_len"]
    calls = []
    for t, c, heads in attention_layers(cfg["denoiser"]):
        d = c // heads
        bh = 2 * slots * heads
        calls.append(("self", 4 * bh * t * t * d,
                      F32 * bh * (4 * t * d + 2 * t)))
        calls.append(("cross", 4 * bh * t * tk * d,
                      F32 * bh * (2 * t * d + 2 * tk * d + t)))
    return calls
