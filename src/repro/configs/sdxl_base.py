"""SDXL base 1.0 text-to-image pipeline presets.

Podell et al., "SDXL: Improving Latent Diffusion Models for High-Resolution
Image Synthesis" (arXiv:2307.01952), at the widths published in
``stabilityai/stable-diffusion-xl-base-1.0`` (``unet/config.json``,
``text_encoder/config.json``, ``text_encoder_2/config.json``): a
three-stage UNet (320, 640, 1280) with no attention at 128x128 latents,
transformer depth (1, 2, 10) and heads of 64 channels, a mid block whose
stack is 10 blocks deep, 2 resnets down and 3 up per stage; a 2048-wide
context from the penultimate states of CLIP ViT-L/14 and OpenCLIP
ViT-bigG/14; bigG's pooled, projected text and six size/crop ids as the
"text_time" added conditioning.  Weights are stored in bfloat16 (6.9 GB
with both towers, against 13.9 GB in float32 on a 16 GB chip);
activations and latents stay float32.  The sampler is this program's DDIM
on SDXL's betas at its pipeline's default guidance, 5.0.
"""
import dataclasses

from repro.configs.bk_sdm import with_kernel_policy
from repro.diffusion.pipeline import PipelineConfig
from repro.diffusion.sampler import DDIMConfig
from repro.diffusion.text_encoder import TextEncoderConfig, TextTowerConfig
from repro.diffusion.unet import UNetConfig
from repro.diffusion.vae import VAEConfig
from repro.kernels.dispatch import KernelPolicy

UNET = UNetConfig(
    block_channels=(320, 640, 1280),
    down_attn=(False, True, True),
    resnets_per_down=2,
    resnets_per_up=3,
    has_mid_block=True,
    transformer_depth=(1, 2, 10),
    num_heads=(5, 10, 20),
    context_dim=2048,
    latent_size=128,                  # 1024x1024 images
    addition_embed="text_time",
    dtype="bfloat16",
)

TEXT = TextEncoderConfig(             # CLIP ViT-L/14
    output="penultimate",
    dtype="bfloat16",
    second=TextTowerConfig(           # OpenCLIP ViT-bigG/14
        d_model=1280, num_layers=32, num_heads=20, d_ff=5120,
        output="penultimate", projection_dim=1280),
)

CONFIG = PipelineConfig(
    unet=UNET,
    text=TEXT,
    vae=VAEConfig(scale_factor=0.13025, dtype="bfloat16"),
    ddim=DDIMConfig(num_inference_steps=25, guidance_scale=5.0),
)

# reduced geometry that runs a full forward pass on CPU in seconds, with
# stacks of depth 1 and 2, a mid block and both towers
SMOKE = PipelineConfig(
    unet=dataclasses.replace(
        UNET, block_channels=(32, 64, 64), transformer_depth=(1, 1, 2),
        num_heads=(2, 4, 4), context_dim=80, text_len=8, time_dim=64,
        latent_size=16, groups=8, addition_time_dim=8, pooled_dim=40,
        addition_time_ids=(128, 128, 0, 0, 128, 128)),
    text=dataclasses.replace(
        TEXT, vocab_size=256, max_len=8, d_model=32, num_layers=2,
        num_heads=4, d_ff=64, second=dataclasses.replace(
            TEXT.second, vocab_size=256, max_len=8, d_model=48,
            num_layers=2, num_heads=4, d_ff=96, projection_dim=40)),
    vae=dataclasses.replace(VAEConfig().smoke(), scale_factor=0.13025,
                            dtype="bfloat16"),
    ddim=DDIMConfig(num_inference_steps=3, guidance_scale=5.0,
                    tips_active_iters=2),
)

# Serving path: the fused Pallas attention kernels (see configs.bk_sdm)
FUSED = with_kernel_policy(CONFIG, KernelPolicy.fused())
SMOKE_FUSED = with_kernel_policy(SMOKE, KernelPolicy.fused())
