"""The paper's own workload: the BK-SDM text-to-image pipeline.

The served geometry is BK-SDM-Small's (482.3M UNet parameters, the
published 0.48B), though the program has long called it BK-SDM-Tiny:
Tiny (0.32B) also drops the innermost 1280-channel stage and its up stage.

Not one of the 10 assigned LM architectures — this is the diffusion config
the processor was evaluated on (MS-COCO, 25 DDIM iterations).  Exposed here
so ``--arch bk-sdm`` selects the paper-faithful pipeline in examples and
benchmarks.  See ``repro.diffusion`` for the model itself.
"""
import dataclasses

from repro.core.precision import PrecisionPolicy
from repro.diffusion.pipeline import PipelineConfig
from repro.diffusion.sampler import DDIMConfig
from repro.diffusion.text_encoder import TextEncoderConfig
from repro.diffusion.unet import UNetConfig
from repro.diffusion.vae import VAEConfig
from repro.kernels.dispatch import KernelPolicy

CONFIG = PipelineConfig(
    unet=UNetConfig(),            # BK-SDM-Small geometry (full)
    text=TextEncoderConfig(),     # CLIP ViT-L/14 text tower geometry
    vae=VAEConfig(),
    ddim=DDIMConfig(num_inference_steps=25),
)

SMOKE = PipelineConfig.smoke()


def with_kernel_policy(cfg: PipelineConfig,
                       policy: KernelPolicy) -> PipelineConfig:
    """Pipeline config with the UNet hot path routed per ``policy``."""
    return dataclasses.replace(
        cfg, unet=dataclasses.replace(cfg.unet, kernel_policy=policy))


def with_precision(cfg: PipelineConfig,
                   policy: PrecisionPolicy) -> PipelineConfig:
    """Pipeline config with the TIPS/DBSC precision runtime set."""
    return dataclasses.replace(
        cfg, unet=dataclasses.replace(cfg.unet, precision=policy))


# Serving path: blocked Pallas attention (self + cross) + PSXU kernel —
# neither the SAS nor the cross-attention probability tensor materializes
# (interpret auto-selected per backend; see kernels.dispatch).
FUSED = with_kernel_policy(CONFIG, KernelPolicy.fused())
SMOKE_FUSED = with_kernel_policy(SMOKE, KernelPolicy.fused())

# Paper operating point for the precision runtime: whole-FFN TIPS coverage
# ("INT12 through the whole following FFN stack", §IV-A) at the measured
# 44.8 % workload target via per-sample adaptive spotting.
ADAPTIVE = with_precision(CONFIG, PrecisionPolicy.adaptive())
PAPER_PRECISION = with_precision(
    CONFIG, PrecisionPolicy(spotting="fixed", ffn_mid=True))
