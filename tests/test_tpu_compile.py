"""Every Pallas kernel of the served path compiles for a TPU v5e.

The TPU compiler runs here on a described (not attached) v5e chip, at the
real serving widths: the BK-SDM-Tiny UNet's 64x64 level (T=4096, 8 heads
of 40, patch 64), CLIP's 77 text keys, the 320->2560 GEGLU FFN, and
DiT-S/2's self-attention (T=256, 6 heads of 64, patch 16).  Interpret-mode
tests cannot see what the chip's compiler refuses (block tiling, vector
layouts, integer matmul types); these do, at no chip time.  Each test
asserts the compiled program contains the kernel (``tpu_custom_call``).

The topology is described inside a module fixture, never at import time:
only one process at a time may load the TPU library, so every test worker
must collect the same tests and only the worker running this file loads it.
"""
import functools
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import attention  # noqa: F401  (resolves the kernel imports)
from repro.kernels.bitslice_matmul.ops import bitslice_matmul
from repro.kernels.cross_attention_tips.ops import cross_attention_cas
from repro.kernels.patch_bitmap.ops import patch_bitmap
from repro.kernels.patch_reuse.ops import patch_delta
from repro.kernels.pssa_attention.ops import pssa_attention

THRESHOLD = 1.0 / 8192.0


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compile(fn, one_chip, *shapes, kernel=None):
    """Compile for the described chip; with ``kernel``, also assert the
    kernel's custom call carries that HLO name, which the benchmark's
    per-kernel roofline readers key on."""
    args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip)
            for s, dt in shapes]
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text
    if kernel is not None:
        calls = re.findall(r"%(\w+?)(?:\.\d+)? = .*custom-call\(", text)
        assert kernel in calls, calls


F32 = jnp.float32
UNET_QKV = ((2, 8, 4096, 40), F32)


@pytest.mark.parametrize("qkv,patch", [
    (UNET_QKV, None), (UNET_QKV, 64),
    (((2, 8, 1024, 80), F32), 32), (((2, 8, 256, 160), F32), 16)],
    ids=["no_patch", "patch64", "32x32_patch32", "16x16_patch16"])
def test_pssa_attention_compiles_unet_64x64(one_chip, qkv, patch):
    """Every UNet level (64x64, 32x32, 16x16 latents) at the op's own
    tiling: a tile the chip's VMEM cannot hold is refused here."""
    fn = functools.partial(pssa_attention, threshold=THRESHOLD, patch=patch,
                           interpret=False)
    _compile(fn, one_chip, qkv, qkv, qkv, kernel="pssa_attention_kernel")


def test_pssa_attention_compiles_dit_s2(one_chip):
    qkv = ((2, 6, 256, 64), F32)
    fn = functools.partial(pssa_attention, threshold=THRESHOLD, patch=16,
                           interpret=False)
    _compile(fn, one_chip, qkv, qkv, qkv, kernel="pssa_attention_kernel")


def test_tips_cross_attention_compiles_clip_text(one_chip):
    text = ((2, 8, 77, 40), F32)
    fn = functools.partial(cross_attention_cas, interpret=False)
    _compile(fn, one_chip, UNET_QKV, text, text,
             kernel="cross_attention_tips_kernel")


def test_dbsc_bitslice_matmul_compiles_geglu(one_chip):
    fn = functools.partial(bitslice_matmul, interpret=False)
    _compile(fn, one_chip, ((8192, 320), F32), ((320, 2560), F32),
             ((8192,), jnp.bool_))


def test_reuse_patch_delta_compiles(one_chip):
    tokens = ((2, 4096, 320), F32)
    fn = functools.partial(patch_delta, patch=64, threshold=1e-3,
                           interpret=False)
    _compile(fn, one_chip, tokens, tokens)


def test_psxu_patch_bitmap_compiles(one_chip):
    fn = functools.partial(patch_bitmap, patch=64, threshold=THRESHOLD,
                           interpret=False)
    _compile(fn, one_chip, ((4096, 4096), F32))
