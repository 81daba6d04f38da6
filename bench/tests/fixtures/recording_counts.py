"""A counts module that a configuration names: ``flops.py`` as it is,
recording each call the benchmark makes of it in ``CALLS``."""
from __future__ import annotations

import flops

CALLS = []
IMPLEMENTS = flops.IMPLEMENTS


def image_flops(cfg: dict) -> int:
    CALLS.append("image_flops")
    return flops.image_flops(cfg)


def attention_calls(cfg: dict, slots: int) -> list:
    CALLS.append("attention_calls")
    return flops.attention_calls(cfg, slots)
