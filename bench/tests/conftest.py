"""Shared set-up of the benchmark's tests: the benchmark's modules on the
path, and a checkout-like directory (with its own compile cache) holding a
cell at a size the CPU runs in seconds."""
from __future__ import annotations

import copy
import json
import os
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, BENCH)


SMOKE_TEXT = dict(vocab_size=256, max_len=8, d_model=32, num_layers=2,
                  num_heads=4, d_ff=64)
SMOKE_DENOISER = {
    "unet": dict(block_channels=[32, 64, 64, 64], num_heads=4, context_dim=32,
                 text_len=8, time_dim=64, latent_size=16, groups=8),
    "dit": dict(latent_size=16, hidden_size=64, depth=4, num_heads=4,
                context_dim=32, text_len=8, time_dim=64, groups=8),
}


def smoke_config(name: str, steps: int = 3, folder: str = "configs") -> dict:
    """A configuration file of the benchmark (``bench/<folder>/<name>.json``),
    cut to CPU-test size."""
    with open(os.path.join(BENCH, folder, f"{name}.json")) as f:
        cfg = json.load(f)
    cfg["denoiser"].update(SMOKE_DENOISER[cfg["denoiser"]["family"]])
    cfg["text"].update(SMOKE_TEXT)
    cfg["vae"].update(channels=[32, 32, 16, 16], groups=8)
    cfg["sampler"].update(num_inference_steps=steps,
                          tips_active_iters=max(1, steps * 20 // 25))
    cfg["serve"]["slots"] = 2
    return cfg


def write_root(path, cfg: dict, mix: dict, cell: str = "smoke.t") -> dict:
    """A checkout-like root with one cell, found by name from data alone."""
    os.makedirs(path / "bench" / "configs", exist_ok=True)
    os.makedirs(path / "bench" / "traffic", exist_ok=True)
    (path / "bench" / "configs" / "smoke.json").write_text(json.dumps(cfg))
    (path / "bench" / "traffic" / "t.json").write_text(json.dumps(mix))
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        spec = json.load(f)
    spec = copy.deepcopy(spec)
    spec["configs"] = [{"name": "smoke", "source": "smoke",
                        "file": "bench/configs/smoke.json", "reduced": [],
                        "why": "CPU test size"}]
    spec["workloads"] = [{"name": cell, "config": "smoke", "traffic": "t",
                          "chips": 1, "why": "CPU test size"}]
    metric_cells = {c["name"]: c["traffic"]
                    for c in json.load(open(os.path.join(
                        ROOT, "BENCHMARK.json")))["workloads"]}
    kind = mix["kind"]
    for m in spec["end_to_end"] + spec["per_layer"]:
        if "workloads" in m:
            # keep a metric on the smoke cell where one of its cells runs
            # the same kind of traffic
            same = any(_kind_of(metric_cells.get(w)) == kind
                       for w in m["workloads"])
            m["workloads"] = [cell] if same else []
    (path / "BENCHMARK.json").write_text(json.dumps(spec))
    return spec


def _kind_of(traffic_name):
    if traffic_name is None:
        return None
    with open(os.path.join(BENCH, "traffic", f"{traffic_name}.json")) as f:
        return json.load(f)["kind"]


# peaks for a run on the CPU: high enough that a standing queue's request
# count (peak over one image's FLOPs, times the window) never runs dry
PEAKS = {"bf16_flops_per_s": 1e13, "hbm_bytes_per_s": 1e12}
