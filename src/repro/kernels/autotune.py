"""Block-size autotuner for the fused Pallas kernels.

The five ``KernelPolicy`` block knobs (``attn_block_q``/``attn_block_k``,
``cross_block_q``, ``bitmap_block_rows``, ``reuse_block_patches``) default
to safe-everywhere values (the attention pair to the PSSA op's tiling by
its operand shape); the right blocks depend on the backend and the
operand geometry.  This module sweeps each kernel family's candidates
with the same min-of-k block-until-ready timing every bench uses
(``runtime.min_wall_s``) and persists the winners to a committed JSON
table keyed exactly like the dispatch layer routes ops::

    {backend}/{op}/{field=value,...}     e.g.
    cpu/self_attention/b=1,h=8,t=4096,d=40,patch=64

At run time ``KernelPolicy.autotuned()`` (see ``dispatch.py``) looks the
table up AT TRACE TIME from the static operand shapes and feeds the
winning blocks into the kernel calls as ordinary block arguments — table
values never enter an executable cache key beyond the hashable policy
itself, so flipping tables cannot cause retracing churn.  Unknown
(backend, op, geometry) keys fall back to the policy's defaults; a
malformed or version-stale table is a hard ``AutotuneTableError`` (a
silently ignored table would masquerade as a tuning regression).

Each kernel family exposes three hooks on its ``ops`` module:

* ``AUTOTUNE_KNOBS``             — the policy field names it tunes
* ``autotune_candidates(geom)``  — block-dict candidates for a geometry
* ``autotune_probe(geom, blocks, *, interpret=None)`` — (jitted fn, args)

Regenerate the committed table with::

    python -m repro.kernels.autotune            # full geometry (minutes)
    python -m repro.kernels.autotune --smoke    # tiny geometry (CI)
"""
from __future__ import annotations

import argparse
import importlib
import json
import os
import sys
from typing import Any, Sequence

import jax

from repro.kernels import runtime

AUTOTUNE_VERSION = 1
DEFAULT_TABLE_PATH = os.path.join(os.path.dirname(__file__),
                                  "autotune_table.json")

# op name (as the dispatch layer routes it) -> (ops module, geometry
# field names — the order is the canonical key order)
_OPS: dict[str, tuple[str, tuple[str, ...]]] = {
    "self_attention": ("repro.kernels.pssa_attention.ops",
                       ("b", "h", "t", "d", "patch")),
    "cross_attention": ("repro.kernels.cross_attention_tips.ops",
                        ("b", "h", "tq", "d", "tk")),
    "bitmap": ("repro.kernels.patch_bitmap.ops",
               ("rows", "tk", "patch")),
    "reuse": ("repro.kernels.patch_reuse.ops",
              ("b", "t", "c", "patch")),
}

# the geometries the serving paths actually run (paper smoke model:
# 64x64 latents -> T=4096 self-attention rows, Tk=77 text keys) — these
# are what the committed table is generated over
DEFAULT_GEOMS: dict[str, tuple[tuple[int, ...], ...]] = {
    "self_attention": ((1, 8, 4096, 40, 64),),
    "cross_attention": ((1, 8, 1024, 40, 77), (1, 8, 4096, 40, 77)),
    "bitmap": ((4096, 4096, 64),),
    "reuse": ((1, 4096, 320, 64),),
}

# tiny geometries for the CI smoke sweep (seconds, not minutes)
SMOKE_GEOMS: dict[str, tuple[tuple[int, ...], ...]] = {
    "self_attention": ((1, 2, 256, 32, 16),),
    "cross_attention": ((1, 2, 256, 32, 77),),
    "bitmap": ((256, 256, 16),),
    "reuse": ((1, 256, 64, 16),),
}


class AutotuneTableError(ValueError):
    """The autotune table is malformed or stale — regenerate it."""


def _op_module(op: str):
    if op not in _OPS:
        raise KeyError(f"unknown autotune op {op!r}; "
                       f"known: {sorted(_OPS)}")
    # the family ops modules reach repro.core via their ref imports;
    # importing core.attention first keeps that cycle resolvable no
    # matter which repro module the caller touched first
    importlib.import_module("repro.core.attention")
    return importlib.import_module(_OPS[op][0])


# ---------------------------------------------------------------------------
# Keys
# ---------------------------------------------------------------------------
def make_key(backend: str, op: str, geom: Sequence[int]) -> str:
    """(backend, op, geometry) -> the canonical table key string."""
    fields = _OPS[op][1]
    if len(geom) != len(fields):
        raise ValueError(f"{op} geometry needs {fields}, got {tuple(geom)}")
    dims = ",".join(f"{f}={int(v)}" for f, v in zip(fields, geom))
    return f"{backend}/{op}/{dims}"


def parse_key(key: str) -> tuple[str, str, tuple[int, ...]]:
    """Canonical key string -> (backend, op, geometry); strict inverse."""
    try:
        backend, op, dims = key.split("/")
    except ValueError:
        raise AutotuneTableError(
            f"bad autotune key {key!r}: want 'backend/op/f=v,...'") from None
    if op not in _OPS:
        raise AutotuneTableError(f"bad autotune key {key!r}: "
                                 f"unknown op {op!r}")
    fields = _OPS[op][1]
    parts = dims.split(",") if dims else []
    got: dict[str, int] = {}
    for part in parts:
        name, _, val = part.partition("=")
        if not val or not val.lstrip("-").isdigit():
            raise AutotuneTableError(
                f"bad autotune key {key!r}: field {part!r} is not 'name=int'")
        got[name] = int(val)
    if tuple(got) != fields:
        raise AutotuneTableError(
            f"bad autotune key {key!r}: {op} geometry fields must be "
            f"{fields} in order, got {tuple(got)}")
    return backend, op, tuple(got[f] for f in fields)


# ---------------------------------------------------------------------------
# Table load / lookup
# ---------------------------------------------------------------------------
_TABLE_CACHE: dict[str, dict[str, Any]] = {}


def clear_cache() -> None:
    """Drop memoized tables (tests monkeypatching the table path)."""
    _TABLE_CACHE.clear()


def validate_table(table: Any, *, source: str = "<table>") -> dict:
    """Structural validation; returns the table or raises loudly."""
    if not isinstance(table, dict):
        raise AutotuneTableError(f"{source}: autotune table must be a JSON "
                                 f"object, got {type(table).__name__}")
    version = table.get("version")
    if version != AUTOTUNE_VERSION:
        raise AutotuneTableError(
            f"{source}: autotune table version {version!r} != expected "
            f"{AUTOTUNE_VERSION}; regenerate with "
            f"'python -m repro.kernels.autotune'")
    entries = table.get("entries")
    if not isinstance(entries, dict):
        raise AutotuneTableError(f"{source}: 'entries' must be an object")
    for key, blocks in entries.items():
        _, op, _ = parse_key(key)                 # raises on bad keys
        knobs = _op_knobs(op)
        if not isinstance(blocks, dict) or not blocks:
            raise AutotuneTableError(
                f"{source}: entry {key!r} must map knob names to ints")
        for name, val in blocks.items():
            if name not in knobs:
                raise AutotuneTableError(
                    f"{source}: entry {key!r} tunes unknown knob {name!r}; "
                    f"{op} knobs are {knobs}")
            if not isinstance(val, int) or isinstance(val, bool) or val <= 0:
                raise AutotuneTableError(
                    f"{source}: entry {key!r} knob {name!r} must be a "
                    f"positive int, got {val!r}")
    return table


def _op_knobs(op: str) -> tuple[str, ...]:
    # knob names are static metadata; avoid importing jax-heavy ops
    # modules just to validate a table
    return {
        "self_attention": ("attn_block_q", "attn_block_k"),
        "cross_attention": ("cross_block_q",),
        "bitmap": ("bitmap_block_rows",),
        "reuse": ("reuse_block_patches",),
    }[op]


def load_table(path: str | None = None) -> dict:
    """Load + validate the table at ``path`` (default: committed table).

    A missing file is a valid empty table (fresh checkouts before the
    first sweep, exotic backends); a PRESENT but malformed or stale file
    raises ``AutotuneTableError``.
    """
    path = path or DEFAULT_TABLE_PATH
    cached = _TABLE_CACHE.get(path)
    if cached is not None:
        return cached
    if not os.path.exists(path):
        table: dict[str, Any] = {"version": AUTOTUNE_VERSION, "entries": {}}
    else:
        try:
            with open(path) as f:
                table = json.load(f)
        except json.JSONDecodeError as e:
            raise AutotuneTableError(
                f"{path}: autotune table is not valid JSON ({e}); "
                f"regenerate with 'python -m repro.kernels.autotune'"
            ) from None
        validate_table(table, source=path)
    _TABLE_CACHE[path] = table
    return table


def lookup(op: str, geom: Sequence[int], *, backend: str | None = None,
           path: str | None = None) -> dict[str, int] | None:
    """Winning blocks for (backend, op, geometry), or None (use defaults)."""
    backend = backend or jax.default_backend()
    entries = load_table(path)["entries"]
    blocks = entries.get(make_key(backend, op, geom))
    return dict(blocks) if blocks is not None else None


# ---------------------------------------------------------------------------
# Sweep
# ---------------------------------------------------------------------------
def sweep_op(op: str, geom: Sequence[int], *, reps: int = 2,
             interpret: bool | None = None, verbose: bool = True):
    """Time every candidate for one (op, geometry); return (best, trace)."""
    mod = _op_module(op)
    geom = tuple(int(v) for v in geom)
    results = []
    for blocks in mod.autotune_candidates(geom):
        fn, args = mod.autotune_probe(geom, blocks, interpret=interpret)
        wall = runtime.min_wall_s(fn, *args, reps=reps)
        results.append({"blocks": dict(blocks), "wall_s": wall})
        if verbose:
            print(f"  {op} {geom} {blocks} -> {wall * 1e3:.2f} ms",
                  file=sys.stderr)
    best = min(results, key=lambda r: r["wall_s"])
    return dict(best["blocks"]), results


def tune(geoms: dict[str, Sequence[Sequence[int]]] | None = None, *,
         reps: int = 2, interpret: bool | None = None,
         backend: str | None = None, verbose: bool = True) -> dict:
    """Sweep every (op, geometry) and return a full, valid table dict."""
    geoms = geoms or DEFAULT_GEOMS
    backend = backend or jax.default_backend()
    entries: dict[str, Any] = {}
    trace: dict[str, Any] = {}
    for op, op_geoms in geoms.items():
        for geom in op_geoms:
            key = make_key(backend, op, geom)
            if verbose:
                print(f"[autotune] {key}", file=sys.stderr)
            best, results = sweep_op(op, geom, reps=reps,
                                     interpret=interpret, verbose=verbose)
            entries[key] = best
            trace[key] = results
    table = {
        "version": AUTOTUNE_VERSION,
        "generated_on": {
            "backend": backend,
            "interpret": runtime.resolve_interpret(interpret),
        },
        "entries": entries,
        "sweep": trace,
    }
    return validate_table(table, source="<tune>")


def save_table(table: dict, path: str | None = None) -> str:
    path = path or DEFAULT_TABLE_PATH
    with open(path, "w") as f:
        json.dump(table, f, indent=2, sort_keys=True)
        f.write("\n")
    _TABLE_CACHE.pop(path, None)
    return path


def main(argv: Sequence[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--out", default=DEFAULT_TABLE_PATH,
                    help="table path to write (default: committed table)")
    ap.add_argument("--smoke", action="store_true",
                    help="tiny geometries (CI wiring check, seconds)")
    ap.add_argument("--reps", type=int, default=2,
                    help="timed repetitions per candidate (min is kept)")
    ap.add_argument("--ops", default=None,
                    help="comma-separated op subset (default: all)")
    args = ap.parse_args(argv)

    geoms = dict(SMOKE_GEOMS if args.smoke else DEFAULT_GEOMS)
    if args.ops:
        wanted = args.ops.split(",")
        unknown = [o for o in wanted if o not in geoms]
        if unknown:
            ap.error(f"unknown ops {unknown}; known: {sorted(geoms)}")
        geoms = {op: geoms[op] for op in wanted}

    table = tune(geoms, reps=args.reps)
    path = save_table(table, args.out)
    print(f"[autotune] wrote {len(table['entries'])} entries -> {path}",
          file=sys.stderr)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
