"""The modules a configuration brings: its plain reference and its counts.

A configuration file (``configs/<name>.json``) may name them at its top
level, each as the path of a module under ``bench/`` without ``.py``:

    "reference": "reference"    (the default: reference.py)
    "counts": "flops"           (the default: flops.py)

A reference module provides

* ``Pipeline(cfg)``, the pipeline's three stages for a configuration:
  ``enc(text_weights, tokens)``, ``step(weights, x, ctx, unctx, i)`` (one
  guided denoising iteration ``i``, a traced int) and ``dec(vae_weights,
  x)``.  ``enc`` may return any pytree that ``step`` takes.
* ``operands(fn)``, a context manager under which both operands of every
  matrix product and convolution traced inside it are rounded by ``fn``
  (the int8 control).

A counts module provides

* ``image_flops(cfg)``: the FLOPs one served image requires;
* ``attention_calls(cfg, slots)``: ``[("self" | "cross", flops, bytes)]``,
  one entry per fused attention call of one slot step.

Each module declares ``IMPLEMENTS``: for the configuration groups it reads,
the keys it implements and the values it supports.  ``IMPLEMENTS["denoiser"]``
maps each denoiser family to its keys; the other groups (``text``, ``vae``,
``sampler``) map straight to theirs.  Every group of the configuration (a
top-level object) but the harness's own ``serve`` and ``check`` has to be
declared, and every declared group present.  A key's entry is a type (or tuple of
types): any value of it; a list: only those values; or a function of the
value that says whether it is supported (its docstring says what it
wants).  ``refuse_unimplemented`` holds a configuration to both modules'
declarations before any weights are made, so a key that a module would
silently ignore is refused by name instead of reading as a wrong answer.

A new module may import ``reference`` and ``flops`` and reuse their parts.
"""
from __future__ import annotations

import importlib.util
import os
import re
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
DEFAULTS = {"reference": "reference", "counts": "flops"}
GROUPS = ("denoiser", "text", "vae", "sampler")
HARNESS_GROUPS = ("serve", "check")     # read by the harness, not the modules
_NAME = re.compile(r"[A-Za-z_][A-Za-z0-9_]*(/[A-Za-z_][A-Za-z0-9_]*)*")


class Unimplemented(ValueError):
    """A configuration holds a key or value that one of its modules does
    not implement."""


def load(name: str):
    """The module at ``bench/<name>.py``, imported once per process under
    its dotted name (``reference`` is the module ``import reference``
    gives)."""
    if not _NAME.fullmatch(name):
        raise ValueError(f"module name {name!r} is not a path under bench/ "
                         f"made of identifiers and '/'")
    dotted = name.replace("/", ".")
    if dotted not in sys.modules:
        path = os.path.join(HERE, *name.split("/")) + ".py"
        spec = importlib.util.spec_from_file_location(dotted, path)
        mod = importlib.util.module_from_spec(spec)
        sys.modules[dotted] = mod
        try:
            spec.loader.exec_module(mod)
        except BaseException:
            del sys.modules[dotted]
            raise
    return sys.modules[dotted]


def module_name(cfg: dict, role: str) -> str:
    return cfg.get(role, DEFAULTS[role])


def reference(cfg: dict):
    return load(module_name(cfg, "reference"))


def counts(cfg: dict):
    return load(module_name(cfg, "counts"))


def _supports(spec, value) -> bool:
    if isinstance(spec, list):
        return any(type(value) is type(a) and value == a for a in spec)
    if isinstance(spec, (type, tuple)):
        types = spec if isinstance(spec, tuple) else (spec,)
        return isinstance(value, types) and (bool in types
                                             or not isinstance(value, bool))
    return bool(spec(value))


def _wants(spec) -> str:
    if isinstance(spec, list):
        return f"only {spec}"
    if isinstance(spec, (type, tuple)):
        types = spec if isinstance(spec, tuple) else (spec,)
        return "a value of type " + " or ".join(t.__name__ for t in types)
    return " ".join((spec.__doc__ or spec.__name__).split())


def unimplemented(cfg: dict, declared: dict) -> list:
    """What of ``cfg`` a module that declares ``declared`` (its
    ``IMPLEMENTS``) does not implement, one phrase each; empty where it
    implements everything."""
    out = []
    groups = {g for g, v in cfg.items()
              if isinstance(v, dict) and g not in HARNESS_GROUPS}
    for group in sorted(groups | set(GROUPS) | set(declared)):
        if group not in declared:
            out.append(f"does not implement the group {group!r}")
            continue
        keys = declared[group]
        if group not in cfg:
            out.append(f"reads the group {group!r}, which the "
                       f"configuration lacks")
            continue
        values = cfg[group]
        if group == "denoiser":
            family = values.get("family")
            if family not in keys:
                out.append(f"does not implement denoiser.family = "
                           f"{family!r} (it implements {sorted(keys)})")
                continue
            keys = keys[family]
        for key, value in values.items():
            if key not in keys:
                out.append(f"does not implement the key {group}.{key}")
            elif not _supports(keys[key], value):
                out.append(f"does not implement {group}.{key} = {value!r} "
                           f"(it supports {_wants(keys[key])})")
    return out


def refuse_unimplemented(cfg: dict) -> None:
    """Raise ``Unimplemented`` naming each key or value of ``cfg`` that its
    reference or counts module does not implement."""
    lines = [f"module {module_name(cfg, role)!r} {what}"
             for role in DEFAULTS
             for what in unimplemented(cfg, load(module_name(cfg, role))
                                       .IMPLEMENTS)]
    if lines:
        raise Unimplemented("configuration refused: " + "; ".join(lines))
