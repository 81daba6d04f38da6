"""Weights made by the benchmark from ``--seed``, in one jitted call.

The served program and the reference both read these; neither makes its
own.  Only the layout (nested dicts and lists, leaf names and shapes) is the
served program's, taken from ``jax.eval_shape`` of its parameters.  Every
leaf is drawn from the seed by a rule on its name:

* matrices and convolution kernels: normal / sqrt(fan-in), fan-in being the
  product of every axis but the last;
* the token table ``embed``: normal x 0.02; positions ``pos``: normal x 0.01;
* vectors named as biases (``b``, ``bias``, ``*_b``): normal x 0.02;
* other vectors (norm scales): 1 + normal x 0.05.

Biases and scales are random rather than 0 and 1, so a served path that
drops one differs from the reference.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def seed_key(seed: int):
    """A PRNG key from a seed of up to 62 bits (two 31-bit words)."""
    seed = int(seed)
    if not 0 <= seed < 1 << 62:
        raise ValueError(f"--seed {seed} is outside [0, 2**62)")
    key = jax.random.PRNGKey(seed & 0x7FFFFFFF)
    return jax.random.fold_in(key, seed >> 31)


def _leaf_name(path) -> str:
    for entry in reversed(path):
        if isinstance(entry, jax.tree_util.DictKey):
            return str(entry.key)
    return ""


def _draw(key, name: str, shape, dtype):
    z = jax.random.normal(key, shape, jnp.float32)
    if len(shape) >= 2:
        if name == "embed":
            x = 0.02 * z
        elif name == "pos":
            x = 0.01 * z
        else:
            fan_in = 1
            for s in shape[:-1]:
                fan_in *= s
            x = z * fan_in ** -0.5
    elif name in ("b", "bias") or name.endswith("_b"):
        x = 0.02 * z
    else:
        x = 1.0 + 0.05 * z
    return x.astype(dtype)


def make(abstract, seed: int):
    """A pytree like ``abstract`` (ShapeDtypeStructs), drawn on the device."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(abstract)
    names = [_leaf_name(p) for p, _ in flat]
    specs = [(tuple(a.shape), a.dtype) for _, a in flat]

    def build(key):
        keys = jax.random.split(key, len(specs))
        return [_draw(k, n, s, d) for k, n, (s, d) in zip(keys, names, specs)]

    leaves = jax.jit(build)(seed_key(seed))
    return jax.tree_util.tree_unflatten(treedef, leaves)
