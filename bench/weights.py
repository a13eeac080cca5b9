"""Weights made by the benchmark from the seed, on the device, in one
jitted call, in the type they are served or trained in.

The tree has the program's parameter shapes (its interface); the values
follow the benchmark's own rules below, so that the reference can use the
same arrays without taking anything the program made.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

BIG = 1 << 26  # leaves larger than this are made one layer at a time


def seed32(seed: int, salt: int = 0) -> int:
    """A 31-bit key for any whole-number seed, 2**31 and above included."""
    return int(np.random.SeedSequence([int(seed), salt]).generate_state(1)[0]
               & 0x7FFFFFFF)


def _leaf_name(path) -> str:
    last = path[-1]
    return str(getattr(last, "key", getattr(last, "name", last)))


def _rule(name: str, shape: tuple, n_layers: int) -> tuple[str, float]:
    """(kind, scale): ``ones`` draws 1 + scale * N(0, 1), ``normal``
    draws scale * N(0, 1)."""
    if name in ("scale", "norm") or name.endswith("_norm"):
        return "ones", 0.1
    if name == "b_f":
        return "ones", 0.1
    if len(shape) <= 1 or name.startswith("b"):
        return "normal", 0.05
    if name == "embed":
        return "normal", 0.02
    if name in ("r", "w_if"):
        return "normal", 0.02
    if name == "conv_w":
        return "normal", 0.5
    fan_in = shape[-2]
    scale = 1.0 / math.sqrt(fan_in)
    if name in ("wo", "down_proj"):
        scale /= math.sqrt(2 * n_layers)
    return "normal", scale


def make_params(abstract, seed: int, n_layers: int, shardings=None):
    """``abstract``: tree of ShapeDtypeStruct (the program's parameter
    shapes and types). Returns the tree of arrays on the device."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(abstract)
    rules = [_rule(_leaf_name(p), tuple(a.shape), n_layers) for p, a in flat]

    def one(key, a, kind, scale):
        def draw(k, shape):
            x = jax.random.normal(k, shape, jnp.float32) * scale
            return (x + 1.0 if kind == "ones" else x).astype(a.dtype)

        if a.size > BIG and len(a.shape) >= 3:
            keys = jax.random.split(key, a.shape[0])
            return jax.lax.map(lambda k: draw(k, a.shape[1:]), keys)
        return draw(key, a.shape)

    def build(key):
        keys = jax.random.split(key, len(flat))
        return jax.tree_util.tree_unflatten(
            treedef,
            [one(keys[i], a, *rules[i]) for i, (_, a) in enumerate(flat)],
        )

    fn = jax.jit(build, out_shardings=shardings)
    return fn(jax.random.PRNGKey(seed32(seed, 1)))
