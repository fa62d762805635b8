"""Weights from the run's seed, made by the benchmark, not the program.

The program only says where each weight sits (its parameter tree's paths,
shapes and dtypes); every value comes from here, in one jitted call on
the device, so the plain reference can make the very same weights again
without taking anything from the program.
"""

from __future__ import annotations

import math
import zlib

import jax
import jax.numpy as jnp

import harness

BLOCK = 1 << 26                      # elements drawn per block of rows


def key_for(seed: int):
    """A PRNG key from any seed a driver may pass (beyond 32 bits too)."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF),
                              (seed >> 32) & 0xFFFFFFFF)


def _name(path) -> str:
    return str(getattr(path[-1], "key", path[-1]))


def _normal(key, shape, std, dtype):
    """N(0, std^2) in ``dtype``, drawn in f32 a block of rows at a time so
    that no f32 copy of a large weight is ever whole."""
    n = math.prod(shape)
    if n <= BLOCK or len(shape) < 2:
        return (jax.random.normal(key, shape, jnp.float32) * std
                ).astype(dtype)
    flat = (shape[0] * shape[1], *shape[2:]) if len(shape) > 2 else shape
    rows = flat[0]
    per = max(1, BLOCK // (n // rows))
    while rows % per:
        per -= 1
    blocks = jax.lax.map(
        lambda i: (jax.random.normal(jax.random.fold_in(key, i),
                                     (per, *flat[1:]), jnp.float32) * std
                   ).astype(dtype),
        jnp.arange(rows // per))
    return blocks.reshape(shape)


def leaf(key, path, s: jax.ShapeDtypeStruct, rules: dict):
    """One weight, drawn by the rule its family gives its last name:
    "fan_in" (normal with std 1/sqrt of the input size), a number (normal
    with that std), "ones", or a function of (key, shape, dtype)."""
    name = _name(path)
    k = jax.random.fold_in(key, zlib.crc32(jax.tree_util.keystr(path)
                                           .encode()))
    shape, dt = s.shape, s.dtype
    if name not in rules:
        raise KeyError(f"no rule for the weight {jax.tree_util.keystr(path)}")
    rule = rules[name]
    if rule == "fan_in":
        return _normal(k, shape, 1.0 / math.sqrt(shape[-2]), dt)
    if rule == "ones":
        return jnp.ones(shape, dt)
    if callable(rule):
        return rule(k, shape, dt)
    return _normal(k, shape, float(rule), dt)


def make(key, abstract, config: dict):
    """The whole parameter tree of ``abstract``'s structure from ``key``
    (``key_for(seed)``; call under ``jax.jit`` with the key traced, so
    that every seed runs the one compiled program), by the rules of the
    family that ``config`` names."""
    rules = harness.family(config).WEIGHTS
    return jax.tree_util.tree_map_with_path(
        lambda p, s: leaf(key, p, s, rules), abstract)
