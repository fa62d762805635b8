"""Plain float32 reference of the optimizer a training mix names, with
the hyperparameters the mix's file states: AdamW (Loshchilov and Hutter,
arXiv:1711.05101) after clipping the gradient to a global norm, weight
decay on every parameter.  The learning rate follows a linear warm-up and
a cosine decay to ``min_ratio`` of its peak.  Parameters are stored in
their own dtype after every update, as the configuration states them.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def lr_at(o: dict, t):
    t = jnp.asarray(t, jnp.float32)
    warm = o["lr"] * jnp.minimum(t / max(o["warmup"], 1), 1.0)
    frac = jnp.clip((t - o["warmup"]) / max(o["total"] - o["warmup"], 1),
                    0.0, 1.0)
    cos = o["min_ratio"] + (1 - o["min_ratio"]) * 0.5 * (1 + jnp.cos(
        jnp.pi * frac))
    return jnp.where(t < o["warmup"], warm, o["lr"] * cos)


def init(o: dict, params):
    if o["name"] != "adamw":
        raise ValueError(f"no reference for the optimizer {o['name']!r}")
    z = lambda p: jnp.zeros(p.shape, jnp.float32)  # noqa: E731
    return {"m": jax.tree_util.tree_map(z, params),
            "v": jax.tree_util.tree_map(z, params)}


def global_norm(tree):
    return jnp.sqrt(sum(jnp.sum(jnp.square(g))
                        for g in jax.tree_util.tree_leaves(tree)))


def update(o: dict, grads, state, params, t):
    """One step at 1-based step ``t``: (new params, new state, the
    gradient as the optimizer used it)."""
    lr = lr_at(o, t)
    grads = jax.tree_util.tree_map(lambda g: g.astype(jnp.float32), grads)
    if o["clip_norm"]:
        n = global_norm(grads)
        s = jnp.minimum(1.0, o["clip_norm"] / jnp.maximum(n, 1e-9))
        grads = jax.tree_util.tree_map(lambda g: g * s, grads)
    b1, b2 = o["b1"], o["b2"]
    bc1, bc2 = 1 - b1 ** t, 1 - b2 ** t

    def leaf(p, g, m, v):
        m = b1 * m + (1 - b1) * g
        v = b2 * v + (1 - b2) * g * g
        pf = p.astype(jnp.float32)
        pf = pf - lr * ((m / bc1) / (jnp.sqrt(v / bc2) + o["eps"])
                        + o["weight_decay"] * pf)
        return pf.astype(p.dtype), m, v
    out = jax.tree_util.tree_map(leaf, params, grads, state["m"], state["v"])
    pick = lambda i: jax.tree_util.tree_map(  # noqa: E731
        lambda x: x[i], out, is_leaf=lambda x: isinstance(x, tuple))
    return pick(0), {"m": pick(1), "v": pick(2)}, grads
