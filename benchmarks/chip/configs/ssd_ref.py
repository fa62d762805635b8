"""Plain float32 reference of a Mamba-2 language model (arXiv:2405.21060):
embedding, a stack of pre-norm residual blocks whose mixer is the Mamba-2
layer, a final RMSNorm, and the head tied to the embedding.  The loss is
the mean next-token cross entropy over every position.

The mixer: one input projection to (z, x, B, C, dt); a depthwise causal
conv (width d_conv, with bias) and SiLU over (x, B, C); dt = softplus(dt +
dt_bias); the SSD scan with A = -exp(A_log) written as the paper's
minimal chunked listing (``ssd_minimal_discrete``); the D skip; a gated
RMSNorm, norm(y * silu(z)); the output projection.

Departures from the published model: none in the equations.  The norm
epsilon is the configuration file's ``norm_epsilon``.

Weights are read by the names of the program's parameter tree (layers
stacked on the leading axis) and made by ``weights.py`` from the seed.
``mode`` "f32": every product in float32 at the highest matmul
precision; "fp8": the control, every matrix product's inputs rounded to
float8 (e4m3) first.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

HI = jax.lax.Precision.HIGHEST


def _round(x, mode):
    x = x.astype(jnp.float32)
    if mode == "fp8":
        x = x.astype(jnp.float8_e4m3fn).astype(jnp.float32)
    return x


def mm(x, w, mode):
    return jnp.matmul(_round(x, mode), _round(w, mode), precision=HI)


def ein(spec, *xs, mode):
    return jnp.einsum(spec, *[_round(x, mode) for x in xs], precision=HI)


def rmsnorm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * w.astype(jnp.float32)


def segsum(x):
    """(..., T) -> (..., T, T): out[i, j] = x[j+1] + ... + x[i] for i >= j,
    -inf above the diagonal."""
    t = x.shape[-1]
    x = jnp.repeat(x[..., None], t, axis=-1)
    x = jnp.where(jnp.tril(jnp.ones((t, t), bool), -1), x, 0.0)
    x = jnp.cumsum(x, axis=-2)
    return jnp.where(jnp.tril(jnp.ones((t, t), bool)), x, -jnp.inf)


def ssd(X, A, B, C, block, mode):
    """The paper's minimal SSD.  X: (b, l, h, p) (already times dt);
    A: (b, l, h) (already times dt); B, C: (b, l, h, n).  l % block == 0."""
    b, l, h, p = X.shape
    c = l // block

    def chunks(x):
        return x.reshape(b, c, block, *x.shape[2:])

    X, A, B, C = chunks(X), chunks(A), chunks(B), chunks(C)
    A = jnp.transpose(A, (0, 3, 1, 2))                  # b h c l
    A_cs = jnp.cumsum(A, -1)
    L = jnp.exp(segsum(A))
    y_diag = ein("bclhn,bcshn,bhcls,bcshp->bclhp", C, B, L, X, mode=mode)
    decay = jnp.exp(A_cs[..., -1:] - A_cs)
    states = ein("bclhn,bhcl,bclhp->bchpn", B, decay, X, mode=mode)
    states = jnp.concatenate([jnp.zeros_like(states[:, :1]), states], 1)
    dchunk = jnp.exp(segsum(jnp.pad(A_cs[..., -1], ((0, 0), (0, 0),
                                                     (1, 0)))))
    states = ein("bhzc,bchpn->bzhpn", dchunk, states, mode=mode)[:, :-1]
    y_off = ein("bclhn,bchpn,bhcl->bclhp", C, states, jnp.exp(A_cs),
                mode=mode)
    return (y_diag + y_off).reshape(b, l, h, p)


def mixer(p, x, c, mode):
    b, l, _ = x.shape
    di = c["expand"] * c["d_model"]
    g, n, hp = c["ngroups"], c["d_state"], c["headdim"]
    h = di // hp
    zxbcdt = mm(x, p["in_proj"], mode)
    z, xbc, dt = (zxbcdt[..., :di], zxbcdt[..., di:2 * di + 2 * g * n],
                  zxbcdt[..., 2 * di + 2 * g * n:])
    w = p["conv_w"].astype(jnp.float32)                 # (d_conv, channels)
    k = w.shape[0]
    xp = jnp.pad(xbc, ((0, 0), (k - 1, 0), (0, 0)))
    conv = sum(xp[:, i:i + l] * w[i] for i in range(k))
    xbc = jax.nn.silu(conv + p["conv_b"].astype(jnp.float32))
    xs = xbc[..., :di].reshape(b, l, h, hp)
    Bm = jnp.repeat(xbc[..., di:di + g * n].reshape(b, l, g, n), h // g, 2)
    Cm = jnp.repeat(xbc[..., di + g * n:].reshape(b, l, g, n), h // g, 2)
    dt = jax.nn.softplus(dt + p["dt_bias"].astype(jnp.float32))
    A = -jnp.exp(p["A_log"].astype(jnp.float32))
    y = ssd(xs * dt[..., None], A * dt, Bm, Cm, c["chunk_size"], mode)
    y = y + xs * p["D"].astype(jnp.float32)[:, None]
    y = rmsnorm(y.reshape(b, l, di) * jax.nn.silu(z), p["norm"]["scale"],
                c["norm_epsilon"])
    return mm(y, p["out_proj"], mode)


def _xent(h, emb, labels, mode):
    """Summed next-token NLL of one row; (S, D), (V, D), (S,)."""
    lg = mm(h, emb.T, mode)
    return jnp.sum(jax.nn.logsumexp(lg, -1)
                   - jnp.take_along_axis(lg, labels[:, None], -1)[:, 0])


def loss(params, c, batch, mode="f32"):
    """Mean cross entropy of a (B, S) batch; each layer and each row's
    loss recomputed in the backward pass, so that it fits the chips."""
    emb = params["embed"]
    x = emb[batch["tokens"]].astype(jnp.float32)

    @jax.checkpoint
    def block(x, lp):
        y = rmsnorm(x, lp["norm_mixer"]["scale"], c["norm_epsilon"])
        return x + mixer(lp["mamba"], y, c, mode), None

    x, _ = jax.lax.scan(block, x, params["stage0"]["layer0"])
    x = rmsnorm(x, params["final_norm"]["scale"], c["norm_epsilon"])
    rows = jax.lax.map(
        lambda a: jax.checkpoint(_xent, static_argnums=3)(a[0], emb, a[1],
                                                          mode),
        (x, batch["labels"]))
    return jnp.sum(rows) / batch["labels"].size
