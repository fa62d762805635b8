"""Mamba2 (state-space duality / SSD, arXiv:2405.21060) — chunked matmul form.

TPU adaptation: the SSD algorithm is exactly its MXU-native formulation —
the inner recurrence is re-expressed as (a) an intra-chunk "attention-like"
masked matmul S = (C·Bᵀ) ∘ decay, (b) per-chunk boundary states via
matmuls, and (c) a short scan over chunk boundaries.  Everything heavy is
a dense contraction; the sequential part is S/chunk_len steps long.

Decode is the O(1) recurrent step on a persistent (H, P, N) state —
attention-free, so the 500k-token shapes run at constant memory.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.models import layers as L


@dataclasses.dataclass(frozen=True)
class MambaCfg:
    d_model: int
    d_state: int = 128          # N
    expand: int = 2
    headdim: int = 64           # P
    ngroups: int = 1            # G (B/C projections shared per group)
    d_conv: int = 4
    chunk: int = 128            # SSD chunk length Q

    @property
    def d_inner(self) -> int:
        return self.expand * self.d_model

    @property
    def nheads(self) -> int:
        return self.d_inner // self.headdim

    @property
    def conv_channels(self) -> int:
        return self.d_inner + 2 * self.ngroups * self.d_state

    @property
    def proj_width(self) -> int:
        return 2 * self.d_inner + 2 * self.ngroups * self.d_state + self.nheads


def init_mamba(key, cfg: MambaCfg, dtype=jnp.float32):
    ks = jax.random.split(key, 4)
    D = cfg.d_model
    p = {
        "in_proj": L.dense_init(ks[0], (D, cfg.proj_width), dtype),
        "conv_w": (jax.random.normal(ks[1], (cfg.d_conv, cfg.conv_channels),
                                     jnp.float32)
                   / math.sqrt(cfg.d_conv)).astype(dtype),
        "conv_b": jnp.zeros((cfg.conv_channels,), dtype),
        "A_log": jnp.log(jnp.linspace(1.0, 16.0, cfg.nheads)
                         ).astype(jnp.float32),
        "D": jnp.ones((cfg.nheads,), jnp.float32),
        "dt_bias": jnp.log(jnp.expm1(
            jnp.exp(jax.random.uniform(
                ks[2], (cfg.nheads,), jnp.float32,
                math.log(1e-3), math.log(1e-1))))),
        "out_proj": L.dense_init(ks[3], (cfg.d_inner, D), dtype,
                                 fan_in=cfg.d_inner),
    }
    p["norm"], _ = L.init_rmsnorm(cfg.d_inner, dtype)
    s = {
        "in_proj": P("data", "model"),
        "conv_w": P(None, "model"),
        "conv_b": P("model"),
        "A_log": P("model"),
        "D": P("model"),
        "dt_bias": P("model"),
        "out_proj": P("model", "data"),
        "norm": {"scale": P(None)},
    }
    return p, s


def _split_proj(cfg: MambaCfg, zxbcdt: jax.Array):
    di, gn = cfg.d_inner, cfg.ngroups * cfg.d_state
    z = zxbcdt[..., :di]
    xbc = zxbcdt[..., di:di + di + 2 * gn]
    dt = zxbcdt[..., di + di + 2 * gn:]
    return z, xbc, dt


def _causal_conv(xbc: jax.Array, w: jax.Array, b: jax.Array,
                 tail: Optional[jax.Array] = None) -> jax.Array:
    """Depthwise causal conv1d.  xbc: (B, S, C); w: (K, C).  ``tail``:
    (B, K-1, C) state from a previous segment (decode/prefill chaining)."""
    k = w.shape[0]
    if tail is None:
        tail = jnp.zeros((xbc.shape[0], k - 1, xbc.shape[2]), xbc.dtype)
    xp = jnp.concatenate([tail, xbc], axis=1)
    out = sum(xp[:, i:i + xbc.shape[1]] * w[i] for i in range(k))
    return jax.nn.silu(out + b)


def _segsum(log_a: jax.Array) -> jax.Array:
    """(..., Q) -> (..., Q, Q) with out[t, s] = sum_{r=s+1..t} log_a_r
    for t >= s, -inf above the diagonal."""
    q = log_a.shape[-1]
    cs = jnp.cumsum(log_a, axis=-1)
    diff = cs[..., :, None] - cs[..., None, :]
    tri = jnp.tril(jnp.ones((q, q), bool))
    return jnp.where(tri, diff, -jnp.inf)


def ssd_chunked(x: jax.Array, dt: jax.Array, A: jax.Array, Bm: jax.Array,
                Cm: jax.Array, chunk: int,
                h0: Optional[jax.Array] = None
                ) -> Tuple[jax.Array, jax.Array]:
    """SSD scan in chunked matmul form.

    x: (B, S, H, P); dt: (B, S, H); A: (H,) negative; Bm/Cm: (B, S, G, N).
    h0: optional initial state (B, H, P, N).  Returns (y (B,S,H,P),
    h_final (B,H,P,N)).  A sequence that is not a multiple of ``chunk`` is
    right-padded with dt = 0 steps, which neither decay nor feed the state.
    """
    s_in = x.shape[1]
    pad = (-s_in) % chunk
    if pad:
        def rpad(a):
            return jnp.pad(a, [(0, 0), (0, pad)] + [(0, 0)] * (a.ndim - 2))
        x, dt, Bm, Cm = rpad(x), rpad(dt), rpad(Bm), rpad(Cm)
    b, s, h, pdim = x.shape
    g, n = Bm.shape[2], Bm.shape[3]
    rep = h // g
    nc = s // chunk

    xc = x.reshape(b, nc, chunk, h, pdim).astype(jnp.float32)
    dtc = dt.reshape(b, nc, chunk, h).astype(jnp.float32)
    Bc = Bm.reshape(b, nc, chunk, g, n).astype(jnp.float32)
    Cc = Cm.reshape(b, nc, chunk, g, n).astype(jnp.float32)
    Bh = jnp.repeat(Bc, rep, axis=3)          # (B,nc,Q,H,N)
    Ch = jnp.repeat(Cc, rep, axis=3)

    la = dtc * A                               # (B,nc,Q,H) log-decay, <= 0
    la_t = jnp.moveaxis(la, -1, 2)             # (B,nc,H,Q)
    Lseg = jnp.exp(_segsum(la_t))              # (B,nc,H,Q,Q)
    xdt = xc * dtc[..., None]                  # dt folded into inputs

    # (a) intra-chunk: S_ts = (C_t . B_s) * L_ts, Y_diag = S @ xdt
    scores = jnp.einsum("bcqhn,bcshn->bchqs", Ch, Bh) * Lseg
    y_diag = jnp.einsum("bchqs,bcshp->bcqhp", scores, xdt)

    # (b) per-chunk final states: H_c = sum_s exp(sum_{r>s} la) * B_s^T xdt_s
    cs_full = jnp.cumsum(la_t, axis=-1)                    # (B,nc,H,Q)
    decay_states = jnp.exp(cs_full[..., -1:] - cs_full)    # (B,nc,H,Q)
    states = jnp.einsum("bcshn,bchs,bcshp->bchpn",
                        Bh, decay_states, xdt)             # (B,nc,H,P,N)

    # (c) inter-chunk recurrence over chunk boundaries.
    chunk_decay = jnp.exp(cs_full[..., -1])                # (B,nc,H)
    if h0 is None:
        h0 = jnp.zeros((b, h, pdim, n), jnp.float32)
    else:
        h0 = h0.astype(jnp.float32)

    def scan_fn(carry, inp):
        st, dec = inp                                      # (B,H,P,N),(B,H)
        new = carry * dec[..., None, None] + st
        return new, carry                                  # emit state BEFORE

    h_final, h_prevs = jax.lax.scan(
        scan_fn, h0,
        (jnp.moveaxis(states, 1, 0), jnp.moveaxis(chunk_decay, 1, 0)))
    h_prevs = jnp.moveaxis(h_prevs, 0, 1)                  # (B,nc,H,P,N)

    # (d) contribution of carried state: y_off[t] = exp(cs[t]) * C_t . H_prev
    state_decay_in = jnp.exp(cs_full)                      # (B,nc,H,Q)
    y_off = jnp.einsum("bcqhn,bchpn,bchq->bcqhp",
                       Ch, h_prevs, state_decay_in)
    y = (y_diag + y_off).reshape(b, s, h, pdim)[:, :s_in]
    return y.astype(x.dtype), h_final


def mamba_forward(params, cfg: MambaCfg, x: jax.Array, *,
                  cache: Optional[Dict[str, jax.Array]] = None
                  ) -> Tuple[jax.Array, Optional[Dict[str, jax.Array]]]:
    """Full-sequence path (train / prefill).  x: (B, S, D)."""
    b, s, d = x.shape
    zxbcdt = x @ params["in_proj"]
    z, xbc_raw, dt = _split_proj(cfg, zxbcdt)
    conv_tail = None if cache is None else cache["conv"]
    xbc = _causal_conv(xbc_raw, params["conv_w"], params["conv_b"], conv_tail)
    di, gn = cfg.d_inner, cfg.ngroups * cfg.d_state
    xs = xbc[..., :di].reshape(b, s, cfg.nheads, cfg.headdim)
    Bm = xbc[..., di:di + gn].reshape(b, s, cfg.ngroups, cfg.d_state)
    Cm = xbc[..., di + gn:].reshape(b, s, cfg.ngroups, cfg.d_state)
    dt = jax.nn.softplus(dt.astype(jnp.float32) + params["dt_bias"])
    A = -jnp.exp(params["A_log"])

    h0 = None if cache is None else cache["ssm"]
    y, h_final = ssd_chunked(xs, dt, A, Bm, Cm, cfg.chunk, h0)
    y = y + xs.astype(jnp.float32) * params["D"][:, None]
    y = y.reshape(b, s, di).astype(x.dtype)
    y = L.rmsnorm(params["norm"], y * jax.nn.silu(z))
    out = y @ params["out_proj"]

    new_cache = None
    if cache is not None:
        tail_src = jnp.concatenate([cache["conv"], xbc_raw], axis=1)
        new_cache = {"conv": tail_src[:, -(cfg.d_conv - 1):],
                     "ssm": h_final}
    return out, new_cache


def mamba_decode(params, cfg: MambaCfg, x: jax.Array,
                 cache: Dict[str, jax.Array]
                 ) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """One-token recurrent step.  x: (B, 1, D); O(1) in sequence length."""
    b = x.shape[0]
    di, gn = cfg.d_inner, cfg.ngroups * cfg.d_state
    zxbcdt = x @ params["in_proj"]
    z, xbc_new, dt = _split_proj(cfg, zxbcdt)

    window = jnp.concatenate([cache["conv"], xbc_new], axis=1)  # (B,K,C)
    conv_out = jnp.einsum("bkc,kc->bc", window, params["conv_w"])
    xbc = jax.nn.silu(conv_out + params["conv_b"])[:, None, :]

    xs = xbc[..., :di].reshape(b, cfg.nheads, cfg.headdim)
    Bm = xbc[..., di:di + gn].reshape(b, cfg.ngroups, cfg.d_state)
    Cm = xbc[..., di + gn:].reshape(b, cfg.ngroups, cfg.d_state)
    rep = cfg.nheads // cfg.ngroups
    Bh = jnp.repeat(Bm, rep, axis=1)
    Ch = jnp.repeat(Cm, rep, axis=1)
    dt = jax.nn.softplus(dt[:, 0].astype(jnp.float32) + params["dt_bias"])
    a = jnp.exp(dt * -jnp.exp(params["A_log"]))             # (B,H)

    h = cache["ssm"].astype(jnp.float32)
    h = (h * a[..., None, None]
         + jnp.einsum("bhp,bhn,bh->bhpn", xs.astype(jnp.float32),
                      Bh.astype(jnp.float32), dt))
    y = jnp.einsum("bhpn,bhn->bhp", h, Ch.astype(jnp.float32))
    y = y + xs.astype(jnp.float32) * params["D"][:, None]
    y = y.reshape(b, 1, di).astype(x.dtype)
    y = L.rmsnorm(params["norm"], y * jax.nn.silu(z))
    out = y @ params["out_proj"]
    new_cache = {"conv": window[:, 1:], "ssm": h}
    return out, new_cache


def init_mamba_cache(batch: int, cfg: MambaCfg, dtype=jnp.bfloat16
                     ) -> Dict[str, jax.Array]:
    return {
        "conv": jnp.zeros((batch, cfg.d_conv - 1, cfg.conv_channels), dtype),
        "ssm": jnp.zeros((batch, cfg.nheads, cfg.headdim, cfg.d_state),
                         jnp.float32),
    }


def mamba_cache_specs() -> Dict[str, P]:
    return {"conv": P(("pod", "data"), None, "model"),
            "ssm": P(("pod", "data"), "model", None, None)}
