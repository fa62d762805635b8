"""Unified decoder LM: dense / MoE / MLA / Mamba / hybrid interleaves.

A model is a sequence of *stages*; each stage is an unrolled pattern of
layers (`LayerSpec`s) scanned ``repeat`` times with stacked params — the
whole 61-to-96-layer model lowers to a handful of ``lax.scan`` ops, which
keeps AOT compile time flat across the 40 dry-run cells.

Layer = pre-norm mixer (attn | mla | mamba) + optional pre-norm FFN
(dense | moe), both residual.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.models import layers as L
from repro.models import mamba as M
from repro.models import mla as MLA
from repro.models import moe as MOE
from repro.parallel.sharding import activation_hint, shard_hint, stack_specs

Params = Dict[str, Any]


@dataclasses.dataclass(frozen=True)
class LayerSpec:
    mixer: str = "attn"            # attn | mla | mamba
    ffn: str = "dense"             # dense | moe | none


@dataclasses.dataclass(frozen=True)
class StageSpec:
    layers: Tuple[LayerSpec, ...]
    repeat: int


@dataclasses.dataclass(frozen=True)
class TransformerCfg:
    name: str
    d_model: int
    vocab_size: int
    stages: Tuple[StageSpec, ...]
    attn: Optional[L.AttentionCfg] = None
    mla: Optional[MLA.MLACfg] = None
    mamba: Optional[M.MambaCfg] = None
    mlp: Optional[L.MLPCfg] = None
    moe: Optional[MOE.MoECfg] = None
    norm: str = "rmsnorm"
    tie_embeddings: bool = False
    embed_inputs: bool = True      # False: caller feeds inputs_embeds (VLM)
    mtp: bool = False              # deepseek-v3 multi-token prediction head
    mtp_loss_weight: float = 0.3
    param_dtype: Any = jnp.float32
    remat: bool = True
    remat_policy: str = "nothing"  # nothing | dots
    block_k: int = 512             # attention kv block

    @property
    def num_layers(self) -> int:
        return sum(len(st.layers) * st.repeat for st in self.stages)


# ---------------------------------------------------------------------------
# Norm dispatch
# ---------------------------------------------------------------------------

def _init_norm(cfg: TransformerCfg, dtype):
    if cfg.norm == "layernorm":
        return L.init_layernorm(cfg.d_model, dtype)
    return L.init_rmsnorm(cfg.d_model, dtype)


def _norm(cfg: TransformerCfg, p, x):
    if cfg.norm == "layernorm":
        return L.layernorm(p, x)
    return L.rmsnorm(p, x)


# ---------------------------------------------------------------------------
# Single layer init/apply
# ---------------------------------------------------------------------------

def init_layer(key, cfg: TransformerCfg, spec: LayerSpec):
    km, kf = jax.random.split(key)
    dt = cfg.param_dtype
    p: Params = {}
    s: Params = {}
    p["norm_mixer"], s["norm_mixer"] = _init_norm(cfg, dt)
    if spec.mixer == "attn":
        p["attn"], s["attn"] = L.init_attention(km, cfg.attn, dt)
    elif spec.mixer == "mla":
        p["mla"], s["mla"] = MLA.init_mla(km, cfg.mla, dt)
    elif spec.mixer == "mamba":
        p["mamba"], s["mamba"] = M.init_mamba(km, cfg.mamba, dt)
    else:
        raise ValueError(spec.mixer)
    if spec.ffn != "none":
        p["norm_ffn"], s["norm_ffn"] = _init_norm(cfg, dt)
        if spec.ffn == "dense":
            p["mlp"], s["mlp"] = L.init_mlp(kf, cfg.mlp, dt)
        elif spec.ffn == "moe":
            p["moe"], s["moe"] = MOE.init_moe(kf, cfg.moe, dt)
        else:
            raise ValueError(spec.ffn)
    return p, s


def _mixer_cache_init(cfg: TransformerCfg, spec: LayerSpec, batch: int,
                      max_len: int, dtype):
    if spec.mixer == "attn":
        return L.init_kv_cache(batch, max_len, cfg.attn, dtype)
    if spec.mixer == "mla":
        return MLA.init_mla_cache(batch, max_len, cfg.mla, dtype)
    return M.init_mamba_cache(batch, cfg.mamba, dtype)


def _mixer_cache_specs(cfg: TransformerCfg, spec: LayerSpec):
    if spec.mixer == "attn":
        return L.kv_cache_specs(cfg.attn)
    if spec.mixer == "mla":
        return MLA.mla_cache_specs()
    return M.mamba_cache_specs()


def apply_layer(params: Params, cfg: TransformerCfg, spec: LayerSpec,
                x: jax.Array, *, positions=None, q_offset=0,
                cache: Optional[Params] = None, decode: bool = False,
                chunked: bool = False, valid_len=None, pages=None
                ) -> Tuple[jax.Array, Optional[Params], jax.Array]:
    """Returns (x_out, new_cache, aux_loss).  ``pages``: (layer, table,
    active) of a paged decode (``decode_paged``), where ``cache`` holds
    the pool's leaves."""
    aux = jnp.zeros((), jnp.float32)
    h = _norm(cfg, params["norm_mixer"], x)
    h = shard_hint(h, P(("pod", "data"), None, None))
    new_cache = None
    if spec.mixer == "attn":
        if pages is not None:
            out, new_cache = L.attention_decode_paged(
                params["attn"], cfg.attn, h, cache, *pages)
        elif decode:
            out, new_cache = L.attention_decode(
                params["attn"], cfg.attn, h, cache, positions=positions)
        else:
            out, new_cache = L.attention_forward(
                params["attn"], cfg.attn, h, positions=positions,
                q_offset=q_offset, kv_cache=cache, block_k=cfg.block_k,
                chunked=chunked, valid_len=valid_len)
    elif spec.mixer == "mla":
        if decode:
            out, new_cache = MLA.mla_decode(params["mla"], cfg.mla, h, cache)
        else:
            out, new_cache = MLA.mla_forward(
                params["mla"], cfg.mla, h, positions=positions,
                q_offset=q_offset, kv_cache=cache, block_k=cfg.block_k,
                chunked=chunked, valid_len=valid_len)
    else:
        if chunked:
            raise ValueError(
                "mamba mixers have value-dependent recurrent state and "
                "no chunked-prefill path (Model.supports_chunked_prefill "
                "gates this)")
        if decode:
            out, new_cache = M.mamba_decode(params["mamba"], cfg.mamba, h,
                                            cache)
        else:
            out, new_cache = M.mamba_forward(params["mamba"], cfg.mamba, h,
                                             cache=cache)
    x = x + out
    if spec.ffn != "none":
        h = _norm(cfg, params["norm_ffn"], x)
        if spec.ffn == "dense":
            y = L.mlp_forward(params["mlp"], cfg.mlp, h)
        else:
            y, aux = MOE.moe_apply(params["moe"], cfg.moe, h)
        x = x + y
    # Layer-boundary constraint: the scan carry (and therefore the saved
    # remat boundary stack) is sequence-sharded over the TP axis.
    x = activation_hint(x)
    return x, new_cache, aux


# ---------------------------------------------------------------------------
# Stage init/apply (stacked params + lax.scan)
# ---------------------------------------------------------------------------

def init_stage(key, cfg: TransformerCfg, stage: StageSpec):
    keys = jax.random.split(key, stage.repeat)

    def one(k):
        ks = jax.random.split(k, len(stage.layers))
        return {f"layer{i}": init_layer(ks[i], cfg, spec)[0]
                for i, spec in enumerate(stage.layers)}

    stacked = jax.vmap(one)(jnp.stack(keys))
    specs = {f"layer{i}": init_layer(key, cfg, spec)[1]
             for i, spec in enumerate(stage.layers)}
    return stacked, stack_specs(specs)


def apply_stage(params_stage: Params, cfg: TransformerCfg, stage: StageSpec,
                x: jax.Array, *, positions=None, q_offset=0,
                caches: Optional[Params] = None, decode: bool = False,
                chunked: bool = False, valid_len=None
                ) -> Tuple[jax.Array, Optional[Params], jax.Array]:
    """Scan the stage's ``repeat`` super-blocks.  ``caches``: stacked cache
    pytree with leading dim = repeat (or None)."""

    def block(x, layer_params, layer_caches):
        new_caches = {} if layer_caches is not None else None
        aux_total = jnp.zeros((), jnp.float32)
        for i, spec in enumerate(stage.layers):
            cache_i = None if layer_caches is None \
                else layer_caches[f"layer{i}"]
            x, nc, aux = apply_layer(
                layer_params[f"layer{i}"], cfg, spec, x,
                positions=positions, q_offset=q_offset, cache=cache_i,
                decode=decode, chunked=chunked, valid_len=valid_len)
            if new_caches is not None:
                new_caches[f"layer{i}"] = nc
            aux_total = aux_total + aux
        return x, new_caches, aux_total

    if cfg.remat:
        policy = (jax.checkpoint_policies.dots_with_no_batch_dims_saveable
                  if cfg.remat_policy == "dots"
                  else jax.checkpoint_policies.nothing_saveable)
        block = jax.checkpoint(block, policy=policy)

    def body(carry, xs):
        x, aux_acc = carry
        layer_params, layer_caches = xs
        x, new_caches, aux = block(x, layer_params, layer_caches)
        return (x, aux_acc + aux), new_caches

    (x, aux_total), new_caches = jax.lax.scan(
        body, (x, jnp.zeros((), jnp.float32)), (params_stage, caches))
    return x, new_caches, aux_total


# ---------------------------------------------------------------------------
# Whole-model init/apply
# ---------------------------------------------------------------------------

def init_params(key, cfg: TransformerCfg):
    ks = jax.random.split(key, len(cfg.stages) + 4)
    dt = cfg.param_dtype
    p: Params = {}
    s: Params = {}
    if cfg.embed_inputs:
        p["embed"] = L.embed_init(ks[0], (cfg.vocab_size, cfg.d_model), dt)
        s["embed"] = P("model", "data")
    for i, stage in enumerate(cfg.stages):
        p[f"stage{i}"], s[f"stage{i}"] = init_stage(ks[i + 1], cfg, stage)
    p["final_norm"], s["final_norm"] = _init_norm(cfg, dt)
    if not cfg.tie_embeddings:
        p["lm_head"] = L.dense_init(ks[-3], (cfg.d_model, cfg.vocab_size), dt)
        s["lm_head"] = P("data", "model")
    if cfg.mtp:
        p["mtp_norm1"], s["mtp_norm1"] = _init_norm(cfg, dt)
        p["mtp_norm2"], s["mtp_norm2"] = _init_norm(cfg, dt)
        p["mtp_proj"] = L.dense_init(ks[-2], (2 * cfg.d_model, cfg.d_model),
                                     dt, fan_in=2 * cfg.d_model)
        s["mtp_proj"] = P(None, "data")
        mtp_spec = cfg.stages[-1].layers[-1]
        p["mtp_block"], s["mtp_block"] = init_layer(ks[-1], cfg, mtp_spec)
    return p, s


def _embed(params, cfg: TransformerCfg, batch: Dict[str, jax.Array]
           ) -> jax.Array:
    if cfg.embed_inputs:
        h = params["embed"][batch["tokens"]]
    else:
        h = batch["inputs_embeds"].astype(cfg.param_dtype)
    return shard_hint(h, P(("pod", "data"), None, None))


def _unembed(params, cfg: TransformerCfg, h: jax.Array) -> jax.Array:
    if cfg.tie_embeddings:
        logits = h @ params["embed"].T
    else:
        logits = h @ params["lm_head"]
    return shard_hint(logits, P(("pod", "data"), None, "model"))


def forward(params: Params, cfg: TransformerCfg, batch: Dict[str, jax.Array],
            *, caches: Optional[Params] = None, q_offset=0,
            decode: bool = False, chunked: bool = False, valid_len=None
            ) -> Tuple[jax.Array, Optional[Params], jax.Array]:
    """Returns (hidden (B,S,D), new_caches, aux_loss)."""
    h = _embed(params, cfg, batch)
    positions = batch.get("positions")
    aux_total = jnp.zeros((), jnp.float32)
    new_caches = {} if caches is not None else None
    for i in range(len(cfg.stages)):
        cache_i = None if caches is None else caches[f"stage{i}"]
        h, nc, aux = apply_stage(
            params[f"stage{i}"], cfg, cfg.stages[i], h,
            positions=positions, q_offset=q_offset, caches=cache_i,
            decode=decode, chunked=chunked, valid_len=valid_len)
        if new_caches is not None:
            new_caches[f"stage{i}"] = nc
        aux_total = aux_total + aux
    h = _norm(cfg, params["final_norm"], h)
    return h, new_caches, aux_total


def _decode_stage_paged(params_stage: Params, cfg: TransformerCfg,
                        stage: StageSpec, x: jax.Array, caches: Params,
                        table: jax.Array, active: jax.Array
                        ) -> Tuple[jax.Array, Params]:
    """One decode token through a stage of GQA layers whose K/V live in
    the page pool.  ``caches[f"layer{j}"]``: "k"/"v" pool leaves
    ``(num_pages + 1, page_tokens, repeat, Hkv, Dh)`` and "len" (repeat,
    B).  The pool leaves are scan constants read at the layer counter (no
    per-layer slice is made).  Returns the stage's output and, per layer,
    the new token's "k"/"v" (repeat, B, Hkv, Dh) and the advanced
    "len"."""
    names = [f"layer{j}" for j in range(len(stage.layers))]

    def body(x, xs):
        layer_params, lens, layer = xs
        out = {}
        for name, spec in zip(names, stage.layers):
            cache = {"k": caches[name]["k"], "v": caches[name]["v"],
                     "len": lens[name]}
            x, out[name], _ = apply_layer(
                layer_params[name], cfg, spec, x, cache=cache, decode=True,
                pages=(layer, table, active))
        return x, out

    return jax.lax.scan(
        body, x, (params_stage, {n: caches[n]["len"] for n in names},
                  jnp.arange(stage.repeat)))


def decode_paged(params: Params, cfg: TransformerCfg,
                 batch: Dict[str, jax.Array], caches: Params,
                 table: jax.Array, active: jax.Array
                 ) -> Tuple[jax.Array, Params]:
    """One decode token per slot of an all-GQA model whose K/V live in the
    page pool (``repro.serve.paging``).  ``caches`` mirrors
    ``init_caches``' tree with each "k"/"v" the pool leaf and each "len"
    the slot counters; ``table`` (B, pages_per_slot) page ids; ``active``
    (B,) bool.  Returns (hidden (B, 1, D), the same tree with each "k"/"v"
    the new token's (layers, B, Hkv, Dh) and each "len" advanced for the
    active slots); the pool writes the tokens into their pages."""
    h = _embed(params, cfg, batch)
    new_caches = {}
    for i, stage in enumerate(cfg.stages):
        h, new_caches[f"stage{i}"] = _decode_stage_paged(
            params[f"stage{i}"], cfg, stage, h, caches[f"stage{i}"], table,
            active)
    return _norm(cfg, params["final_norm"], h), new_caches


def logits_fn(params: Params, cfg: TransformerCfg,
              batch: Dict[str, jax.Array]) -> jax.Array:
    h, _, _ = forward(params, cfg, batch)
    return _unembed(params, cfg, h)


def cross_entropy(logits: jax.Array, labels: jax.Array,
                  mask: Optional[jax.Array] = None) -> jax.Array:
    """Mean token NLL in f32; labels < 0 are ignored.

    The label log-prob is extracted with a one-hot contraction, NOT
    take_along_axis: a vocab-gather over model-sharded logits would force
    GSPMD to all-gather the (B, S, V) tensor, while the one-hot product
    reduces over the sharded vocab dim in place (partial sums + a scalar
    all-reduce)."""
    lf = logits.astype(jnp.float32)
    lse = jax.nn.logsumexp(lf, axis=-1)
    onehot = jax.nn.one_hot(jnp.maximum(labels, 0), logits.shape[-1],
                            dtype=jnp.float32)
    ll = jnp.sum(lf * onehot, axis=-1)
    nll = lse - ll
    valid = (labels >= 0) if mask is None else mask & (labels >= 0)
    nll = jnp.where(valid, nll, 0.0)
    return jnp.sum(nll) / jnp.maximum(jnp.sum(valid), 1)


def loss_fn(params: Params, cfg: TransformerCfg,
            batch: Dict[str, jax.Array]) -> Tuple[jax.Array, Dict]:
    """Language-model loss (+ MoE aux + optional MTP)."""
    h, _, aux = forward(params, cfg, batch)
    logits = _unembed(params, cfg, h)
    labels = batch["labels"]
    loss = cross_entropy(logits, labels)
    metrics = {"nll": loss, "aux": aux}
    if cfg.mtp and cfg.embed_inputs:
        # Predict token t+2 from h_t combined with embed(token_{t+1}).
        emb_next = params["embed"][batch["tokens"]][:, 1:]      # (B,S-1,D)
        h_in = jnp.concatenate(
            [_norm(cfg, params["mtp_norm1"], h[:, :-1]),
             _norm(cfg, params["mtp_norm2"], emb_next)], axis=-1)
        h_mtp = h_in @ params["mtp_proj"]
        mtp_spec = cfg.stages[-1].layers[-1]
        h_mtp, _, aux2 = apply_layer(params["mtp_block"], cfg, mtp_spec,
                                     h_mtp)
        logits_mtp = _unembed(params, cfg, h_mtp)
        mtp_loss = cross_entropy(logits_mtp, labels[:, 1:])
        loss = loss + cfg.mtp_loss_weight * mtp_loss
        aux = aux + aux2
        metrics["mtp"] = mtp_loss
    total = loss + aux
    metrics["loss"] = total
    return total, metrics


# ---------------------------------------------------------------------------
# Caches
# ---------------------------------------------------------------------------

def init_caches(cfg: TransformerCfg, batch: int, max_len: int,
                dtype=jnp.bfloat16) -> Params:
    caches: Params = {}
    for i, stage in enumerate(cfg.stages):
        def one_block(_):
            return {f"layer{j}": _mixer_cache_init(cfg, spec, batch,
                                                   max_len, dtype)
                    for j, spec in enumerate(stage.layers)}
        caches[f"stage{i}"] = jax.vmap(one_block)(jnp.arange(stage.repeat))
    return caches


def cache_specs(cfg: TransformerCfg) -> Params:
    specs: Params = {}
    for i, stage in enumerate(cfg.stages):
        block = {f"layer{j}": _mixer_cache_specs(cfg, spec)
                 for j, spec in enumerate(stage.layers)}
        specs[f"stage{i}"] = stack_specs(block)
    return specs
