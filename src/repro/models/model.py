"""build_model: uniform API over decoder-only and encoder-decoder stacks.

The Model object is what the substrate layers (train/serve/launch) consume:
  init / abstract_params / param_specs     — parameters
  loss                                      — training objective
  init_caches / cache_specs / prefill / decode_step — serving
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, Optional, Tuple, Union

import jax
import jax.numpy as jnp

from repro.models import encdec as ED
from repro.models import transformer as T

Params = Dict[str, Any]
Cfg = Union[T.TransformerCfg, ED.EncDecCfg]


@dataclasses.dataclass
class Model:
    cfg: Cfg

    @property
    def kind(self) -> str:
        return "encdec" if isinstance(self.cfg, ED.EncDecCfg) else "decoder"

    @property
    def name(self) -> str:
        return self.cfg.name

    # -- parameters -----------------------------------------------------

    def init(self, rng) -> Params:
        if self.kind == "encdec":
            return ED.init_params(rng, self.cfg)[0]
        return T.init_params(rng, self.cfg)[0]

    def abstract_params(self) -> Params:
        return jax.eval_shape(lambda: self.init(jax.random.PRNGKey(0)))

    def param_specs(self) -> Params:
        if self.kind == "encdec":
            return _specs_encdec(self.cfg)
        return _specs_decoder(self.cfg)

    def param_count(self) -> int:
        import math
        tree = self.abstract_params()
        return sum(math.prod(l.shape)
                   for l in jax.tree_util.tree_leaves(tree))

    # -- training ---------------------------------------------------------

    def loss(self, params: Params, batch: Dict[str, jax.Array]
             ) -> Tuple[jax.Array, Dict]:
        if self.kind == "encdec":
            return ED.loss_fn(params, self.cfg, batch)
        return T.loss_fn(params, self.cfg, batch)

    def logits(self, params: Params, batch: Dict[str, jax.Array]):
        if self.kind == "encdec":
            memory = ED.encode(params, self.cfg, batch["frame_embeds"])
            return ED.decode_train(params, self.cfg, batch["tokens"], memory)
        return T.logits_fn(params, self.cfg, batch)

    # -- serving ----------------------------------------------------------

    def init_caches(self, batch: int, max_len: int, *, enc_len: int = 0,
                    dtype=jnp.bfloat16) -> Params:
        if self.kind == "encdec":
            return ED.init_caches(self.cfg, batch, max_len, enc_len, dtype)
        return T.init_caches(self.cfg, batch, max_len, dtype)

    def cache_specs(self) -> Params:
        if self.kind == "encdec":
            return ED.cache_specs(self.cfg)
        return T.cache_specs(self.cfg)

    def prefill(self, params: Params, batch: Dict[str, jax.Array],
                caches: Params) -> Tuple[jax.Array, Params]:
        """Fill the cache from a prompt; returns (last-position logits,
        caches)."""
        if self.kind == "encdec":
            return ED.prefill(params, self.cfg, batch, caches)
        h, new_caches, _ = T.forward(params, self.cfg, batch, caches=caches,
                                     q_offset=0, decode=False)
        logits = T._unembed(params, self.cfg, h[:, -1:])
        return logits[:, 0], new_caches

    @property
    def supports_chunked_prefill(self) -> bool:
        """Whether every mixer has an absolute-position chunked prefill
        path (attn/mla).  Mamba's recurrent state is value-dependent, so
        a right-padded chunk would corrupt it — those models (and the
        enc-dec stack) prefill one-shot."""
        if self.kind == "encdec":
            return False
        return all(spec.mixer in ("attn", "mla")
                   for st in self.cfg.stages for spec in st.layers)

    def prefill_chunk(self, params: Params, batch: Dict[str, jax.Array],
                      caches: Params, *, q_offset, valid_len, last_index
                      ) -> Tuple[jax.Array, Params]:
        """One page-sized prefill chunk at TRACED ``q_offset`` (chunk
        index never forces a retrace).  The chunk is right-padded to the
        page boundary; ``valid_len`` clamps the cache length counters so
        pad positions don't count, and ``last_index`` (chunk-local, also
        traced) picks which position's logits to return — meaningful on
        the final chunk, where it is the prompt's last real token."""
        h, new_caches, _ = T.forward(
            params, self.cfg, batch, caches=caches, q_offset=q_offset,
            decode=False, chunked=True, valid_len=valid_len)
        h_last = jax.lax.dynamic_slice_in_dim(h, last_index, 1, axis=1)
        logits = T._unembed(params, self.cfg, h_last)
        return logits[:, 0], new_caches

    def decode_step(self, params: Params, batch: Dict[str, jax.Array],
                    caches: Params) -> Tuple[jax.Array, Params]:
        """One token for every sequence.  batch: {"tokens": (B, 1)} or
        {"inputs_embeds": (B, 1, D)}."""
        if self.kind == "encdec":
            return ED.decode_step(params, self.cfg, batch["tokens"], caches)
        h, new_caches, _ = T.forward(params, self.cfg, batch, caches=caches,
                                     decode=True)
        logits = T._unembed(params, self.cfg, h)
        return logits[:, 0], new_caches

    def decode_step_paged(self, params: Params, batch: Dict[str, jax.Array],
                          caches: Params, table: jax.Array, active: jax.Array
                          ) -> Tuple[jax.Array, Params]:
        """One token for every slot, K/V read from the page pool
        (``T.decode_paged``; the pool runs it only where every cache leaf
        is a GQA K/V or its counter).  ``caches``: the cache tree with the
        pool's "k"/"v" leaves; ``table`` (B, pages_per_slot) page ids;
        ``active`` (B,) bool.  Returns (logits, caches holding each
        layer's new-token K/V and the advanced counters)."""
        if self.kind == "encdec":
            raise ValueError("the encoder-decoder has no paged decode")
        h, new_caches = T.decode_paged(params, self.cfg, batch, caches,
                                       table, active)
        logits = T._unembed(params, self.cfg, h)
        return logits[:, 0], new_caches


def _specs_decoder(cfg: T.TransformerCfg) -> Params:
    return _eval_specs(lambda k: T.init_params(k, cfg))


def _specs_encdec(cfg: ED.EncDecCfg) -> Params:
    return _eval_specs(lambda k: ED.init_params(k, cfg))


def _eval_specs(init_fn: Callable) -> Params:
    """Spec trees are built by the init functions themselves; evaluate them
    without materializing parameters."""
    closure = {}

    def capture():
        _, specs = init_fn(jax.random.PRNGKey(0))
        closure["specs"] = specs
        return 0

    jax.eval_shape(capture)
    return closure["specs"]


def build_model(cfg: Cfg) -> Model:
    return Model(cfg=cfg)
