"""Paged KV-cache subsystem: ``PagePool`` + ``PageTable`` own ALL serving
cache memory (PR 9).

The single-entity principle applied to cache memory: one pool owns a
device-resident region of fixed-size pages (``ServeCfg.page_tokens``
positions each, pow2), preallocated once and reused in the spirit of
pMR's region/buffer reuse — allocation, free, splice, extract, park,
snapshot, and defragmentation happen HERE or not at all
(``tools/check_api.py`` rule 5 forbids ``init_caches`` calls and direct
cache-row splice/extract outside this module and the model definitions).

Layout
------
A model's cache pytree is probed once with ``jax.eval_shape`` (vary the
batch, then the max_len argument) to classify every leaf:

- **token leaves** carry a per-position axis (attention K/V rows, MLA
  latents).  The pool stores them as ``(num_pages + 1, page_tokens,
  *rest)`` — page id 0 is a reserved, never-allocated zero page so
  unoccupied page-table entries always have somewhere harmless to point.
  A *logical page* spans page_tokens positions across EVERY token leaf
  (all layers at once), so one allocation covers a token-range for the
  whole model.
- **state leaves** have no position axis (the ``len`` counters, Mamba
  conv/SSM state, accumulators in the test fakes).  They live in a
  batch-shaped slot arena ``(batch, *rest)``, spliced per slot.

Per request, a ``PageTable`` maps logical token positions to physical
pages (``pages[i]`` backs positions ``[i*page_tokens, (i+1)*page_tokens)``)
plus the logical token count.  The decode/prefill arenas the model
actually computes on are *assembled inside the jitted step* (gather by
page id) and the touched page is scattered back — persistent device
memory is the pool itself, proportional to allocated pages, i.e. to
generated length, not to ``batch * max_len``.

Degenerate layout: ``page_tokens == max_len`` IS the old contiguous
layout (one page per slot), so the pool serves both and the serve bench
can compare them like-for-like.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation


class OutOfPages(RuntimeError):
    """The pool cannot back the requested tokens with free pages."""


def resolve_page_tokens(max_len: int, page_tokens: Optional[int]) -> int:
    """Validate/derive the page size.  Explicit values must be pow2 and
    divide ``max_len`` (or equal it — the degenerate contiguous layout);
    ``None`` auto-picks the largest pow2 <= 16 that divides ``max_len``."""
    if page_tokens is not None:
        pt = int(page_tokens)
        if pt == max_len:
            return pt
        if pt < 1 or (pt & (pt - 1)) != 0:
            raise ValueError(f"page_tokens={pt} must be a power of two")
        if max_len % pt != 0:
            raise ValueError(
                f"page_tokens={pt} must divide max_len={max_len}")
        return pt
    pt = 1
    while pt * 2 <= min(16, max_len) and max_len % (pt * 2) == 0:
        pt *= 2
    return pt


# ---------------------------------------------------------------------------
# Cache creation chokepoints (rule 5: the only init_caches call sites
# outside the model definitions)
# ---------------------------------------------------------------------------


def contiguous_caches(model, batch: int, max_len: int, *, dtype,
                      enc_len: int = 0):
    """A plain contiguous cache (the pre-paging layout) for the simple
    ``generate`` path and for layout probes."""
    if enc_len:
        return model.init_caches(batch, max_len, enc_len=enc_len,
                                 dtype=dtype)
    return model.init_caches(batch, max_len, dtype=dtype)


def abstract_caches(model, batch: int, max_len: int, *, dtype,
                    enc_len: int = 0):
    """``eval_shape`` of a contiguous cache (no memory materialized)."""
    return jax.eval_shape(
        lambda: contiguous_caches(model, batch, max_len, dtype=dtype,
                                  enc_len=enc_len))


# ---------------------------------------------------------------------------
# Contiguous-row splice/extract (batch-axis located per-leaf via specs)
# ---------------------------------------------------------------------------


def _batch_axis(spec) -> int:
    """Locate the batch axis of a cache leaf from its PartitionSpec (the
    entry sharded over the data axes)."""
    for i, entry in enumerate(spec):
        if entry in ("data", ("pod", "data"), ("data",), "pod"):
            return i
        if isinstance(entry, tuple) and "data" in entry:
            return i
    return 0


def splice_cache(full, one, index: int, specs):
    """Insert a batch-1 cache pytree into slot ``index`` of a full-batch
    contiguous cache, batch axis located per-leaf via the spec tree."""
    from jax.sharding import PartitionSpec as P

    def leaf(f, o, s):
        ax = _batch_axis(s)
        return jax.lax.dynamic_update_slice_in_dim(
            f, jnp.asarray(o).astype(f.dtype), index, axis=ax)

    return jax.tree_util.tree_map(
        leaf, full, one, specs,
        is_leaf=lambda x: isinstance(x, P))


def extract_cache(full, index: int, specs):
    """The inverse of ``splice_cache``: slice slot ``index`` out of a
    full-batch contiguous cache as a batch-1 pytree."""
    from jax.sharding import PartitionSpec as P

    def leaf(f, s):
        return jax.lax.dynamic_slice_in_dim(f, index, 1,
                                            axis=_batch_axis(s))

    return jax.tree_util.tree_map(
        leaf, full, specs,
        is_leaf=lambda x: isinstance(x, P))


# ---------------------------------------------------------------------------
# Layout probe
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class LeafLayout:
    shape: Tuple[int, ...]         # abstract shape at (batch=1, max_len)
    dtype: Any
    batch_axis: int
    token_axis: Optional[int]      # None: state leaf (no position axis)


def _diff_axes(a, b) -> List[int]:
    assert len(a.shape) == len(b.shape), (a.shape, b.shape)
    return [i for i, (x, y) in enumerate(zip(a.shape, b.shape)) if x != y]


@dataclasses.dataclass
class PageLayout:
    """Probed per-leaf cache layout for one model + max_len + dtype."""
    treedef: Any
    leaves: List[LeafLayout]
    max_len: int
    page_tokens: int

    @property
    def pages_per_slot(self) -> int:
        return self.max_len // self.page_tokens

    @property
    def token_leaf_ids(self) -> List[int]:
        return [i for i, l in enumerate(self.leaves)
                if l.token_axis is not None]

    @property
    def state_leaf_ids(self) -> List[int]:
        return [i for i, l in enumerate(self.leaves)
                if l.token_axis is None]

    def page_bytes(self) -> int:
        """Bytes one logical page occupies across every token leaf."""
        total = 0
        for i in self.token_leaf_ids:
            l = self.leaves[i]
            rest = [s for ax, s in enumerate(l.shape)
                    if ax not in (l.batch_axis, l.token_axis)]
            total += (self.page_tokens * int(np.prod(rest, initial=1))
                      * jnp.dtype(l.dtype).itemsize)
        return total

    def row_bytes(self) -> int:
        """Bytes one full contiguous ``max_len`` row occupies (the
        pre-paging per-slot cost the bench compares against)."""
        return self.pages_per_slot * self.page_bytes()


def probe_layout(model, max_len: int, page_tokens: int, *,
                 dtype) -> PageLayout:
    """Classify cache leaves by varying ``batch`` then ``max_len`` under
    ``eval_shape`` — model-agnostic (works for the test fakes too)."""
    base = abstract_caches(model, 1, max_len, dtype=dtype)
    wide = abstract_caches(model, 2, max_len, dtype=dtype)
    deep = abstract_caches(model, 1, 2 * max_len, dtype=dtype)
    bl, treedef = jax.tree_util.tree_flatten(base)
    wl = jax.tree_util.tree_leaves(wide)
    dl = jax.tree_util.tree_leaves(deep)
    leaves = []
    for b, w, d in zip(bl, wl, dl):
        baxes = _diff_axes(b, w)
        if len(baxes) != 1:
            raise ValueError(
                f"cache leaf {b.shape} has no unique batch axis ({baxes})")
        taxes = _diff_axes(b, d)
        if len(taxes) > 1:
            raise ValueError(
                f"cache leaf {b.shape} has no unique token axis ({taxes})")
        leaves.append(LeafLayout(
            shape=tuple(b.shape), dtype=b.dtype, batch_axis=baxes[0],
            token_axis=taxes[0] if taxes else None))
    return PageLayout(treedef=treedef, leaves=leaves, max_len=max_len,
                      page_tokens=page_tokens)


# ---------------------------------------------------------------------------
# Page table + extracted request cache
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class PageTable:
    """One request's logical-position -> physical-page mapping."""
    pages: List[int] = dataclasses.field(default_factory=list)
    tokens: int = 0                # cache positions occupied (logical len)

    def page_of(self, position: int, page_tokens: int) -> int:
        return self.pages[position // page_tokens]


@dataclasses.dataclass
class RequestCache:
    """A request's cache extracted to host, page-granular: ONLY its live
    pages move (`` ~ generated tokens``), never a full max_len row."""
    pages: List[Any]               # per token leaf: (n_pages, pt, *rest)
    state: List[Any]               # per state leaf: (1, *rest)
    tokens: int

    def nbytes(self) -> int:
        return int(sum(np.asarray(l).nbytes
                       for l in list(self.pages) + list(self.state)))


def abstract_request_cache(layout: "PageLayout", tokens: int
                           ) -> RequestCache:
    """The abstract (ShapeDtypeStruct) image of an extracted request with
    ``tokens`` cache positions — what checkpoint restore validates
    against, built from the probed layout instead of a pickled treedef."""
    n = -(-tokens // layout.page_tokens) if tokens > 0 else 0
    pages, state = [], []
    for i in layout.token_leaf_ids:
        l = layout.leaves[i]
        rest = [s for ax, s in enumerate(l.shape)
                if ax not in (l.batch_axis, l.token_axis)]
        pages.append(jax.ShapeDtypeStruct(
            (n, layout.page_tokens, *rest), l.dtype))
    for i in layout.state_leaf_ids:
        l = layout.leaves[i]
        state.append(jax.ShapeDtypeStruct(tuple(l.shape), l.dtype))
    return RequestCache(pages=pages, state=state, tokens=tokens)


def layout_for(model, cfg) -> PageLayout:
    """The probed page layout a ``ServeCfg`` implies (no pool memory)."""
    return probe_layout(model, cfg.max_len,
                        resolve_page_tokens(cfg.max_len, cfg.page_tokens),
                        dtype=cfg.cache_dtype)


# ---------------------------------------------------------------------------
# The pool
# ---------------------------------------------------------------------------


class PagePool:
    """Device-resident page pool + slot-state arena: the ONE owner of
    serving cache memory.

    ``num_pages`` defaults to ``batch * max_len / page_tokens`` (capacity
    parity with the contiguous layout); ``ServeCfg.pool_pages`` overcommits
    or undercommits it.  Free pages are reused LIFO (recently-freed pages
    are hottest).  ``rid``-keyed ``PageTable``s are the only route from a
    logical token position to pool memory.
    """

    def __init__(self, model, cfg, comm=None):
        self.model = model
        self.cfg = cfg
        self.comm = comm
        self.page_tokens = resolve_page_tokens(cfg.max_len, cfg.page_tokens)
        self.layout = probe_layout(model, cfg.max_len, self.page_tokens,
                                   dtype=cfg.cache_dtype)
        pps = self.layout.pages_per_slot
        self.num_pages = int(cfg.pool_pages) if cfg.pool_pages \
            else cfg.batch * pps
        if self.num_pages < 1:
            raise ValueError("pool needs at least one page")
        # page 0 is the reserved zero page; allocatable ids are 1..num_pages
        self._free: List[int] = list(range(self.num_pages, 0, -1))
        self.tables: Dict[int, PageTable] = {}
        self.pool: List[jax.Array] = []       # token leaves
        self.state: List[jax.Array] = []      # slot-state arena leaves
        for i, l in enumerate(self.layout.leaves):
            if l.token_axis is not None:
                rest = [s for ax, s in enumerate(l.shape)
                        if ax not in (l.batch_axis, l.token_axis)]
                self.pool.append(jnp.zeros(
                    (self.num_pages + 1, self.page_tokens, *rest), l.dtype))
            else:
                rest = [s for ax, s in enumerate(l.shape)
                        if ax != l.batch_axis]
                # state arena keeps the slot axis where the batch axis was
                shape = list(rest)
                shape.insert(min(l.batch_axis, len(rest)), cfg.batch)
                self.state.append(jnp.zeros(tuple(shape), l.dtype))
        self._jit_decode: Dict[int, Callable] = {}
        self._jit_chunk: Optional[Callable] = None
        self._jit_splice_row: Optional[Callable] = None

    # -- books -------------------------------------------------------------

    @property
    def pages_total(self) -> int:
        return self.num_pages

    @property
    def pages_free(self) -> int:
        return len(self._free)

    @property
    def pages_allocated(self) -> int:
        return self.num_pages - len(self._free)

    def pages_for(self, n_tokens: int) -> int:
        return -(-n_tokens // self.page_tokens) if n_tokens > 0 else 0

    def resident_bytes(self) -> int:
        """Cache bytes actually backing live tokens: allocated pages x
        page bytes + the slot-state arena — the number that scales with
        generated length instead of ``batch * max_len``."""
        state = sum(int(np.prod(s.shape, initial=1))
                    * jnp.dtype(s.dtype).itemsize for s in self.state)
        return self.pages_allocated * self.layout.page_bytes() + state

    def contiguous_bytes(self, rows: Optional[int] = None) -> int:
        """What the same occupancy costs in the contiguous layout."""
        rows = self.cfg.batch if rows is None else rows
        state = sum(int(np.prod(s.shape, initial=1))
                    * jnp.dtype(s.dtype).itemsize for s in self.state)
        return rows * self.layout.row_bytes() + state

    def has_room(self, n_tokens: int) -> bool:
        return self.pages_free >= self.pages_for(n_tokens)

    def ensure(self, rid: int, n_tokens: int) -> List[int]:
        """Grow ``rid``'s table to cover ``n_tokens`` positions; returns
        the newly allocated page ids.  Raises ``OutOfPages`` (allocating
        nothing) when the pool cannot back the growth."""
        table = self.tables.setdefault(rid, PageTable())
        need = self.pages_for(n_tokens) - len(table.pages)
        if need <= 0:
            return []
        if need > len(self._free):
            raise OutOfPages(
                f"rid {rid} needs {need} page(s), {len(self._free)} free "
                f"of {self.num_pages}")
        new = [self._free.pop() for _ in range(need)]
        table.pages.extend(new)
        return new

    def release(self, rid: int) -> int:
        """Free every page ``rid`` holds; returns how many."""
        table = self.tables.pop(rid, None)
        if table is None:
            return 0
        for p in reversed(table.pages):
            self._free.append(p)
        return len(table.pages)

    def check_integrity(self) -> None:
        """Allocator invariants (the property-test surface): every page
        allocated at most once, free+allocated partitions the pool, page 0
        never handed out, tables consistent with their token counts."""
        seen: Dict[int, int] = {}
        for rid, t in self.tables.items():
            assert len(t.pages) >= self.pages_for(t.tokens), (rid, t)
            for p in t.pages:
                assert 1 <= p <= self.num_pages, (rid, p)
                assert p not in seen, f"page {p} owned by {seen[p]} and {rid}"
                seen[p] = rid
        free = set(self._free)
        assert len(free) == len(self._free), "free list holds duplicates"
        assert 0 not in free, "zero page on the free list"
        assert not (free & set(seen)), "page both free and allocated"
        assert len(free) + len(seen) == self.num_pages, \
            (len(free), len(seen), self.num_pages)

    # -- table materialization --------------------------------------------

    def _table_row(self, rid: Optional[int]) -> List[int]:
        pps = self.layout.pages_per_slot
        if rid is None or rid not in self.tables:
            return [0] * pps
        pages = self.tables[rid].pages
        return list(pages) + [0] * (pps - len(pages))

    def table_array(self, slot_rids: Sequence[Optional[int]]) -> jax.Array:
        return jnp.asarray([self._table_row(r) for r in slot_rids],
                           jnp.int32)

    # -- jitted assemble / writeback ---------------------------------------

    def _assemble(self, pool, state, table):
        """Gather a (B, max_len, ...) cache pytree from pages (inside the
        step's jit: the arena is a temporary, not resident memory)."""
        b = table.shape[0]
        pps = self.layout.pages_per_slot
        leaves: List[Optional[jax.Array]] = [None] * len(self.layout.leaves)
        ti = si = 0
        for i, l in enumerate(self.layout.leaves):
            if l.token_axis is not None:
                g = pool[ti][table]                  # (B, pps, pt, *rest)
                g = g.reshape((b, pps * self.page_tokens) + g.shape[3:])
                leaves[i] = jnp.moveaxis(g, (0, 1),
                                         (l.batch_axis, l.token_axis))
                ti += 1
            else:
                arena = state[si]
                src_ax = min(l.batch_axis, arena.ndim - 1)
                leaves[i] = jnp.moveaxis(arena, src_ax, l.batch_axis) \
                    if src_ax != l.batch_axis else arena
                si += 1
        return jax.tree_util.tree_unflatten(self.layout.treedef, leaves)

    def _split(self, caches):
        """Inverse bookkeeping of ``_assemble``: flatten a cache pytree
        back into (token leaves, state leaves)."""
        flat = jax.tree_util.tree_leaves(caches)
        tok = [flat[i] for i in self.layout.token_leaf_ids]
        state = [flat[i] for i in self.layout.state_leaf_ids]
        return tok, state

    def _writeback_page(self, pool, tok_leaves, slot: int, pid, k, active):
        """Scatter slot ``slot``'s page ``k`` (token positions
        ``[k*pt, (k+1)*pt)``) from assembled leaves back into the pool;
        ``active`` masks the write (inactive slots keep pool content)."""
        pt = self.page_tokens
        out = []
        for ti, i in enumerate(self.layout.token_leaf_ids):
            l = self.layout.leaves[i]
            row = jax.lax.index_in_dim(tok_leaves[ti], slot,
                                       axis=l.batch_axis, keepdims=False)
            t_ax = l.token_axis - (1 if l.batch_axis < l.token_axis else 0)
            page = jax.lax.dynamic_slice_in_dim(row, k * pt, pt, axis=t_ax)
            page = jnp.moveaxis(page, t_ax, 0)       # (pt, *rest)
            cur = pool[ti][pid]
            out.append(pool[ti].at[pid].set(
                jnp.where(active, page.astype(cur.dtype), cur)))
        return out

    def bind_decode(self, decode_fn) -> Callable:
        """One jitted paged decode step: assemble arena from pages ->
        ``decode_fn`` -> write each active slot's touched page back.
        Returns ``fn(params, tok, rids, pos, slot_rids, active_mask)``
        -> next tokens (and commits pool/state internally).  The program
        is ``paged_decode`` (``jit_paged_decode`` in a profiler trace);
        building and uploading its page arguments is the ``serve.pages``
        span."""
        b = self.cfg.batch

        @jax.jit
        def paged_decode(params, pool, state, tok, rids, pos, table, pids,
                         ks, active):
            caches = self._assemble(pool, state, table)
            nxt, new_caches = decode_fn(params, tok, caches, rids, pos)
            tok_leaves, new_state = self._split(new_caches)
            for i in range(b):
                pool = self._writeback_page(pool, tok_leaves, i, pids[i],
                                            ks[i], active[i])
            # inactive slots keep their arena state (a masked select per
            # leaf keeps parked/prefilling slots' state bit-intact)
            out_state = []
            for si, li in enumerate(self.layout.state_leaf_ids):
                l = self.layout.leaves[li]
                ax = min(l.batch_axis, state[si].ndim - 1)
                flat = jax.tree_util.tree_leaves(new_caches)
                new = jnp.moveaxis(flat[li], l.batch_axis, ax) \
                    if ax != l.batch_axis else flat[li]
                mask = jnp.moveaxis(
                    active.reshape((b,) + (1,) * (new.ndim - 1)), 0, ax)
                out_state.append(jnp.where(mask, new.astype(state[si].dtype),
                                           state[si]))
            return nxt, pool, out_state

        def run(params, tok, rids, pos, slot_rids, active_mask):
            with TraceAnnotation("serve.pages"):
                table = self.table_array(slot_rids)
                pt = self.page_tokens
                pids, ks = [], []
                for r, a in zip(slot_rids, active_mask):
                    t = self.tables.get(r) if r is not None else None
                    if a and t is not None:
                        pids.append(t.page_of(t.tokens, pt))
                        ks.append(t.tokens // pt)
                    else:
                        pids.append(0)
                        ks.append(0)
                args = (table, jnp.asarray(pids, jnp.int32),
                        jnp.asarray(ks, jnp.int32),
                        jnp.asarray(active_mask, jnp.bool_))
            nxt, self.pool, self.state = paged_decode(
                params, self.pool, self.state, tok, rids, pos, *args)
            for r, a in zip(slot_rids, active_mask):
                if a and r is not None:
                    self.tables[r].tokens += 1
            return nxt

        return run

    def bind_prefill_chunk(self, chunk_fn) -> Callable:
        """One jitted prefill chunk over a batch-1 arena gathered from the
        request's pages: ``chunk_fn(params, tokens, caches, q_offset,
        valid_len, last_index)`` -> (logits, caches).  Writes the chunk's
        page back and returns (logits, state-leaves) for the caller to
        carry between chunks.  The program is ``paged_prefill_chunk``;
        its page arguments are built in a ``serve.pages`` span (``rid``)."""

        @jax.jit
        def paged_prefill_chunk(params, pool, state1, tokens, table1,
                                q_offset, valid_len, last_index, pid, k):
            caches = self._assemble(pool, state1, table1)
            logits, new_caches = chunk_fn(params, tokens, caches, q_offset,
                                          valid_len, last_index)
            tok_leaves, new_state = self._split(new_caches)
            pool = self._writeback_page(pool, tok_leaves, 0, pid, k,
                                        jnp.bool_(True))
            return logits, pool, new_state

        def run(params, rid, tokens, chunk_idx, valid_len, last_index,
                state1):
            with TraceAnnotation("serve.pages", rid=rid):
                t = self.tables[rid]
                args = (self.table_array([rid]),
                        jnp.int32(chunk_idx * self.page_tokens),
                        jnp.int32(valid_len), jnp.int32(last_index),
                        jnp.int32(t.pages[chunk_idx]), jnp.int32(chunk_idx))
            logits, self.pool, new_state = paged_prefill_chunk(
                params, self.pool, state1, tokens, *args)
            t.tokens = min(valid_len, (chunk_idx + 1) * self.page_tokens)
            return logits, new_state

        return run

    # -- state arena -------------------------------------------------------

    def fresh_state1(self) -> List[jax.Array]:
        """Zeroed batch-1 state leaves (a new request's non-positional
        cache state, carried across prefill chunks)."""
        out = []
        for li in self.layout.state_leaf_ids:
            l = self.layout.leaves[li]
            shape = [1 if ax == l.batch_axis else s
                     for ax, s in enumerate(l.shape)]
            out.append(jnp.zeros(tuple(shape), l.dtype))
        return out

    def read_state(self, slot: int) -> List[jax.Array]:
        out = []
        for si, li in enumerate(self.layout.state_leaf_ids):
            l = self.layout.leaves[li]
            ax = min(l.batch_axis, self.state[si].ndim - 1)
            row = jax.lax.dynamic_slice_in_dim(self.state[si], slot, 1,
                                               axis=ax)
            out.append(jnp.moveaxis(row, ax, l.batch_axis)
                       if ax != l.batch_axis else row)
        return out

    def write_state(self, slot: int, state1: Sequence[Any]) -> None:
        new = []
        for si, li in enumerate(self.layout.state_leaf_ids):
            l = self.layout.leaves[li]
            ax = min(l.batch_axis, self.state[si].ndim - 1)
            one = jnp.asarray(state1[si]).astype(self.state[si].dtype)
            if ax != l.batch_axis:
                one = jnp.moveaxis(one, l.batch_axis, ax)
            new.append(jax.lax.dynamic_update_slice_in_dim(
                self.state[si], one, slot, axis=ax))
        self.state = new

    # -- one-shot splice (models without chunked prefill) ------------------

    def splice_row(self, rid: int, slot: int, cache_b1, n_tokens: int
                   ) -> None:
        """Adopt a contiguous batch-1 cache (a one-shot prefill result)
        into pool pages + slot state.  Pages are allocated here; the
        jitted scatter writes ``ceil(n_tokens/pt)`` pages (masked, so the
        trace is shared across token counts)."""
        self.ensure(rid, n_tokens)
        if self._jit_splice_row is None:
            pps = self.layout.pages_per_slot
            pt = self.page_tokens

            @jax.jit
            def splice(pool, cache_b1, pids, n_pages):
                flat = jax.tree_util.tree_leaves(cache_b1)
                for j in range(pps):
                    out = []
                    for ti, i in enumerate(self.layout.token_leaf_ids):
                        l = self.layout.leaves[i]
                        row = jnp.squeeze(flat[i], axis=l.batch_axis)
                        t_ax = l.token_axis - (
                            1 if l.batch_axis < l.token_axis else 0)
                        page = jax.lax.slice_in_dim(row, j * pt,
                                                    (j + 1) * pt, axis=t_ax)
                        page = jnp.moveaxis(page, t_ax, 0)
                        cur = pool[ti][pids[j]]
                        out.append(pool[ti].at[pids[j]].set(
                            jnp.where(j < n_pages, page.astype(cur.dtype),
                                      cur)))
                    pool = out
                return pool

            self._jit_splice_row = splice
        t = self.tables[rid]
        pids = jnp.asarray(self._table_row(rid), jnp.int32)
        self.pool = self._jit_splice_row(self.pool, cache_b1, pids,
                                         jnp.int32(len(t.pages)))
        flat = jax.tree_util.tree_leaves(cache_b1)
        self.write_state(slot, [flat[i] for i in self.layout.state_leaf_ids])
        t.tokens = n_tokens

    # -- extract / splice / park (the elastic + preemption surface) --------

    def extract(self, rid: int, slot: int) -> RequestCache:
        """Page-granular extract to host: ONLY ``rid``'s live pages and
        its slot state move — re-mesh snapshot cost is proportional to
        generated tokens, not ``max_len``."""
        t = self.tables[rid]
        idx = np.asarray(t.pages, np.int32)
        pages = [jax.device_get(leaf[idx]) for leaf in self.pool]
        state = [jax.device_get(s) for s in self.read_state(slot)]
        return RequestCache(pages=pages, state=state, tokens=t.tokens)

    def splice(self, rid: int, slot: int, rc: RequestCache) -> None:
        """The inverse of ``extract``: allocate pages for ``rc.tokens``
        and write the host pages + state back.  Raises ``OutOfPages``
        without side effects when the pool has no room."""
        if rid in self.tables and self.tables[rid].pages:
            raise ValueError(f"rid {rid} already holds pages")
        self.ensure(rid, rc.tokens)
        t = self.tables[rid]
        idx = jnp.asarray(t.pages, jnp.int32)
        self.pool = [
            leaf.at[idx].set(jnp.asarray(pg).astype(leaf.dtype))
            for leaf, pg in zip(self.pool, rc.pages)]
        self.write_state(slot, rc.state)
        t.tokens = rc.tokens

    def park(self, rid: int, slot: int) -> RequestCache:
        """Extract + free: the request leaves the pool (host-parked) so
        its pages serve someone else."""
        rc = self.extract(rid, slot)
        self.release(rid)
        return rc

    # -- defragmentation ---------------------------------------------------

    def defragment(self) -> int:
        """Compact allocated pages into the lowest ids (tables rewritten,
        page data moved device-side).  Returns pages moved.  After heavy
        admit/finish churn this re-establishes a dense prefix so the free
        list is one contiguous tail — the region-reuse discipline pMR
        applies to RDMA buffers."""
        owners: Dict[int, Tuple[int, int]] = {}
        for rid, t in self.tables.items():
            for j, p in enumerate(t.pages):
                owners[p] = (rid, j)
        moves: List[Tuple[int, int]] = []
        target = 1
        for p in sorted(owners):
            if p != target:
                moves.append((p, target))
            target += 1
        if moves:
            src = jnp.asarray([m[0] for m in moves], jnp.int32)
            dst = jnp.asarray([m[1] for m in moves], jnp.int32)
            self.pool = [leaf.at[dst].set(leaf[src]) for leaf in self.pool]
            for old, new in moves:
                rid, j = owners[old]
                self.tables[rid].pages[j] = new
        n_alloc = len(owners)
        self._free = list(range(self.num_pages, n_alloc, -1))
        return len(moves)
