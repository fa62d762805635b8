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
plus the logical token count.  Persistent device memory is the pool
itself, proportional to allocated pages, i.e. to generated length, not
to ``batch * max_len``.

Programs
--------
- **Paged decode** (``decodes_paged``: every token leaf is the K or V
  of a GQA attention layer and every state leaf its length counter; on
  TPU also the Pallas kernel takes the pool's layout and the decode
  runs on one device).  The model attends to the pool's pages directly,
  reading only each slot's live pages (``repro.kernels.paged_attention``)
  and returning each layer's new token, which the program writes into
  its page; the pool and the state arena are donated, so that write is
  in place and no second pool is ever live.
- **Arena decode** (every other case: MLA latents, recurrent state,
  cross-attention memory, and on TPU a GQA layout the kernel cannot
  copy or a decode over a mesh).  A ``(batch, max_len)`` arena is
  assembled inside the jitted step (gather by page id), the model
  decodes on it, and each slot's touched page is scattered back.
- **Prefill chunk**: a batch-1 arena gathered from the request's pages,
  its chunk's page scattered back.

Each program takes its host-side arguments (page table, token ids,
positions, page ids, mask, scalars) packed into ONE int32 array, sent
with one explicit ``jax.device_put`` (``PagePool.put``, counted in
``h2d_uploads``) and sliced apart inside the jit.  The page table is
not rebuilt per call: ``slot_pages`` mirrors every slot's table on the
host and changes where pages are granted, released, spliced or moved.

Degenerate layout: ``page_tokens == max_len`` IS the old contiguous
layout (one page per slot), so the pool serves both and the serve bench
can compare them like-for-like.
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation
from jax.sharding import NamedSharding, PartitionSpec

from repro.kernels.paged_attention import kernel as paged_kernel


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
    name: str = ""                 # the leaf's key in its cache dict


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

    @property
    def kv_pages(self) -> bool:
        """Whether the cache suits the paged decode: every token leaf is
        the "k" or "v" of a GQA attention cache laid out ``(layers,
        batch, max_len, Hkv, Dh)`` and every state leaf is a "len"
        counter."""
        def kv(l):
            return (l.name in ("k", "v") and len(l.shape) == 5
                    and (l.batch_axis, l.token_axis) == (1, 2))
        return bool(self.token_leaf_ids) and all(
            kv(l) if l.token_axis is not None else l.name == "len"
            for l in self.leaves)

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
    paths, treedef = jax.tree_util.tree_flatten_with_path(base)
    wl = jax.tree_util.tree_leaves(wide)
    dl = jax.tree_util.tree_leaves(deep)
    leaves = []
    for (path, b), w, d in zip(paths, wl, dl):
        baxes = _diff_axes(b, w)
        if len(baxes) != 1:
            raise ValueError(
                f"cache leaf {b.shape} has no unique batch axis ({baxes})")
        taxes = _diff_axes(b, d)
        if len(taxes) > 1:
            raise ValueError(
                f"cache leaf {b.shape} has no unique token axis ({taxes})")
        key = path[-1] if path else None
        leaves.append(LeafLayout(
            shape=tuple(b.shape), dtype=b.dtype, batch_axis=baxes[0],
            token_axis=taxes[0] if taxes else None,
            name=str(getattr(key, "key", key))))
    return PageLayout(treedef=treedef, leaves=leaves, max_len=max_len,
                      page_tokens=page_tokens)


def decodes_paged(layout: PageLayout, devices: int = 1) -> bool:
    """Whether the pool decodes on its pages (else on a gathered arena).

    The cache must be all GQA K/V (``layout.kv_pages``).  On TPU the
    paged decode's attention is the Pallas kernel, never the jnp oracle,
    so it runs only where the kernel can copy the pool's pages
    (``kernel.fits``) and the decode program spans one device: a Mosaic
    kernel is not partitioned across a mesh.  On other backends the
    oracle attends, on any mesh.  ``devices``: the devices of the mesh
    the decode runs under."""
    if not layout.kv_pages:
        return False
    if jax.default_backend() != "tpu":
        return True
    return devices == 1 and all(paged_kernel.fits(layout.leaves[i])
                                for i in layout.token_leaf_ids)


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
        mesh = comm.mesh if comm is not None else None
        # which decode program runs
        self.paged = decodes_paged(self.layout,
                                   mesh.size if mesh is not None else 1)
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
        # each slot's page ids on the host, 0 (the zero page) past its
        # pages; the programs' page tables are read from here
        self.slot_pages = np.zeros((cfg.batch, pps), np.int32)
        self._slot_of: Dict[int, int] = {}    # rid -> the slot it sits in
        # uploads land replicated over a concrete mesh, else on the
        # default device
        self._placement = NamedSharding(mesh, PartitionSpec()) \
            if mesh is not None and hasattr(mesh, "devices") else None
        self.h2d_uploads = 0                  # explicit host->device puts
        self._slot_ids: Optional[List[jax.Array]] = None
        self._zero_state1: Optional[List[jax.Array]] = None
        self._jit_write_state: Optional[Callable] = None
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

    def ensure(self, rid: int, n_tokens: int,
               slot: Optional[int] = None) -> List[int]:
        """Grow ``rid``'s table to cover ``n_tokens`` positions; returns
        the newly allocated page ids.  Raises ``OutOfPages`` (allocating
        nothing) when the pool cannot back the growth.  ``slot``: the
        slot ``rid`` takes, whose row of ``slot_pages`` then follows its
        table until ``release``."""
        table = self.tables.setdefault(rid, PageTable())
        need = self.pages_for(n_tokens) - len(table.pages)
        if need > len(self._free):
            raise OutOfPages(
                f"rid {rid} needs {need} page(s), {len(self._free)} free "
                f"of {self.num_pages}")
        new = [self._free.pop() for _ in range(max(need, 0))]
        table.pages.extend(new)
        n = len(table.pages)
        if slot is not None:         # the slot's last holder leaves it
            self._slot_of = {r: s for r, s in self._slot_of.items()
                             if s != slot}
            self._slot_of[rid] = slot
            self.slot_pages[slot] = 0
            self.slot_pages[slot, :n] = table.pages
        elif new and rid in self._slot_of:
            self.slot_pages[self._slot_of[rid], n - len(new):n] = new
        return new

    def release(self, rid: int) -> int:
        """Free every page ``rid`` holds; returns how many."""
        slot = self._slot_of.pop(rid, None)
        if slot is not None:
            self.slot_pages[slot] = 0
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
        assert len(set(self._slot_of.values())) == len(self._slot_of), \
            "two requests in one slot"
        for slot, row in enumerate(self.slot_pages):
            rid = next((r for r, s in self._slot_of.items() if s == slot),
                       None)
            pages = self.tables[rid].pages if rid is not None else []
            assert list(row) == pages + [0] * (len(row) - len(pages)), \
                (slot, rid, row)

    # -- uploads -----------------------------------------------------------

    def put(self, host: np.ndarray) -> jax.Array:
        """One explicit host->device transfer (counted in
        ``h2d_uploads``), replicated over the communicator's mesh."""
        self.h2d_uploads += 1
        return jax.device_put(host, self._placement)

    def decode_kv_pages(self, rids: Sequence[int]) -> int:
        """Pages the next decode's attention reads for the decoding
        requests ``rids``: their live pages on the paged path, the whole
        arena (batch x pages per slot) on the arena path.  Host books
        only, no device sync."""
        if not self.paged:
            return self.cfg.batch * self.layout.pages_per_slot
        return sum(self.pages_for(self.tables[r].tokens) for r in rids)

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

    def bind_decode(self, decode_fn, paged_fn) -> Callable:
        """One jitted decode step for every slot.  Returns ``fn(params,
        tok, rids, pos, slot_rids, active_mask)`` -> next tokens on the
        device (and commits pool/state internally); ``tok``, ``rids`` and
        ``pos`` are the slots' (B,) int32 host arrays.  Which program it runs is fixed
        by the probed layout (``self.paged``); both are named
        ``paged_decode`` (``jit_paged_decode`` in a profiler trace), and
        building and uploading their page arguments is the
        ``serve.pages`` span.

        - Paged: ``paged_fn(params, tok, caches, table, active, rids,
          pos)`` on a cache tree whose "k"/"v" leaves are the pool's own
          (``Model.decode_step_paged``) reads the live pages and returns
          each layer's new token, which one scatter per leaf writes into
          its page, in place (the pool and the state arena are donated).
        - Arena: assemble a ``(batch, max_len)`` arena from pages ->
          ``decode_fn(params, tok, caches, rids, pos)`` -> write each
          active slot's touched page back.

        The returned function's ``program`` is the jitted program, whose
        arguments are ``(params, pool, state, packed)``: ``packed`` is
        ``pack_decode``'s array, uploaded once per call."""
        if self.paged:
            return self._decode_runner(self._paged_program(paged_fn))
        return self._decode_runner(self._arena_program(decode_fn))

    def _paged_view(self, pool, state):
        """The cache tree with the pool's token leaves and the state
        arena's leaves in their places (no gather: the leaves as they
        are)."""
        tok, st = iter(pool), iter(state)
        leaves = [next(tok) if l.token_axis is not None else next(st)
                  for l in self.layout.leaves]
        return jax.tree_util.tree_unflatten(self.layout.treedef, leaves)

    def pack_decode(self, tok, rids, pos, slot_rids, active_mask
                    ) -> np.ndarray:
        """Every host-side argument of one decode, as one ``(batch,
        pages_per_slot + 6)`` int32 array.  A slot's row is its page
        table (zeros where it does not decode), then its token, rid and
        position, the page its new token goes to and that token's offset
        in the page (paged) or the page's index in the table (arena), and
        1 where it decodes.  A slot that does not decode writes to page 0
        (arena, masked) or past the pool (paged, dropped)."""
        pt, pps = self.page_tokens, self.layout.pages_per_slot
        active = np.asarray(active_mask, bool)
        out = np.zeros((self.cfg.batch, pps + 6), np.int32)
        out[active, :pps] = self.slot_pages[active]
        out[:, pps:pps + 3] = np.stack([tok, rids, pos], axis=1)
        out[:, pps + 3] = self.num_pages + 1 if self.paged else 0
        for s in np.flatnonzero(active):
            t = self.tables[slot_rids[s]]
            out[s, pps + 3] = t.page_of(t.tokens, pt)
            out[s, pps + 4] = t.tokens % pt if self.paged else t.tokens // pt
        out[:, pps + 5] = active
        return out

    def _unpack_decode(self, packed):
        """Inside the program: ``pack_decode``'s array as (table, tok
        (B, 1), rids, pos, pids, idx, active)."""
        pps = self.layout.pages_per_slot
        c = packed[:, pps:]
        return (packed[:, :pps], c[:, :1], c[:, 1], c[:, 2], c[:, 3],
                c[:, 4], c[:, 5] != 0)

    def _decode_runner(self, program) -> Callable:
        def run(params, tok, rids, pos, slot_rids, active_mask):
            with TraceAnnotation("serve.pages") as span:
                n0 = self.h2d_uploads
                packed = self.put(self.pack_decode(tok, rids, pos,
                                                   slot_rids, active_mask))
                span.set_metadata(uploads=self.h2d_uploads - n0)
            nxt, self.pool, self.state = program(params, self.pool,
                                                 self.state, packed)
            for r, a in zip(slot_rids, active_mask):
                if a and r is not None:
                    self.tables[r].tokens += 1
            return nxt

        run.program = program
        return run

    def _paged_program(self, paged_fn) -> Callable:
        @functools.partial(jax.jit, donate_argnums=(1, 2))
        def paged_decode(params, pool, state, packed):
            table, tok, rids, pos, pids, offs, active = \
                self._unpack_decode(packed)
            nxt, new = paged_fn(params, tok, self._paged_view(pool, state),
                                table, active, rids, pos)
            toks, new_state = self._split(new)
            # each leaf's token (L, B, Hkv, Dh) into row offs of page pids
            # (an idle slot's pid is past the pool: its write is dropped)
            pool = [p.at[pids, offs].set(
                jnp.moveaxis(t, 1, 0).astype(p.dtype), mode="drop")
                for p, t in zip(pool, toks)]
            return nxt, pool, new_state

        return paged_decode

    def _arena_program(self, decode_fn) -> Callable:
        b = self.cfg.batch

        @jax.jit
        def paged_decode(params, pool, state, packed):
            table, tok, rids, pos, pids, ks, active = \
                self._unpack_decode(packed)
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

        return paged_decode

    def pack_chunk(self, rid: int, tokens: Sequence[int], chunk_idx: int,
                   valid_len: int, last_index: int) -> np.ndarray:
        """Every host-side argument of one prefill chunk, as one int32
        vector: the chunk's query offset, ``valid_len``, ``last_index``,
        its page id and index, the request's page table, then its
        ``page_tokens`` token ids (zero-padded)."""
        pt, pps = self.page_tokens, self.layout.pages_per_slot
        out = np.zeros(5 + pps + pt, np.int32)
        out[:5] = (chunk_idx * pt, valid_len, last_index,
                   self.tables[rid].pages[chunk_idx], chunk_idx)
        out[5:5 + pps] = self.slot_pages[self._slot_of[rid]]
        out[5 + pps:5 + pps + len(tokens)] = tokens
        return out

    def bind_prefill_chunk(self, chunk_fn) -> Callable:
        """One jitted prefill chunk over a batch-1 arena gathered from the
        request's pages: ``chunk_fn(params, tokens, caches, q_offset,
        valid_len, last_index)`` -> (logits, caches).  Writes the chunk's
        page back and returns (logits, state-leaves) for the caller to
        carry between chunks.  The program is ``paged_prefill_chunk``,
        whose host-side arguments are ``pack_chunk``'s vector, uploaded
        in a ``serve.pages`` span (``rid``, ``uploads``)."""
        pps = self.layout.pages_per_slot

        @jax.jit
        def paged_prefill_chunk(params, pool, state1, packed):
            q_offset, valid_len, last_index, pid, k = \
                (packed[j] for j in range(5))
            caches = self._assemble(pool, state1, packed[None, 5:5 + pps])
            logits, new_caches = chunk_fn(params, packed[None, 5 + pps:],
                                          caches, q_offset, valid_len,
                                          last_index)
            tok_leaves, new_state = self._split(new_caches)
            pool = self._writeback_page(pool, tok_leaves, 0, pid, k,
                                        jnp.bool_(True))
            return logits, pool, new_state

        def run(params, rid, tokens, chunk_idx, valid_len, last_index,
                state1):
            with TraceAnnotation("serve.pages", rid=rid) as span:
                n0 = self.h2d_uploads
                packed = self.put(self.pack_chunk(rid, tokens, chunk_idx,
                                                  valid_len, last_index))
                span.set_metadata(uploads=self.h2d_uploads - n0)
            logits, self.pool, new_state = paged_prefill_chunk(
                params, self.pool, state1, packed)
            self.tables[rid].tokens = min(valid_len,
                                          (chunk_idx + 1) * self.page_tokens)
            return logits, new_state

        run.program = paged_prefill_chunk
        return run

    # -- state arena -------------------------------------------------------

    def fresh_state1(self) -> List[jax.Array]:
        """Zeroed batch-1 state leaves (a new request's non-positional
        cache state, carried across prefill chunks); made once and shared,
        since no program donates them."""
        if self._zero_state1 is None:
            self._zero_state1 = []
            for li in self.layout.state_leaf_ids:
                l = self.layout.leaves[li]
                shape = [1 if ax == l.batch_axis else s
                         for ax, s in enumerate(l.shape)]
                self._zero_state1.append(jnp.zeros(tuple(shape), l.dtype))
        return list(self._zero_state1)

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
        """Write batch-1 state leaves into slot ``slot`` of the arena, in
        one program whose slot index is already on the device (the slot
        indexes are uploaded once, with the first write)."""
        if self._jit_write_state is None:
            self._slot_ids = jax.device_put(
                [np.int32(i) for i in range(self.cfg.batch)],
                self._placement)
            self.h2d_uploads += 1

            @jax.jit
            def write(state, state1, slot):
                new = []
                for si, li in enumerate(self.layout.state_leaf_ids):
                    l = self.layout.leaves[li]
                    ax = min(l.batch_axis, state[si].ndim - 1)
                    one = state1[si].astype(state[si].dtype)
                    if ax != l.batch_axis:
                        one = jnp.moveaxis(one, l.batch_axis, ax)
                    new.append(jax.lax.dynamic_update_slice_in_dim(
                        state[si], one, slot, axis=ax))
                return new

            self._jit_write_state = write
        self.state = self._jit_write_state(self.state, list(state1),
                                           self._slot_ids[slot])

    # -- one-shot splice (models without chunked prefill) ------------------

    def splice_row(self, rid: int, slot: int, cache_b1, n_tokens: int
                   ) -> None:
        """Adopt a contiguous batch-1 cache (a one-shot prefill result)
        into pool pages + slot state.  Pages are allocated here; the
        jitted scatter writes ``ceil(n_tokens/pt)`` pages (masked, so the
        trace is shared across token counts)."""
        self.ensure(rid, n_tokens, slot)
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
        self.pool = self._jit_splice_row(self.pool, cache_b1,
                                         self.slot_pages[slot].copy(),
                                         np.int32(len(t.pages)))
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
        self.ensure(rid, rc.tokens, slot)
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
                if rid in self._slot_of:
                    self.slot_pages[self._slot_of[rid], j] = new
        n_alloc = len(owners)
        self._free = list(range(self.num_pages, n_alloc, -1))
        return len(moves)
