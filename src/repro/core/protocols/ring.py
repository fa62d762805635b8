"""Ring protocols: bandwidth-optimal RS / AG / AR on a torus axis.

Uni- and bidirectional variants.  The bidirectional ring splits the payload
in half and drives both torus directions concurrently, halving the beta
term — only valid when the axis has wraparound links (Topology.wraparound).

Every ring all-reduce is two pipeline stages — reduce-scatter then
all-gather — and the engine's nonblocking start/wait arms split exactly at
that seam: ``start`` runs the RS stage and returns the in-flight shard,
``wait`` runs the AG stage.  The blocking ``*_all_reduce_flat`` entry
points are the composition of the two, so the overlapped and blocking
paths are bit-identical by construction.

The RS combine step (summing the received partial into the local chunk)
optionally runs through the Pallas ``repro.kernels.local_reduce`` kernel
(``use_kernel=True``, same gating ``compression.py`` uses for quantize):
it streams VMEM tiles and accumulates in f32, which is a pure-bandwidth
win on TPU but NOT bit-identical to the jnp ``a + b`` path for sub-f32
dtypes — keep it off when exact blocking/overlap parity matters.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from repro.core.protocols import common as c


def _combine(acc: jax.Array, contrib: jax.Array,
             use_kernel: bool = False) -> jax.Array:
    """The RS combine step: acc + contrib, optionally via the Pallas
    tiled chunk-reduction kernel (f32 accumulation, cast back)."""
    if use_kernel:
        # same gating contract as compression's quantize: the kernel path
        # compiles on TPU and falls back to the jnp oracle elsewhere
        # (interpret mode is test-only — see repro.kernels.local_reduce.ops).
        from repro.kernels.local_reduce import ops as lr_ops
        return lr_ops.sum_chunks(
            jnp.stack([acc.reshape(-1), contrib.reshape(-1)]),
            dtype=acc.dtype).reshape(acc.shape)
    return acc + contrib


def ring_reduce_scatter_flat(x2d: jax.Array, axis_name: str,
                             use_kernel: bool = False) -> jax.Array:
    """x2d: (p, ...) per device, row j = chunk j (``common.chunk_view``).
    Returns this device's fully-reduced chunk.

    Device i ends with sum_j x2d[j-th device][i].  p-1 steps, (p-1)/p * n
    bytes per device: bandwidth-optimal.
    """
    p = x2d.shape[0]
    if p == 1:
        return x2d[0]
    i = c.axis_index(axis_name)
    fwd = c.fwd_perm(p)
    acc = c.dyn_chunk(x2d, i - 1)
    for s in range(1, p):
        acc = lax.ppermute(acc, axis_name, fwd)
        acc = _combine(acc, c.dyn_chunk(x2d, i - s - 1), use_kernel)
    return acc  # == reduced chunk i


class RingAllGatherRun:
    """Steppable ring all-gather: the wait-phase stage machine.

    One ``step()`` is one ring hop (one ``ppermute`` + placement) — the
    unit of per-stage ``progress()`` in the schedule IR.  ``result()``
    drains the remaining hops; the op sequence is identical to the old
    straight-line loop, so callers that never step early are
    bit-identical to the blocking path by construction.
    """

    def __init__(self, shard: jax.Array, axis_name: str):
        p = c.axis_size(axis_name)
        self.axis_name = axis_name
        self.p = p
        self.done = 0
        self.total = max(0, p - 1)
        self.cur = shard
        if p == 1:
            self.buf = shard[None]
            return
        self.i = c.axis_index(axis_name)
        self.fwd = c.fwd_perm(p)
        self.buf = c.dyn_put(jnp.zeros((p,) + shard.shape, shard.dtype),
                             shard, self.i)

    @property
    def remaining(self) -> int:
        return self.total - self.done

    def step(self, stages: int = 1) -> int:
        """Advance up to ``stages`` ring hops; returns hops taken."""
        stages = min(int(stages), self.remaining)
        for _ in range(stages):
            self.done += 1
            # now holds the shard of (i - done)
            self.cur = lax.ppermute(self.cur, self.axis_name, self.fwd)
            self.buf = c.dyn_put(self.buf, self.cur, self.i - self.done)
        return stages

    def result(self) -> jax.Array:
        self.step(self.remaining)
        return self.buf


def ring_all_gather_flat(shard: jax.Array, axis_name: str) -> jax.Array:
    """shard: (chunk,) -> (p, chunk) with row j = device j's shard."""
    return RingAllGatherRun(shard, axis_name).result()


def bidir_ring_reduce_scatter_flat(x2d: jax.Array, axis_name: str,
                                   use_kernel: bool = False) -> jax.Array:
    """Split each chunk in half; forward ring reduces the low halves,
    backward ring the high halves. Both directions are active every step."""
    p = x2d.shape[0]
    if p == 1:
        return x2d[0]
    chunk = x2d.shape[1]
    if chunk % 2:
        return ring_reduce_scatter_flat(x2d, axis_name, use_kernel)
    i = c.axis_index(axis_name)
    half = chunk // 2
    lo, hi = x2d[:, :half], x2d[:, half:]
    fwd, bwd = c.fwd_perm(p), c.bwd_perm(p)
    acc_f = c.dyn_chunk(lo, i - 1)
    acc_b = c.dyn_chunk(hi, i + 1)
    for s in range(1, p):
        acc_f = lax.ppermute(acc_f, axis_name, fwd)
        acc_b = lax.ppermute(acc_b, axis_name, bwd)
        acc_f = _combine(acc_f, c.dyn_chunk(lo, i - s - 1), use_kernel)
        acc_b = _combine(acc_b, c.dyn_chunk(hi, i + s + 1), use_kernel)
    return jnp.concatenate([acc_f, acc_b])  # reduced chunk i (both halves)


class BidirRingAllGatherRun:
    """Steppable bidirectional ring all-gather.  One ``step()`` is one
    double-hop (both torus directions active), so the stage count is
    ``ceil((p-1)/2)`` — matching ``protocol_stage_counts``' wait split
    for the bidirectional ring."""

    def __init__(self, shard: jax.Array, axis_name: str):
        p = c.axis_size(axis_name)
        self.axis_name = axis_name
        self.p = p
        self.done = 0
        self.n_f = p // 2
        self.n_b = (p - 1) // 2
        self.total = max(self.n_f, self.n_b)
        if p == 1:
            self.buf = shard[None]
            return
        self.i = c.axis_index(axis_name)
        self.fwd, self.bwd = c.fwd_perm(p), c.bwd_perm(p)
        self.buf = c.dyn_put(jnp.zeros((p,) + shard.shape, shard.dtype),
                             shard, self.i)
        self.cur_f = shard  # fwd: after s hops holds shard of (i - s)
        self.cur_b = shard  # bwd: after s hops holds shard of (i + s)

    @property
    def remaining(self) -> int:
        return self.total - self.done

    def step(self, stages: int = 1) -> int:
        stages = min(int(stages), self.remaining)
        for _ in range(stages):
            self.done += 1
            s = self.done
            if s <= self.n_f:
                self.cur_f = lax.ppermute(self.cur_f, self.axis_name,
                                          self.fwd)
                self.buf = c.dyn_put(self.buf, self.cur_f, self.i - s)
            if s <= self.n_b:
                self.cur_b = lax.ppermute(self.cur_b, self.axis_name,
                                          self.bwd)
                self.buf = c.dyn_put(self.buf, self.cur_b, self.i + s)
        return stages

    def result(self) -> jax.Array:
        self.step(self.remaining)
        return self.buf


def bidir_ring_all_gather_flat(shard: jax.Array, axis_name: str) -> jax.Array:
    """Gather by sending simultaneously in both ring directions:
    ceil((p-1)/2) steps with both links busy."""
    return BidirRingAllGatherRun(shard, axis_name).result()


# ---------------------------------------------------------------------------
# Stage-split all-reduce: start = RS stage, finish = AG stage.  The blocking
# entry points compose the two, so start/wait callers are bit-identical.
# ---------------------------------------------------------------------------

def ring_all_reduce_start(x2d: jax.Array, axis_name: str,
                          use_kernel: bool = False) -> jax.Array:
    """First pipeline stage of the ring all-reduce (the reduce-scatter):
    returns the in-flight reduced shard."""
    return ring_reduce_scatter_flat(x2d, axis_name, use_kernel)


def ring_all_reduce_finish(shard: jax.Array, axis_name: str) -> jax.Array:
    """Remaining stage (the all-gather) on an in-flight shard."""
    return ring_all_gather_flat(shard, axis_name)


def bidir_ring_all_reduce_start(x2d: jax.Array, axis_name: str,
                                use_kernel: bool = False) -> jax.Array:
    return bidir_ring_reduce_scatter_flat(x2d, axis_name, use_kernel)


def bidir_ring_all_reduce_finish(shard: jax.Array,
                                 axis_name: str) -> jax.Array:
    return bidir_ring_all_gather_flat(shard, axis_name)


def ring_all_reduce_flat(x2d: jax.Array, axis_name: str,
                         use_kernel: bool = False) -> jax.Array:
    """RS + AG: the classic bandwidth-optimal all-reduce."""
    shard = ring_all_reduce_start(x2d, axis_name, use_kernel)
    return ring_all_reduce_finish(shard, axis_name)


def bidir_ring_all_reduce_flat(x2d: jax.Array, axis_name: str,
                               use_kernel: bool = False) -> jax.Array:
    shard = bidir_ring_all_reduce_start(x2d, axis_name, use_kernel)
    return bidir_ring_all_reduce_finish(shard, axis_name)
