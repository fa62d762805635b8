"""Serve-layer slot scheduler coverage: admission into finished slots,
eos handling (including eos/max_new hit at prefill), decode shape
stability (no recompilation across admissions), admission control
(max_queue shedding), sampling purity in (seed, rid, position), and the
elastic drain/resume surface (snapshot -> shrink -> re-admit, in memory
and via disk)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.serve.controller import plan_serve_batch
from repro.serve.engine import (BatchScheduler, Request, ServeCfg,
                                extract_cache, splice_cache)
from repro.serve.state import load_snapshot, save_snapshot

VOCAB = 32


class FakeLM:
    """Deterministic LM: next token = (last token + 1) % VOCAB.

    Matches the model surface BatchScheduler needs (init_caches / prefill /
    decode_step / cache_specs); ``decode_traces`` counts jit retraces —
    the body only runs while tracing under the scheduler's jit."""

    def __init__(self):
        self.decode_traces = 0

    def init_caches(self, b, max_len, dtype=jnp.float32):
        return {"pos": jnp.zeros((b, 1), jnp.int32),
                "kv": jnp.zeros((b, max_len, 2), dtype)}

    def cache_specs(self):
        return {"pos": P("data", None), "kv": P("data", None, None)}

    def prefill(self, params, batch, caches):
        toks = batch["tokens"]
        nxt = (toks[:, -1] + 1) % VOCAB
        return (jax.nn.one_hot(nxt, VOCAB),
                {"pos": caches["pos"] + toks.shape[1], "kv": caches["kv"]})

    def decode_step(self, params, batch, caches):
        self.decode_traces += 1
        tok = batch["tokens"][:, 0]
        nxt = (tok + 1) % VOCAB
        return (jax.nn.one_hot(nxt, VOCAB),
                {"pos": caches["pos"] + 1, "kv": caches["kv"]})


def make_sched(batch=2, eos_id=-1, max_len=64):
    model = FakeLM()
    cfg = ServeCfg(max_len=max_len, batch=batch, eos_id=eos_id)
    return model, BatchScheduler(model, {"w": jnp.zeros(())}, cfg)


def test_admission_into_finished_slots():
    _, sched = make_sched(batch=2)
    sched.submit(Request(rid=0, prompt=[1], max_new=2))
    sched.submit(Request(rid=1, prompt=[5], max_new=6))
    sched.submit(Request(rid=2, prompt=[9], max_new=2))

    sched.step()
    # r0 finished in the first decode step; its slot must be free
    assert sched.slots[0] is None and sched.slots[1].rid == 1
    assert [r.rid for r in sched.completed] == [0]

    sched.step()
    # r2 was admitted into the freed slot 0 (not a new slot)
    assert [r.rid for r in sched.completed] == [0, 2]
    assert sched.slots[0] is None and sched.slots[1].rid == 1

    done = sched.run()
    assert [r.rid for r in done] == [0, 2, 1]
    by_rid = {r.rid: r.generated for r in done}
    assert by_rid[0] == [2, 3]
    assert by_rid[1] == [6, 7, 8, 9, 10, 11]
    assert by_rid[2] == [10, 11]


def test_eos_stops_early_and_frees_slot():
    _, sched = make_sched(batch=1, eos_id=7)
    sched.submit(Request(rid=0, prompt=[5], max_new=10))
    sched.submit(Request(rid=1, prompt=[20], max_new=2))
    done = sched.run()
    by_rid = {r.rid: r.generated for r in done}
    # r0: prefill 6, decode 7 == eos -> stops at 2 tokens, slot freed for r1
    assert by_rid[0] == [6, 7]
    assert by_rid[1] == [21, 22]


def test_eos_at_prefill_never_occupies_slot():
    _, sched = make_sched(batch=1, eos_id=7)
    sched.submit(Request(rid=0, prompt=[6], max_new=5))   # prefill -> eos
    sched.submit(Request(rid=1, prompt=[10], max_new=2))
    sched._admit()
    # r0 completed straight from prefill; the slot went to r1
    assert [r.rid for r in sched.completed] == [0]
    assert sched.completed[0].generated == [7]
    assert sched.slots[0].rid == 1
    done = sched.run()
    assert {r.rid: r.generated for r in done}[1] == [11, 12]


def test_max_new_one_gets_exactly_one_token():
    # Regression: a max_new=1 request used to occupy a slot and receive a
    # second (spurious) decode token.
    _, sched = make_sched(batch=2)
    sched.submit(Request(rid=0, prompt=[3], max_new=1))
    sched.submit(Request(rid=1, prompt=[8], max_new=3))
    done = sched.run()
    by_rid = {r.rid: r.generated for r in done}
    assert by_rid[0] == [4], by_rid
    assert by_rid[1] == [9, 10, 11]


def test_no_recompilation_across_admissions():
    model, sched = make_sched(batch=2)
    for rid in range(6):
        sched.submit(Request(rid=rid, prompt=[rid], max_new=1 + rid % 3))
    done = sched.run()
    assert len(done) == 6
    # continuous batching at fixed shapes: decode traced exactly once
    assert model.decode_traces == 1, model.decode_traces
    for r in done:
        want = [(r.prompt[-1] + 1 + i) % VOCAB for i in range(r.max_new)]
        assert r.generated == want, (r.rid, r.generated, want)


def test_splice_cache_replaces_one_batch_row():
    full = {"kv": jnp.zeros((4, 8), jnp.float32)}
    one = {"kv": jnp.ones((1, 8), jnp.float32)}
    out = splice_cache(full, one, 2, {"kv": P("data", None)})
    np.testing.assert_array_equal(np.asarray(out["kv"][2]), np.ones(8))
    assert float(jnp.abs(out["kv"]).sum()) == 8.0


def test_extract_cache_inverts_splice():
    specs = {"kv": P("data", None)}
    full = {"kv": jnp.arange(32, dtype=jnp.float32).reshape(4, 8)}
    one = extract_cache(full, 2, specs)
    assert one["kv"].shape == (1, 8)
    np.testing.assert_array_equal(np.asarray(one["kv"][0]),
                                  np.asarray(full["kv"][2]))
    back = splice_cache({"kv": jnp.zeros((4, 8), jnp.float32)}, one, 2,
                        specs)
    np.testing.assert_array_equal(np.asarray(back["kv"][2]),
                                  np.asarray(full["kv"][2]))


# ---------------------------------------------------------------------------
# PR 7: admission control, sampling purity, drain/resume
# ---------------------------------------------------------------------------


class CacheLM(FakeLM):
    """Cache-SENSITIVE fake: next token = (last + acc) % VOCAB where the
    cache carries ``acc`` (prompt sum at prefill, +1 per decode step).
    A resume that re-prefilled, zeroed, or misplaced a slot's cache rows
    produces visibly different tokens — what the drain/resume tests need
    (FakeLM's chain only reads the previous token, which a broken resume
    would reproduce by accident)."""

    def init_caches(self, b, max_len, dtype=jnp.float32):
        c = super().init_caches(b, max_len, dtype)
        c["acc"] = jnp.zeros((b, 1), jnp.int32)
        return c

    def cache_specs(self):
        s = super().cache_specs()
        s["acc"] = P("data", None)
        return s

    def prefill(self, params, batch, caches):
        toks = batch["tokens"]
        acc = caches["acc"] + toks.sum(axis=1, keepdims=True)
        nxt = (toks[:, -1] + acc[:, 0]) % VOCAB
        return (jax.nn.one_hot(nxt, VOCAB),
                {"pos": caches["pos"] + toks.shape[1],
                 "kv": caches["kv"], "acc": acc})

    def decode_step(self, params, batch, caches):
        self.decode_traces += 1
        tok = batch["tokens"][:, 0]
        acc = caches["acc"] + 1
        nxt = (tok + acc[:, 0]) % VOCAB
        return (jax.nn.one_hot(nxt, VOCAB),
                {"pos": caches["pos"] + 1, "kv": caches["kv"],
                 "acc": acc})


def _expected_cache_lm(prompt, max_new):
    """Reference token stream for CacheLM."""
    acc = sum(prompt)
    out = [(prompt[-1] + acc) % VOCAB]
    while len(out) < max_new:
        acc += 1
        out.append((out[-1] + acc) % VOCAB)
    return out


def test_plan_serve_batch():
    # 8 slots over 8-way data: 1 seq/device; survivors keep that load
    assert plan_serve_batch(8, 8, 6) == 6
    assert plan_serve_batch(8, 8, 8) == 8
    # never exceeds the original batch on regrowth
    assert plan_serve_batch(8, 8, 12) == 8
    # uneven per-device load rounds up, floor of 1
    assert plan_serve_batch(6, 4, 2) == 4
    assert plan_serve_batch(4, 1, 1) == 4     # single-device: unchanged
    assert plan_serve_batch(1, 8, 1) == 1
    with pytest.raises(ValueError):
        plan_serve_batch(8, 8, 0)


def test_eager_admission_and_ttft():
    _, sched = make_sched(batch=2)
    r = Request(rid=0, prompt=[1], max_new=4)
    assert sched.submit(r)
    # a free slot admits at submit time, not at the first step
    assert sched.slots[0] is not None and sched.slots[0].rid == 0
    assert r.t_submit is not None and r.t_first is not None
    assert r.ttft_s is not None and r.ttft_s >= 0.0


def test_max_queue_sheds_over_bound():
    model = FakeLM()
    cfg = ServeCfg(max_len=64, batch=1, max_queue=1)
    sched = BatchScheduler(model, {"w": jnp.zeros(())}, cfg)
    assert sched.submit(Request(rid=0, prompt=[1], max_new=4))   # slot
    assert sched.submit(Request(rid=1, prompt=[2], max_new=4))   # queued
    assert not sched.submit(Request(rid=2, prompt=[3], max_new=4))  # shed
    assert [r.rid for r in sched.shed] == [2]
    done = sched.run()
    assert sorted(r.rid for r in done) == [0, 1]


def test_sampling_pure_in_seed_rid_pos():
    """Non-greedy tokens must not depend on batch composition, slot
    index, or admission order — the property that makes elastic resume
    bit-identical."""
    def run(batch):
        model = FakeLM()
        cfg = ServeCfg(max_len=64, batch=batch, greedy=False, seed=7)
        sched = BatchScheduler(model, {"w": jnp.zeros(())}, cfg)
        for rid in range(4):
            sched.submit(Request(rid=rid, prompt=[rid + 1, rid + 2],
                                 max_new=5))
        return {r.rid: r.generated for r in sched.run()}

    wide, narrow = run(4), run(1)
    assert wide == narrow
    # and a different seed actually changes the streams
    model = FakeLM()
    cfg = ServeCfg(max_len=64, batch=4, greedy=False, seed=8)
    sched = BatchScheduler(model, {"w": jnp.zeros(())}, cfg)
    for rid in range(4):
        sched.submit(Request(rid=rid, prompt=[rid + 1, rid + 2],
                             max_new=5))
    other = {r.rid: r.generated for r in sched.run()}
    assert other != wide


def test_snapshot_shrink_resume_bit_identical():
    """Drain at a step boundary -> rebuild on a SMALLER batch: in-flight
    requests resume from their cache rows (cache-sensitive fake: any
    re-prefill or cache mixup diverges), overflow parks then re-admits
    into freed slots, and every token stream matches the uninterrupted
    reference."""
    model = CacheLM()
    cfg = ServeCfg(max_len=64, batch=3, cache_dtype=jnp.float32)
    sched = BatchScheduler(model, {"w": jnp.zeros(())}, cfg)
    reqs = [Request(rid=i, prompt=[i + 1, i + 3], max_new=6)
            for i in range(5)]
    for r in reqs:
        sched.submit(r)
    sched.step()
    sched.step()

    snap = sched.snapshot()
    assert len(snap.inflight) == 3 and len(snap.queue) == 2
    # the drained pages must match each request's progress: cache
    # positions = prompt len (2) + decode steps (generated minus the
    # prefill token), and only that many positions' pages moved
    for s in snap.inflight:
        want = 2 + len(s.req.generated) - 1
        assert s.cache.tokens == want
        pt = sched.pool.page_tokens
        assert all(p.shape[0] == -(-want // pt) for p in s.cache.pages)

    small = ServeCfg(max_len=64, batch=2, cache_dtype=jnp.float32)
    sched2 = BatchScheduler.from_snapshot(model, {"w": jnp.zeros(())},
                                          small, snap)
    # 2 resumed into slots, 1 parked awaiting a freed slot, queue intact
    assert sum(s is not None for s in sched2.slots) == 2
    assert len(sched2.parked) == 1 and len(sched2.queue) == 2
    done = sched2.run()
    assert sorted(r.rid for r in done) == [0, 1, 2, 3, 4]
    for r in done:
        assert r.generated == _expected_cache_lm(r.prompt, r.max_new), \
            (r.rid, r.generated)


def test_snapshot_disk_roundtrip(tmp_path):
    model = CacheLM()
    cfg = ServeCfg(max_len=32, batch=2, cache_dtype=jnp.float32,
                   seed=3, max_queue=5)
    sched = BatchScheduler(model, {"w": jnp.zeros(())}, cfg)
    for i in range(3):
        sched.submit(Request(rid=i, prompt=[i + 2], max_new=5))
    sched.step()
    save_snapshot(str(tmp_path), sched.snapshot(), step=1)

    snap = load_snapshot(str(tmp_path), model)
    # cfg (incl. seed / max_queue / dtype) and books survive the roundtrip
    assert snap.cfg == cfg
    assert len(snap.inflight) == 2 and len(snap.queue) == 1
    sched2 = BatchScheduler.from_snapshot(model, {"w": jnp.zeros(())},
                                          cfg, snap)
    done = sched2.run()
    assert sorted(r.rid for r in done) == [0, 1, 2]
    for r in done:
        assert r.generated == _expected_cache_lm(r.prompt, r.max_new)


# ---------------------------------------------------------------------------
# PR 9: paged pool + chunked prefill
# ---------------------------------------------------------------------------


class ChunkLM(CacheLM):
    """Chunk-capable cache-sensitive fake: same token chain as CacheLM,
    with a ``prefill_chunk`` that accumulates ``acc`` one page at a time
    (masked by ``valid_len``, so right-padding must not leak) and a
    ``chunk_traces`` counter — the chunked-vs-one-shot bit-identity and
    prefill trace-count tests run on this."""

    supports_chunked_prefill = True

    def __init__(self):
        super().__init__()
        self.chunk_traces = 0

    def prefill_chunk(self, params, batch, caches, *, q_offset, valid_len,
                      last_index):
        self.chunk_traces += 1
        toks = batch["tokens"]                       # (1, pt), 0-padded
        pt = toks.shape[1]
        posn = q_offset + jnp.arange(pt)[None, :]
        valid = posn < valid_len
        acc = caches["acc"] + jnp.where(valid, toks, 0).sum(
            axis=1, keepdims=True)
        nxt = (toks[:, last_index] + acc[:, 0]) % VOCAB
        pos = jnp.minimum(caches["pos"] + pt, valid_len)
        return (jax.nn.one_hot(nxt, VOCAB),
                {"pos": pos, "kv": caches["kv"], "acc": acc})


def _chunk_sched(batch=2, max_len=32, page_tokens=4, pool_pages=None,
                 chunked=True):
    model = ChunkLM()
    cfg = ServeCfg(max_len=max_len, batch=batch, cache_dtype=jnp.float32,
                   page_tokens=page_tokens, pool_pages=pool_pages,
                   chunked_prefill=chunked)
    return model, BatchScheduler(model, {"w": jnp.zeros(())}, cfg)


def test_chunked_prefill_bit_identical_to_one_shot():
    """Prompts spanning 1 to 3+ pages, chunked on vs off: every stream
    must equal the uninterrupted CacheLM reference bit for bit."""
    prompts = [[5], [1, 2, 3], [2] * 4, [1] * 5, [3] * 11]

    def run(chunked):
        _, sched = _chunk_sched(batch=2, page_tokens=4, chunked=chunked)
        for i, p in enumerate(prompts):
            sched.submit(Request(rid=i, prompt=list(p), max_new=4))
        return {r.rid: r.generated for r in sched.run()}

    on, off = run(True), run(False)
    assert on == off
    for i, p in enumerate(prompts):
        assert on[i] == _expected_cache_lm(p, 4), (i, on[i])


def test_no_recompilation_across_chunked_prefills():
    """Chunks are padded to the page boundary, so prefill compiles ONCE
    across every prompt length (and decode stays at one trace)."""
    model, sched = _chunk_sched(batch=2, page_tokens=4)
    for i, n in enumerate([1, 2, 4, 5, 9, 12]):
        sched.submit(Request(rid=i, prompt=[(i + j) % VOCAB
                                            for j in range(n)], max_new=3))
    done = sched.run()
    assert len(done) == 6
    assert model.chunk_traces == 1, model.chunk_traces
    assert model.decode_traces == 1, model.decode_traces
    for r in done:
        assert r.generated == _expected_cache_lm(r.prompt, 3), r.rid


def test_resident_bytes_scale_with_generated_not_max_len():
    """Page-granular residency: live bytes track allocated pages (=
    ceil(tokens/pt) per request), strictly under the contiguous
    batch*max_len layout for short requests."""
    _, sched = _chunk_sched(batch=2, max_len=32, page_tokens=4)
    sched.submit(Request(rid=0, prompt=[1, 2], max_new=8))
    sched.submit(Request(rid=1, prompt=[3], max_new=8))
    sched.step()
    pool = sched.pool
    want_pages = sum(-(-t.tokens // pool.page_tokens)
                     for t in pool.tables.values())
    assert pool.pages_allocated == want_pages
    assert pool.resident_bytes() < pool.contiguous_bytes()
    # and the pool is capacity-par with contiguous when fully allocated
    assert pool.pages_total == 2 * (32 // 4)


def test_preemption_parks_lifo_and_streams_stay_bit_identical():
    """An undercommitted pool preempts the most recently admitted slot
    mid-decode (pages parked to host), resumes it after the survivor
    frees pages — and determinism keeps every stream equal to the
    uninterrupted reference."""
    # 4 pages of 4 = 16 positions; two rid streams need ~14 each, so they
    # cannot coexist to completion: one must park and resume.
    _, sched = _chunk_sched(batch=2, max_len=32, page_tokens=4,
                            pool_pages=4)
    reqs = [Request(rid=i, prompt=[i + 1, i + 2], max_new=12)
            for i in range(2)]
    for r in reqs:
        sched.submit(r)
    parked_seen = 0
    while sched.pending():
        sched.step()
        parked_seen = max(parked_seen, len(sched.parked))
        sched.pool.check_integrity()
    assert parked_seen >= 1                    # preemption actually fired
    for r in sched.completed:
        assert r.generated == _expected_cache_lm(r.prompt, r.max_new), \
            (r.rid, r.generated)


def test_pool_too_small_for_one_request_raises():
    _, sched = _chunk_sched(batch=1, max_len=32, page_tokens=4,
                            pool_pages=2)
    sched.submit(Request(rid=0, prompt=[1, 2], max_new=12))  # ~14 tokens
    with pytest.raises(Exception) as ei:
        sched.run()
    assert "pool" in str(ei.value) or "page" in str(ei.value)


def test_snapshot_mid_chunked_prefill_requeues_and_matches():
    """Draining while a long prompt is mid-prefill (no token emitted)
    returns it to the queue head; the rebuilt scheduler re-prefills it
    bit-identically."""
    model, sched = _chunk_sched(batch=1, max_len=32, page_tokens=4)
    long = Request(rid=0, prompt=[1] * 10, max_new=4)      # 3 chunks
    sched.submit(long)                                     # chunk 1 ran
    assert 0 in sched._prefills and long.generated == []
    snap = sched.snapshot()
    assert len(snap.inflight) == 0
    assert [r.rid for r in snap.queue] == [0]
    cfg = ServeCfg(max_len=32, batch=1, cache_dtype=jnp.float32,
                   page_tokens=4)
    sched2 = BatchScheduler.from_snapshot(model, {"w": jnp.zeros(())},
                                          cfg, snap)
    done = sched2.run()
    assert done[0].generated == _expected_cache_lm(long.prompt, 4)


def test_from_snapshot_sheds_queue_tail_under_max_queue():
    model = CacheLM()
    cfg = ServeCfg(max_len=64, batch=4, cache_dtype=jnp.float32)
    sched = BatchScheduler(model, {"w": jnp.zeros(())}, cfg)
    for i in range(8):
        sched.submit(Request(rid=i, prompt=[i + 1], max_new=6))
    sched.step()
    snap = sched.snapshot()          # 4 in flight, 4 queued

    # shrink to 2 slots with a backlog bound of 3: 2 resume, 2 park,
    # queue gets 3 - 2 = 1 spot -> 3 of the 4 queued are shed
    small = ServeCfg(max_len=64, batch=2, cache_dtype=jnp.float32,
                     max_queue=3)
    sched2 = BatchScheduler.from_snapshot(model, {"w": jnp.zeros(())},
                                          small, snap)
    assert len(sched2.parked) == 2
    assert len(sched2.shed) == 3
    done = sched2.run()
    # in-flight work is never shed; every surviving request finishes right
    assert sorted(r.rid for r in done) == [0, 1, 2, 3, 4]
    for r in done:
        assert r.generated == _expected_cache_lm(r.prompt, r.max_new)


# ---------------------------------------------------------------------------
# Paged decode: a GQA model attends to the pool's pages directly
# ---------------------------------------------------------------------------

GQA_PROMPTS = [[1, 2, 3], [4] * 9, [5] * 5, [7] * 2, [9] * 13,
               list(range(20))]


class Spans:
    """Stands in for ``TraceAnnotation`` in the engine: records each
    span's name and metadata, keyword and ``set_metadata`` alike."""

    def __init__(self):
        self.log = []

    def __call__(self, name, **meta):
        self.log.append((name, meta))
        return self

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def set_metadata(self, **meta):
        self.log[-1][1].update(meta)

    def named(self, name):
        return [m for n, m in self.log if n == name]


def _tiny(arch="qwen2-72b"):
    from repro.configs import get_config
    from repro.models import build_model
    model = build_model(get_config(arch, reduced=True))
    return model, model.init(jax.random.PRNGKey(0))


def _gqa_sched(model, params, arena=False, monkeypatch=None):
    from repro.serve import paging
    cfg = ServeCfg(max_len=32, batch=3, cache_dtype=jnp.float32,
                   page_tokens=4)
    if not arena:
        return BatchScheduler(model, params, cfg)
    with monkeypatch.context() as m:    # the program non-GQA models run
        m.setattr(paging.PageLayout, "kv_pages", property(lambda s: False))
        return BatchScheduler(model, params, cfg)


@pytest.mark.parametrize("attention", ["oracle", "kernel"])
def test_paged_decode_streams_equal_the_arena_program(attention,
                                                      monkeypatch):
    import functools
    from repro.models import layers
    model, params = _tiny()
    arena = _gqa_sched(model, params, arena=True, monkeypatch=monkeypatch)
    if attention == "kernel":       # the Pallas kernel, interpreted
        monkeypatch.setattr(layers.paged_ops, "paged_attention",
                            functools.partial(
                                layers.paged_ops.paged_attention,
                                force_kernel=True, interpret=True))
    paged = _gqa_sched(model, params)
    assert paged.pool.paged and not arena.pool.paged
    streams = []
    for sched in (paged, arena):
        for i, p in enumerate(GQA_PROMPTS):
            sched.submit(Request(rid=i, prompt=list(p), max_new=6))
        streams.append({r.rid: r.generated for r in sched.run()})
        sched.pool.check_integrity()
    assert streams[0] == streams[1]
    assert all(len(t) == 6 for t in streams[0].values())


def test_paged_decode_logits_and_cache_match_the_arena():
    """One decode of the same pool, both ways: the logits agree, the new
    token's K/V is the one the arena program writes into its row, and the
    pool's paged program writes it into its page and nothing else."""
    model, params = _tiny()
    sched = _gqa_sched(model, params)
    for i, p in enumerate(GQA_PROMPTS[:3]):
        sched.submit(Request(rid=i, prompt=list(p), max_new=8))
    while sched._prefills:
        sched.step()
    pool = sched.pool
    rids = [s.rid for s in sched.slots]
    mask = [True, False, True]                     # slot 1 sits this out
    active = jnp.asarray(mask)
    table = jnp.asarray(pool.slot_pages)
    tok = jnp.asarray(sched._next_tok)[:, None]
    arena = pool._assemble(pool.pool, pool.state, table)
    want, want_caches = model.decode_step(params, {"tokens": tok}, arena)
    got, got_caches = model.decode_step_paged(
        params, {"tokens": tok}, pool._paged_view(pool.pool, pool.state),
        table, active)
    np.testing.assert_allclose(np.asarray(got)[[0, 2]],
                               np.asarray(want)[[0, 2]], atol=1e-4,
                               rtol=1e-4)
    c_want = want_caches["stage0"]["layer0"]
    c_got = got_caches["stage0"]["layer0"]
    lens = [pool.tables[r].tokens for r in rids]
    for slot in (0, 2):
        for f in ("k", "v"):
            np.testing.assert_allclose(
                np.asarray(c_got[f][:, slot]),
                np.asarray(c_want[f][:, slot, lens[slot]]), atol=1e-5,
                rtol=1e-5)
    before = [np.asarray(p) for p in pool.pool]
    np.testing.assert_array_equal(np.asarray(c_got["len"]),
                                  np.asarray(pool.state[0])
                                  + np.asarray([1, 0, 1]))
    sched._decode(params, sched._next_tok, sched._rids, sched._pos,
                  [r if a else None for r, a in zip(rids, mask)], mask)
    pt = pool.page_tokens
    for leaf, f in ((0, "k"), (1, "v")):
        after = np.asarray(pool.pool[leaf])
        want_pool = before[leaf].copy()
        for slot in (0, 2):
            n = lens[slot]
            page = pool.tables[rids[slot]].pages[n // pt]
            want_pool[page, n % pt] = np.asarray(c_got[f][:, slot])
        np.testing.assert_allclose(after, want_pool, atol=1e-6, rtol=1e-6)
    assert [pool.tables[r].tokens for r in rids] == \
        [lens[0] + 1, lens[1], lens[2] + 1]


def test_paged_decode_donates_the_pool_and_the_state_arena():
    model, params = _tiny()
    sched = _gqa_sched(model, params)
    sched.submit(Request(rid=0, prompt=[1, 2, 3], max_new=4))
    assert not sched._prefills               # one chunk: decoding already
    old = list(sched.pool.pool) + list(sched.pool.state)
    sched.step()
    assert sched.decode_steps == 1
    assert all(a.is_deleted() for a in old)


def test_decode_span_reports_the_paged_path_and_its_live_pages(monkeypatch):
    from repro.serve import engine
    model, params = _tiny()
    sched = _gqa_sched(model, params)
    spans = Spans()
    monkeypatch.setattr(engine, "TraceAnnotation", spans)
    live = []
    decode = sched._decode

    def counted(params, tok, rids, pos, slot_rids, mask):
        pool = sched.pool
        live.append(sum(pool.pages_for(pool.tables[r].tokens)
                        for r, a in zip(slot_rids, mask) if a))
        return decode(params, tok, rids, pos, slot_rids, mask)

    sched._decode = counted
    for i, p in enumerate(GQA_PROMPTS):
        sched.submit(Request(rid=i, prompt=list(p), max_new=6))
    sched.run()
    meta = spans.named("serve.decode")
    assert len(meta) == len(live) == sched.decode_steps
    assert all(m["paged"] == 1 for m in meta)
    assert [m["kv_pages"] for m in meta] == live
    assert max(live) > 3


@pytest.mark.parametrize("arena", [False, True], ids=["paged", "arena"])
def test_steady_steps_upload_once_a_program_and_read_once_a_step(
        arena, monkeypatch):
    """In steady state each decode or chunk program gets its host-side
    arguments in one explicit upload, and a step reads its tokens back in
    one explicit read, plus one for a prompt's first token.  Under the
    guard any implicit transfer raises."""
    from repro.serve import engine
    model, params = _tiny()
    sched = _gqa_sched(model, params, arena=arena, monkeypatch=monkeypatch)
    assert sched.pool.paged != arena
    for rid, p in enumerate(([1, 2, 3], [4] * 5)):
        sched.submit(Request(rid=rid, prompt=p, max_new=12))
    while sched._prefills:
        sched.step()
    # four chunks: the first runs at submit, the last in the third step
    late = Request(rid=2, prompt=list(range(1, 14)), max_new=2)
    sched.submit(late)
    assert list(sched._prefills) == [2]
    spans = Spans()
    monkeypatch.setattr(engine, "TraceAnnotation", spans)
    ups, reads = sched.h2d_uploads, sched.d2h_reads
    with jax.transfer_guard("disallow"):
        for _ in range(4):
            sched.step()
    assert late.done and not sched._prefills
    decodes = spans.named("serve.decode")
    chunks = spans.named("serve.prefill_chunk")
    assert [m["slots"] for m in decodes] == [2, 2, 3, 2]
    assert len(chunks) == 3
    assert sched.h2d_uploads - ups == len(decodes) + len(chunks)
    assert sched.d2h_reads - reads == 4 + 1
    samples = spans.named("serve.sample")
    assert [m.get("rid") for m in samples] == [None, None, 2, None, None]
    assert all(m["reads"] == 1 for m in samples)
    sched.pool.check_integrity()


@pytest.mark.parametrize("arch", ["mamba2-1.3b", "deepseek-v3-671b"])
def test_models_without_an_all_gqa_cache_keep_the_arena(arch, monkeypatch):
    from repro.serve import engine
    model, params = _tiny(arch)
    cfg = ServeCfg(max_len=16, batch=2, cache_dtype=jnp.float32,
                   page_tokens=4)
    sched = BatchScheduler(model, params, cfg)
    assert not sched.pool.paged
    spans = Spans()
    monkeypatch.setattr(engine, "TraceAnnotation", spans)
    for i in range(3):
        sched.submit(Request(rid=i, prompt=[i + 1] * (3 + i), max_new=3))
    done = sched.run()
    assert sorted(len(r.generated) for r in done) == [3, 3, 3]
    meta = spans.named("serve.decode")
    assert meta and all(m["paged"] == 0 for m in meta)
    pps = sched.pool.layout.pages_per_slot
    assert all(m["kv_pages"] == cfg.batch * pps for m in meta)


@pytest.mark.parametrize("arch,backend,devices,paged", [
    ("qwen2-72b", "cpu", 1, True),
    ("qwen2-72b", "cpu", 8, True),      # the oracle partitions like jnp
    ("qwen2-72b", "tpu", 1, True),
    ("qwen2-72b", "tpu", 4, False),     # a Mosaic kernel spans one device
    ("granite-34b", "cpu", 1, True),
    ("granite-34b", "tpu", 1, False),   # one bf16 KV head: no page copy
    ("nemotron-4-340b", "tpu", 1, False),   # head dim 192
    ("mamba2-1.3b", "cpu", 1, False),
])
def test_decode_program_follows_layout_backend_and_mesh(arch, backend,
                                                        devices, paged,
                                                        monkeypatch):
    """On the chip the paged decode is always the kernel: a layout the
    kernel cannot copy, or a decode over a mesh, keeps the arena."""
    from repro.configs import get_config
    from repro.models import build_model
    from repro.serve.paging import decodes_paged, probe_layout
    model = build_model(get_config(arch))
    layout = probe_layout(model, 64, 16, dtype=jnp.bfloat16)
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    assert decodes_paged(layout, devices) == paged
