"""The serving scheduler's profiler spans (``repro.serve.engine``,
``repro.serve.paging``), read back from a ``jax.profiler`` trace taken
on the CPU: how they nest, the metadata they carry, and that tracing
leaves the tokens as they were."""

import glob
import os

import jax
import jax.numpy as jnp
import pytest
from jax.profiler import ProfileData

from repro.configs import get_config
from repro.models import build_model
from repro.serve import BatchScheduler, Request, ServeCfg

# reading an event's metadata warns about the binding's type on this JAX
pytestmark = pytest.mark.filterwarnings(
    "ignore:builtin type event_stats:DeprecationWarning")

PAGE = 4
# one to four pages each, more requests than slots
PROMPTS = [[1, 2, 3], [4] * 9, [5] * 5, [7] * 2, [9] * 13]


def serve(model, params, trace_dir=None):
    """Serve ``PROMPTS`` step by step, traced into ``trace_dir`` if given.
    Returns the tokens by rid, each step's ``decode_steps`` on entry, and
    for each step that decoded, how many slots got a decode token."""
    sched = BatchScheduler(model, params, ServeCfg(
        max_len=32, batch=3, cache_dtype=jnp.float32, page_tokens=PAGE))
    reqs = [Request(rid=100 + i, prompt=p, max_new=4)
            for i, p in enumerate(PROMPTS)]
    step_nums, decoded = [], []
    if trace_dir:
        jax.profiler.start_trace(trace_dir)
    try:
        for r in reqs:
            sched.submit(r)
        while sched.pending():
            before = {r.rid: len(r.generated) for r in reqs}
            step_nums.append(sched.decode_steps)
            sched.step()
            # a request's first token comes from its last prefill chunk
            n = sum(len(r.generated) - before[r.rid]
                    - (before[r.rid] == 0 < len(r.generated)) for r in reqs)
            if n:
                decoded.append(n)
    finally:
        if trace_dir:
            jax.profiler.stop_trace()
    return {r.rid: r.generated for r in reqs}, step_nums, decoded


def host_events(trace_dir):
    """(start, end, name, metadata) of the trace's ``serve.*`` spans and
    its host events of jitted calls, outer spans before the ones they
    hold."""
    path, = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    name = ev.name
                    if name.startswith(("serve.", "PjitFunction(")):
                        s = int(ev.start_ns)
                        out.append((s, s + int(ev.duration_ns), name,
                                    dict(ev.stats)))
    return sorted(out, key=lambda e: (e[0], -e[1]))


@pytest.fixture(scope="module")
def runs(tmp_path_factory):
    model = build_model(get_config("qwen2-72b", reduced=True))
    params = model.init(jax.random.PRNGKey(0))
    plain = serve(model, params)
    trace_dir = str(tmp_path_factory.mktemp("serve_trace"))
    traced = serve(model, params, trace_dir)
    events = host_events(trace_dir)
    spans = [e for e in events if e[2].startswith("serve.")]
    return {"plain": plain, "traced": traced, "events": events,
            "spans": spans}


def named(spans, *names):
    return [s for s in spans if s[2] in names]


def inside(span, outers):
    return any(o[0] <= span[0] and span[1] <= o[1] and o is not span
               for o in outers)


def test_tracing_leaves_the_tokens_bit_identical(runs):
    assert runs["traced"][0] == runs["plain"][0]
    assert all(len(t) == 4 for t in runs["plain"][0].values())


def test_phases_nest_inside_a_step_or_an_admission(runs):
    spans = runs["spans"]
    outer = named(spans, "serve.step", "serve.admit")
    phases = named(spans, "serve.prefill_chunk", "serve.decode",
                   "serve.sample")
    assert phases and all(inside(p, outer) for p in phases)
    # the pool's argument build sits inside the program that uses it
    users = named(spans, "serve.prefill_chunk", "serve.decode")
    pages = named(spans, "serve.pages")
    assert pages and all(inside(p, users) for p in pages)
    # admission runs from submit() too, outside any step
    steps = named(spans, "serve.step")
    assert any(not inside(a, steps) for a in named(spans, "serve.admit"))


def test_chunk_spans_carry_each_prompts_rid_and_chunk(runs):
    spans = runs["spans"]
    chunks = {}
    for s in named(spans, "serve.prefill_chunk"):
        chunks.setdefault(s[3]["rid"], []).append(s[3]["chunk"])
    assert chunks == {100 + i: list(range(-(-len(p) // PAGE)))
                      for i, p in enumerate(PROMPTS)}
    # each prompt's first token is sampled once, under its own rid, inside
    # its last chunk
    firsts = [s for s in named(spans, "serve.sample") if "rid" in s[3]]
    assert sorted(s[3]["rid"] for s in firsts) == sorted(chunks)
    for s in firsts:
        last = [c for c in named(spans, "serve.prefill_chunk")
                if c[3]["rid"] == s[3]["rid"]
                and c[3]["chunk"] == len(chunks[s[3]["rid"]]) - 1]
        assert inside(s, last)
    assert {s[3]["rid"] for s in named(spans, "serve.pages")
            if "rid" in s[3]} == set(chunks)


def test_decode_spans_count_the_slots_that_decoded(runs):
    _, step_nums, decoded = runs["traced"]
    slots = [s[3]["slots"] for s in named(runs["spans"], "serve.decode")]
    assert slots == decoded and max(slots) > 1
    assert [s[3]["step_num"] for s in named(runs["spans"], "serve.step")] \
        == step_nums


def test_the_page_pool_programs_have_stable_names(runs):
    names = {e[2] for e in runs["events"]}
    assert {"PjitFunction(paged_decode)",
            "PjitFunction(paged_prefill_chunk)"} <= names
