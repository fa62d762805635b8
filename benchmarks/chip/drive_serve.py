"""Serving driver: open-loop requests into the program's ``BatchScheduler``.

The scheduler is built as the serving launcher builds it
(``repro.launch.serve``): a ``repro.comm`` session over the chips, the
cache in the weights' dtype, and the page size and chunked prefill left
at the server's defaults.  Set-up first sends one short request into
every slot, so that every program and every slot's bookkeeping has run
before any timed request.  Then requests from ``loadgen.arrivals``
arrive on their schedule from ``warm_s`` seconds before the window opens,
so that the window opens in steady state; the window is ``--seconds``
long, and after it the loop runs on, taking no new request, until every
request due in the window has its first token (at most ``grace_s``).

Every token is stamped with the time the ``step()`` (or ``submit()``)
that produced it returned.  A request's time to first token runs from
when it was due, not from when it was sent.

The check: a sample of finished requests drawn from the seed, with the
longest among them; the plain reference runs over each prompt and its
served tokens, and the widest gap by which a served token's reference
logit lies below the reference's best at that position is compared.
"""

from __future__ import annotations

import time

import numpy as np

import harness
import loadgen
import modelcfg
import weights

WARM_RID = 1 << 30


def _mesh(devs):
    from repro.runtime import substrate
    return substrate.make_mesh((len(devs), 1), ("data", "model"),
                               devices=devs)


class Call:
    """One ``step()`` or ``submit()``: its host interval, whether it ran
    inside a traced ``bench.step`` span, the decode tokens it produced and
    the cache positions they attended, the prefill chunks it ran, and the
    prompt lengths whose first token it produced."""

    def __init__(self, t0, t1, traced):
        self.t0, self.t1, self.traced = t0, t1, traced
        self.decode = self.kv = self.chunks = 0
        self.prompts = []


class Book:
    """Per-request timestamps and per-call counts, kept from the public
    surface of the scheduler (its requests and its slots)."""

    def __init__(self, sched):
        self.sched = sched
        self.reqs = {}              # rid -> Request
        self.due = {}               # rid -> due time (perf_counter)
        self.times = {}             # rid -> [token times]
        self.live = []              # rids not yet finished
        self.calls = []             # Call per step() / submit()

    def _prefilling(self):
        return {r.rid for r in self.sched.slots
                if r is not None and not r.generated}

    def _placed(self):
        return {r.rid for r in self.sched.slots if r is not None}

    def call(self, fn, *a, traced=False):
        """Run ``fn`` (a step or a submit) and book what it produced: a
        step advances every prefilling slot by one chunk, and a request
        placed into a slot runs its first chunk there."""
        pre = self._prefilling() if fn == self.sched.step else set()
        placed = self._placed()
        t0 = time.perf_counter()
        fn(*a)
        t1 = time.perf_counter()
        call = Call(t0, t1, traced)
        after = self._placed()
        for rid in self.live:
            req, ts = self.reqs[rid], self.times[rid]
            if rid not in placed and (req.generated or rid in after):
                call.chunks += 1                # placed during this call
            new = len(req.generated) - len(ts)
            if new:
                if not ts:
                    call.prompts.append(len(req.prompt))
                ts.extend([t1] * new)
                if len(ts) > 1:                 # a decode token, not the
                    call.decode += 1            # prompt's first
                    call.kv += len(req.prompt) + len(ts) - 1
        call.chunks += len(pre)
        self.live = [r for r in self.live if not self.reqs[r].done]
        self.calls.append(call)

    def submit(self, req, due):
        self.reqs[req.rid], self.due[req.rid] = req, due
        self.times[req.rid] = []
        self.live.append(req.rid)
        self.call(self.sched.submit, req)


def measure(c, rec, devs, seed, seconds, tracer, compiles, t_start, log):
    import jax
    from repro import comm as comm_mod
    from repro.models import build_model
    from repro.serve import BatchScheduler, Request, ServeCfg

    mix = c["mix"]
    model = build_model(modelcfg.transformer_cfg(c["config"]))
    abstract = model.abstract_params()
    params = jax.jit(lambda k: weights.make(k, abstract, c["config"]))(
        weights.key_for(seed))
    session = comm_mod.Session(mesh=_mesh(devs))
    scfg = ServeCfg(max_len=mix["max_len"], batch=mix["slots"],
                    cache_dtype=model.cfg.param_dtype)
    sched = BatchScheduler(model, params, scfg, comm=session.world)
    book = Book(sched)
    pt = sched.pool.page_tokens
    for i in range(mix["slots"]):                # every slot, two chunks
        book.submit(Request(rid=WARM_RID + i, prompt=[1] * (pt + 1),
                            max_new=2), 0.0)
    while sched.pending():
        book.call(sched.step)
    book = Book(sched)

    vocab = model.cfg.vocab_size
    arr = loadgen.arrivals(seed, mix, vocab, mix["warm_s"], seconds)
    dues = [a.due for a in arr]
    t_origin = time.perf_counter()
    w0 = t_origin + mix["warm_s"]
    w1 = w0 + seconds
    i, c0, c1, draining = 0, None, None, False
    in_window, late = [], []          # late: how late each was sent
    while True:
        now = time.perf_counter()
        if c0 is None and now >= w0:
            c0 = compiles.n
        if not draining:
            tracer.poll(now, w1)
        if not draining and now >= w1:
            draining, c1 = True, compiles.n
            tracer.stop()
            in_window = [a.rid for a in arr
                         if w0 <= t_origin + a.due < w1]
        if draining and (all(book.times[r] for r in in_window)
                         or now >= w1 + mix["grace_s"]):
            break
        while not draining and i < len(arr) and t_origin + dues[i] <= now:
            a = arr[i]
            late.append(now - (t_origin + a.due))
            with tracer.span("bench.submit"):
                book.submit(Request(rid=a.rid, prompt=a.prompt,
                                    max_new=a.max_new), t_origin + a.due)
            i += 1
        if sched.pending():
            with tracer.span("bench.step"):
                book.call(sched.step, traced=tracer.active)
        else:
            nxt = t_origin + dues[i] if i < len(arr) else w1
            with tracer.span("bench.wait"):
                time.sleep(max(0.0, min(nxt, w1) - time.perf_counter()))
    rec.setup_s = w0 - t_start
    rec.window = (w0, w1)
    rec.window_compiles = c1 - c0
    rec.book = book
    rec.in_window = in_window
    rec.attempted = len(in_window)
    rec.failed = sum(1 for r in in_window if not book.times[r])
    finished = sorted(r for r, q in book.reqs.items() if q.done)
    log(f"window: {len(in_window)} requests due, {rec.failed} without a "
        f"first token; {len(finished)} finished in all; set-up "
        f"{rec.setup_s:.3f} s")
    itl = sorted(b - a for ts in book.times.values()
                 for a, b in zip(ts, ts[1:]) if w0 <= b < w1)
    if itl:
        n = len(itl)
        log("window gaps between tokens: " + ", ".join(
            f"p{q} {1e3 * itl[min(n - 1, q * n // 100)]:.2f}"
            for q in (10, 50, 80, 90, 95, 99)) + f" ms of {n}")
    late.sort()
    log(f"generator lateness: median {1e3 * late[len(late) // 2]:.2f} ms,"
        f" max {1e3 * late[-1]:.2f} ms over {len(late)} requests")
    rng = np.random.default_rng(loadgen._ss(seed, 3))
    longest = max(finished, key=lambda r: len(book.reqs[r].prompt)
                  + len(book.reqs[r].generated))
    rest = [r for r in finished if r != longest]
    k = min(mix["check_requests"] - 1, len(rest))
    pick = [longest] + [rest[j] for j in rng.choice(len(rest), k,
                                                    replace=False)]
    sample = [(list(book.reqs[r].prompt), list(book.reqs[r].generated))
              for r in pick]
    return {"sched": sched, "params": params, "session": session,
            "book": book, "sample": sample, "abstract": abstract,
            "max_len": mix["max_len"]}


def release(out) -> None:
    """Drop every reference to the program's state (the book keeps its
    timestamps but not the scheduler), so its device memory is free."""
    import gc
    import jax
    for k in ("sched", "params", "session"):
        out.pop(k, None)
    out["book"].sched = None
    gc.collect()
    jax.clear_caches()


def gaps(c, out, seed, modes=("f32",)):
    """Per mode, the widest gap over the sample: for "f32", of the served
    tokens; for any other mode, of the token that mode's logits put
    first, both measured under the float32 reference."""
    import jax
    import jax.numpy as jnp
    ref = harness.reference(c["config"])
    cfg, abstract, L = c["config"], out["abstract"], out["max_len"]
    params = jax.jit(lambda k: weights.make(k, abstract, cfg))(
        weights.key_for(seed))
    fwd = {m: jax.jit(lambda p, t, m=m: ref.logits(p, cfg, t, m))
           for m in set(modes) | {"f32"}}
    widest = {m: 0.0 for m in modes}
    for prompt, served in out["sample"]:
        seq = prompt + served[:-1]
        toks = jnp.asarray(seq + [0] * (L - len(seq)), jnp.int32)
        pos = np.arange(len(prompt) - 1, len(seq))
        picks = {"f32": jnp.asarray(served, jnp.int32)}
        for m in modes:                # one (L, vocab) logits at a time
            if m != "f32":
                picks[m] = jnp.argmax(fwd[m](params, toks)[pos], -1)
        ref_lg = fwd["f32"](params, toks)[pos]
        best = jnp.max(ref_lg, -1)
        for m in modes:
            got = jnp.take_along_axis(ref_lg, picks[m][:, None], -1)[:, 0]
            widest[m] = max(widest[m], float(jnp.max(best - got)))
        del ref_lg
    return widest


class Unattributed(RuntimeError):
    """The trace cannot say which program is decode and which is chunk."""


def traced_programs(rec):
    """{"decode": [...], "chunk": [...]}: the device durations (ns) of the
    paged decode program and of the prefill-chunk program in the traced
    window; None without a trace.

    Both are jitted functions named ``step`` in the program's page pool,
    and they are the two programs that hold most of the device's time.  A
    ``step()`` runs the decode program once where a slot decodes and the
    chunk program once per prefill chunk, as the host booked; the i-th
    traced ``step()`` is the trace's i-th ``bench.step`` span.  The decode
    program is the one of the two whose runs in each span fit that
    booking the better way round.  Where the spans do not pair with the
    booked steps, or both ways round fit alike, it raises
    ``Unattributed``: a metric read from a guess would be wrong unseen."""
    if rec.trace is None:
        return None
    calls = [k for k in rec.book.calls if k.traced]
    spans = [(s, e) for s, e, n in rec.trace["spans"] if n == "bench.step"]
    progs = rec.trace["programs"]
    top = sorted(progs, key=lambda n: -sum(progs[n]))[:2]
    if len(top) < 2 or not calls or len(calls) != len(spans):
        raise Unattributed(
            f"{len(calls)} traced step() calls against {len(spans)} "
            f"bench.step spans and {len(top)} programs")

    def runs(name, s, e):
        return sum(1 for a, _, n in rec.trace["runs"]
                   if n == name and s <= a < e)
    miss = [0, 0]                  # [top[0] is decode, top[1] is decode]
    for k, (s, e) in zip(calls, spans):
        a, b = runs(top[0], s, e), runs(top[1], s, e)
        want_decode = 1 if k.decode else 0
        miss[0] += abs(a - want_decode) + abs(b - k.chunks)
        miss[1] += abs(b - want_decode) + abs(a - k.chunks)
    if miss[0] == miss[1]:
        raise Unattributed(f"{top[0]} and {top[1]} fit the booked steps "
                           f"alike ({miss[0]} runs off either way round)")
    decode, chunk = top if miss[0] < miss[1] else top[::-1]
    return {"decode": progs[decode], "chunk": progs[chunk],
            "names": (decode, chunk), "miss": sorted(miss)}


def check(c, rec, out, seed, devs, log):
    progs = traced_programs(rec)
    if progs:
        log(f"trace attribution: decode {progs['names'][0]}, chunk "
            f"{progs['names'][1]}; runs off the booked steps {progs['miss']}"
            f" (this way round, the other)")
    lim = c["limits"]["limits"]
    g = gaps(c, out, seed)
    return [("served_logit_gap", g["f32"], lim["served_logit_gap"])]
