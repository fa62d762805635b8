"""The attribution of the device's idle time to the serving scheduler's
spans (``serve_spans.py``), on small traces worked by hand."""

import chipbench_tiny  # noqa: F401
import serve_spans as ss

# One step, one submit and a wait in a window of 100 ns.  The device is
# busy over 0-1, 14-24, 35-45, 47-50, 62-64 and 80-90.
SPANS = [(0, 100, "bench.window", {}),
         (0, 60, "bench.step", {}),
         (2, 58, "serve.step", {"_r": 1, "step_num": 0}),
         (4, 10, "serve.admit", {}),
         (10, 30, "serve.prefill_chunk", {"rid": 7, "chunk": 1}),
         (11, 14, "serve.pages", {"rid": 7}),
         (24, 30, "serve.sample", {"rid": 7}),
         (30, 45, "serve.decode", {"slots": 3}),
         (31, 35, "serve.pages", {}),
         (45, 56, "serve.sample", {}),
         (60, 70, "bench.submit", {}),
         (61, 69, "serve.admit", {}),
         (70, 100, "bench.wait", {}),
         (150, 160, "serve.step", {"step_num": 1})]     # after the window
GAPS = [(1, 14), (24, 35), (45, 47), (50, 62), (64, 80), (90, 100)]


def test_self_idle_per_span_name():
    sp = ss.split(SPANS, GAPS, (0, 100))
    assert sp["idle_ns"] == 64
    # the step holds 12 + 11 + 2 + 8 ns of idle; its own part is 2-4 and
    # 56-58; the chunk keeps 10-11 after its pages and its sample
    assert sp["step_idle_ns"] == [33]
    assert sp["step_split_ns"] == {
        "bench.step": 3, "serve.step": 4, "serve.admit": 6,
        "serve.prefill_chunk": 1, "serve.pages": 3 + 4,
        "serve.sample": 6 + 8, "serve.decode": 1}
    assert sum(v for k, v in sp["step_split_ns"].items()
               if k.startswith("serve.")) == 33
    # the admission inside submit() is outside the step's split
    assert sp["self_idle_ns"]["serve.admit"] == 6 + 6
    assert sp["self_idle_ns"]["bench.submit"] == 2
    assert sp["self_idle_ns"]["bench.wait"] == 20
    assert sp["decode_slots"] == [3]


def test_a_gap_is_named_by_its_innermost_span():
    sp = ss.split(SPANS, GAPS, (0, 100))
    assert sp["idle_gaps"] == [
        ["bench.wait", 16e-9], ["serve.admit", 13e-9],
        ["serve.step", 12e-9], ["serve.sample", 11e-9],
        ["bench.wait", 10e-9], ["serve.sample", 2e-9]]


def test_readings():
    assert ss.readings(ss.split(SPANS, GAPS, (0, 100))) == {
        "serve.step_idle_ms": 33e-6, "serve.sample_idle_ms": 14e-6,
        "serve.decode_batch": 3.0}
    two = [(0, 10, "serve.step", {}), (2, 8, "serve.decode", {"slots": 2}),
           (10, 30, "serve.step", {}), (12, 28, "serve.decode", {"slots": 4})]
    sp = ss.split(two, [(0, 5), (20, 30)], (0, 30))
    assert sp["step_idle_ns"] == [5, 10]
    assert sp["step_split_ns"] == {"serve.step": 2 + 2,
                                   "serve.decode": 3 + 8}
    assert ss.readings(sp) == {"serve.step_idle_ms": 7.5e-6,
                               "serve.sample_idle_ms": 0.0,
                               "serve.decode_batch": 3.0}
    # a trace of a program without serve.* spans reads nothing
    assert ss.readings(ss.split([(0, 60, "bench.step", {})], GAPS,
                                (0, 100))) == {}


def test_host_spans_keep_the_metadata(tmp_path):
    import jax
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation("bench.step"):
        with jax.profiler.StepTraceAnnotation("serve.step", step_num=4):
            with jax.profiler.TraceAnnotation("serve.decode") as span:
                span.set_metadata(slots=5)
            with jax.profiler.TraceAnnotation("other"):
                pass
    jax.profiler.stop_trace()
    got = sorted(ss.host_spans(str(tmp_path)))
    assert [(n, m) for _, _, n, m in got] == [
        ("bench.step", {}), ("serve.step", {"_r": 1, "step_num": 4}),
        ("serve.decode", {"slots": 5})]
    assert got[0][0] <= got[1][0] <= got[2][0] < got[2][1] <= got[1][1] \
        <= got[0][1]
