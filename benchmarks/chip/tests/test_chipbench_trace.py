"""The reduction from trace intervals to the per-layer numbers, on a
small synthetic trace of two devices."""

import pytest

import chipbench_tiny  # noqa: F401
import trace_reduce as tr


def synthetic():
    # device 0: compute 0-40, an async collective 30-70 (start 30-32,
    # done 60-70), compute 80-90; device 1: compute 10-20 and a
    # synchronous all-reduce 20-50.  Programs: two decode runs and one
    # chunk run.  Host: a step span over 0-60 and a feed span over 60-100.
    d0 = {"ops": [(0, 40, "fusion.1"), (30, 32, "collective-permute-start.3"),
                  (60, 70, "collective-permute-done.4"),
                  (80, 90, "fusion.2")],
          "modules": [(0, 40, "jit_step(1)"), (60, 90, "jit_step(1)"),
                      (42, 50, "jit_step(2)")]}
    d1 = {"ops": [(10, 20, "convolution.5"), (20, 50, "all-reduce.7"),
                  (20, 50, "while.2")],
          "modules": []}
    host = [(0, 100, "bench.window"), (0, 60, "bench.step"),
            (60, 100, "bench.feed")]
    return {"devices": {"/device:TPU:0": d0, "/device:TPU:1": d1},
            "host": host}


def test_interval_arithmetic():
    assert tr.union([(5, 9), (0, 3), (2, 4), (9, 9)]) == [(0, 4), (5, 9)]
    assert tr.subtract([(0, 10), (20, 30)], [(2, 4), (8, 22)]) == \
        [(0, 2), (4, 8), (22, 30)]
    assert tr.clip([(0, 10), (15, 20)], (5, 16)) == [(5, 10), (15, 16)]


def test_op_names_drop_the_instruction_text():
    assert tr.op_name("%fusion.3 = bf16[2]{0} fusion(%all-reduce.1)") == \
        "fusion.3"
    assert tr.op_name("all-reduce.7") == "all-reduce.7"


def test_collectives_pair_start_and_done():
    ops = synthetic()["devices"]["/device:TPU:0"]["ops"]
    assert tr.collective_intervals(ops) == [(30, 70)]


def test_reduce():
    r = tr.reduce(synthetic(), (0, 100))
    assert r["window_ns"] == 100 and r["devices"] == 2
    # busy: device 0 0-40, 60-70, 80-90 = 60; device 1 10-50 = 40
    assert r["busy_ns"] == 50
    assert r["idle_share_worst"] == 0.6
    # collectives: device 0 30-70 = 40, device 1 20-50 = 30
    assert r["collective_ns"] == 35
    # exposed: device 0 40-70 (compute ends at 40) = 30; device 1 20-50
    # (only the loop that holds it) = 30
    assert r["exposed_ns"] == 30
    assert r["programs"] == {"jit_step(1)": [40, 30], "jit_step(2)": [8]}
    assert r["runs"] == [(0, 40, "jit_step(1)"), (42, 50, "jit_step(2)"),
                         (60, 90, "jit_step(1)")]
    assert r["spans"] == [(0, 60, "bench.step"), (60, 100, "bench.feed")]
    assert r["top_ops"][0] == ["fusion", 50e-9]
    # device 0's idle gaps: 40-60 under the step span, 90-100 and 70-80
    # under the feed span
    assert r["idle_gaps"] == [["bench.step", 20e-9], ["bench.feed", 10e-9],
                              ["bench.feed", 10e-9]]


def test_reduce_clips_to_the_window():
    r = tr.reduce(synthetic(), (35, 65))
    assert r["window_ns"] == 30
    assert r["busy_ns"] == (10 + 15) / 2       # dev0 35-40, 60-65; dev1 35-50
    assert r["programs"] == {"jit_step(1)": [30], "jit_step(2)": [8]}


def serve_rec(booked):
    """A traced serving window: three step() spans, with the decode
    program (jit_step(7)) and the chunk program (jit_step(9)) run as a
    step with one decode, one with a decode and two chunks, and one with
    one chunk would run them; ``booked`` is (decode, chunks) per step."""
    import types
    from drive_serve import Call
    runs = [(0, 10, "jit_step(7)"),
            (20, 25, "jit_step(9)"), (26, 31, "jit_step(9)"),
            (32, 42, "jit_step(7)"), (50, 55, "jit_step(9)"),
            (56, 57, "jit_add(1)")]
    host = [(0, 100, "bench.window"), (0, 12, "bench.step"),
            (18, 44, "bench.step"), (45, 47, "bench.submit"),
            (48, 60, "bench.step")]
    trace = {"devices": {"/device:TPU:0": {
        "ops": [(s, e, "fusion.1") for s, e, _ in runs], "modules": runs}},
        "host": host}
    calls = [Call(-5, -1, False)]              # before the trace: not paired
    for decode, chunks in booked:
        k = Call(0, 1, True)
        k.decode, k.chunks = decode, chunks
        calls.append(k)
    return types.SimpleNamespace(trace=tr.reduce(trace, (0, 100)),
                                 book=types.SimpleNamespace(calls=calls))


def test_traced_programs_tell_decode_from_chunk():
    import drive_serve
    got = drive_serve.traced_programs(serve_rec([(32, 0), (30, 2), (0, 1)]))
    assert got["decode"] == [10, 10] and got["chunk"] == [5, 5, 5]
    assert got["names"] == ("jit_step(7)", "jit_step(9)")
    assert got["miss"] == [0, 6]
    # a booking that fits both ways round alike is an error, not a guess
    with pytest.raises(drive_serve.Unattributed):
        drive_serve.traced_programs(serve_rec([(1, 1), (1, 1), (1, 1)]))
    # and so is a trace whose step spans do not pair with the booked steps
    with pytest.raises(drive_serve.Unattributed):
        drive_serve.traced_programs(serve_rec([(32, 0), (30, 2)]))
