"""Every cell, configuration, mix, limit and metric that BENCHMARK.json
names is there and loads; the generators repeat exactly for a seed."""

import os
import re

import numpy as np

import chipbench_tiny as tiny
import harness
import loadgen

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


def test_every_named_file_loads():
    b = tiny.bench()
    assert b["command"][1] == "benchmarks/chip/run.py"
    for w in b["workloads"]:
        c = harness.resolve(b, w["name"])
        assert c["mix"]["kind"] in ("train", "serve")
        harness.driver(c["mix"])
        harness.reference(c["config"])
        assert c["limits"]["limits"]
    for m in b["end_to_end"] + b["per_layer"]:
        assert NAME.match(m["name"])
        mod = harness.load_module(os.path.join(harness.HERE, "metrics",
                                               m["name"] + ".py"))
        assert callable(mod.read)
    for group in (b["workloads"], b["configs"]):
        for e in group:
            assert NAME.match(e["name"])


def test_configs_build_the_program_they_describe():
    import modelcfg
    for c in tiny.bench()["configs"]:
        conf = harness.read_json(os.path.join(tiny.ROOT, c["file"]))
        modelcfg.transformer_cfg(conf)          # raises on any mismatch
        for key in c["reduced"]:
            assert key in conf["reduced"]


def test_every_family_module_has_what_the_yardstick_calls():
    for c in tiny.bench()["configs"]:
        conf = harness.read_json(os.path.join(tiny.ROOT, c["file"]))
        fam = harness.family(conf)
        for fn in ("width", "depth", "tied", "layer_params",
                   "mixer_flops_per_token", "cache_bytes_per_token",
                   "program_pairs"):
            assert callable(getattr(fam, fn)), (conf["family"], fn)
        assert fam.WEIGHTS and fam.TINY["program"]["reduced"]


def test_every_cell_reports_its_metrics():
    b = tiny.bench()
    for w in b["workloads"]:
        e2e = harness.metrics_for(b, w["name"], False)
        assert "setup_s" in [m["name"] for m in e2e] and len(e2e) >= 2
        assert harness.metrics_for(b, w["name"], True)


def test_train_batches_repeat_for_a_seed():
    mix = {"global_batch": 4, "seq_len": 64}
    a = loadgen.train_batch(2 ** 40 + 3, 7, mix, 1000)
    b = loadgen.train_batch(2 ** 40 + 3, 7, mix, 1000)
    c = loadgen.train_batch(2 ** 40 + 4, 7, mix, 1000)
    assert np.array_equal(a["tokens"], b["tokens"])
    assert not np.array_equal(a["tokens"], c["tokens"])
    assert np.array_equal(a["tokens"][:, 1:], a["labels"][:, :-1])
    rows = loadgen.train_rows(5, 0, 4, 64, 1000)
    assert len({r.tobytes() for r in rows}) == 4


def test_arrivals_repeat_and_share_their_sizes():
    mix = harness.read_json(os.path.join(harness.HERE, "traffic",
                                         "chat_stream.json"))
    a = loadgen.arrivals(2 ** 33 + 1, mix, 1000, 20.0, 30.0)
    b = loadgen.arrivals(2 ** 33 + 1, mix, 1000, 20.0, 30.0)
    c = loadgen.arrivals(9, mix, 1000, 20.0, 30.0)
    assert [(x.due, x.prompt, x.max_new) for x in a] == \
        [(x.due, x.prompt, x.max_new) for x in b]
    assert [x.prompt for x in a] != [x.prompt for x in c]
    # another seed: the same schedule, other token ids
    win = [[x for x in r if 20.0 <= x.due < 50.0] for r in (a, c)]
    assert len(win[0]) == len(win[1]) == round(mix["rate_per_s"] * 30)
    assert [(x.due, len(x.prompt), x.max_new) for x in a] == \
        [(x.due, len(x.prompt), x.max_new) for x in c]
    # another order seed: the same sizes in the window, in another order
    d = loadgen.arrivals(9, dict(mix, order_seed=1), 1000, 20.0, 30.0)
    wd = [x for x in d if 20.0 <= x.due < 50.0]
    assert [len(x.prompt) for x in wd] != [len(x.prompt) for x in win[0]]
    assert sorted(len(x.prompt) for x in wd) == sorted(
        len(x.prompt) for x in win[0])
    assert sorted(x.max_new for x in wd) == sorted(x.max_new
                                                   for x in win[0])
    assert all(mix["prompt"]["min"] <= len(x.prompt) <= mix["prompt"]["max"]
               for x in a)
