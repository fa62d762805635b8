"""The yardstick's counts against hand figures and the program's model."""

import math

import pytest

import chipbench_tiny  # noqa: F401  (puts the benchmark on sys.path)
import counts
import harness


def config(name):
    b = {c["name"]: c for c in chipbench_tiny.bench()["configs"]}[name]
    return harness.read_json(f"{chipbench_tiny.ROOT}/{b['file']}")


def test_qwen2_stage_params_and_kv():
    c = config("qwen2-72b-L4")
    p = counts.params(c)
    # 4 x 877.7M per layer + 2 x 152064 x 8192 embedding and head
    assert math.isclose(p["total"], 6.00e9, rel_tol=2e-3)
    assert p["total"] == 6_002_163_712
    assert counts.cache_bytes_per_token(c) == 16 * 1024


def test_mamba2_params_match_the_program():
    from repro.models import build_model
    import modelcfg
    c = config("mamba2-1.3b")
    model = build_model(modelcfg.transformer_cfg(c))
    assert counts.params(c)["total"] == model.param_count()


def test_qwen2_params_match_the_program():
    from repro.models import build_model
    import modelcfg
    c = config("qwen2-72b-L4")
    assert counts.params(c)["total"] == build_model(
        modelcfg.transformer_cfg(c)).param_count()


def test_flops_and_bytes():
    c = config("mamba2-1.3b")
    f = counts.train_flops_per_token(c, 2048)
    # 6 x (1.24e9 in the layers + 1.03e8 in the head) + 3 x 48 layers x
    # the SSD's 2 x 64 heads x (256 x 128 + 256 x 64 + 2 x 64 x 128) MACs
    assert 9.2e9 < f < 9.35e9
    q = config("qwen2-72b-L4")
    base = counts.forward_flops_per_token(q, 0.0)
    assert base == 2.0 * (counts.params(q)["layers"]
                          + counts.params(q)["head"])
    assert counts.forward_flops_per_token(q, 100.0) - base == \
        4.0 * 4 * 64 * 128 * 100
    w = counts.decode_bytes(q, 0)
    assert w == 2 * (counts.params(q)["total"] - counts.params(q)["embed"])
    assert counts.decode_bytes(q, 10) - w == 10 * 16 * 1024


def test_unknown_device_kind_raises():
    assert counts.peaks("TPU v5 lite")["bf16_flops"] == 197e12
    assert counts.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        counts.peaks("cpu")
