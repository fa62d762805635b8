"""Launch-layer units: HLO analyzer (trip counts, flops, collectives),
sharding fitters, analytic memory/FLOPs models, mesh construction."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.launch import hloanalysis as H
from repro.parallel.sharding import filter_spec, stack_specs


def test_analyzer_trip_count_multiplication():
    def f(w, x):
        def body(c, wi):
            return jnp.tanh(c @ wi), None
        c, _ = jax.lax.scan(body, x, w)
        return c.sum()
    compiled = jax.jit(f).lower(
        jax.ShapeDtypeStruct((8, 64, 64), jnp.float32),
        jax.ShapeDtypeStruct((16, 64), jnp.float32)).compile()
    cost = H.analyze_module(compiled.as_text())
    assert cost.trip_counts == [8]
    np.testing.assert_allclose(cost.flops, 8 * 2 * 16 * 64 * 64, rtol=0.01)


def test_analyzer_dot_flops_exact():
    def f(a, b):
        return a @ b
    compiled = jax.jit(f).lower(
        jax.ShapeDtypeStruct((32, 128), jnp.float32),
        jax.ShapeDtypeStruct((128, 16), jnp.float32)).compile()
    cost = H.analyze_module(compiled.as_text())
    assert cost.flops == 2 * 32 * 128 * 16


def test_analyzer_skips_movement_bytes():
    def f(a):
        return jnp.transpose(a).reshape(-1).astype(jnp.bfloat16)
    compiled = jax.jit(f).lower(
        jax.ShapeDtypeStruct((64, 64), jnp.float32)).compile()
    cost = H.analyze_module(compiled.as_text())
    # transpose/reshape/convert are movement: hbm charge stays small
    assert cost.hbm_bytes <= 4 * 64 * 64 * 3


def test_wire_factors():
    assert H._wire_factor("all-reduce", 2) == 1.0       # 2(p-1)/p
    assert H._wire_factor("all-gather", 4) == 0.75
    assert H._wire_factor("collective-permute", 16) == 1.0
    assert H._wire_factor("all-to-all", 1) == 0.0


def test_group_info_iota_and_pod_crossing():
    line = "x = f32[4] all-reduce(%y), replica_groups=[2,256]<=[512]"
    p, crosses = H._group_info(line, 512, pod_size=256)
    assert p == 256 and not crosses          # consecutive: intra-pod
    line2 = ("x = f32[4] all-reduce(%y), "
             "replica_groups=[256,2]<=[2,256]T(1,0)")
    p2, crosses2 = H._group_info(line2, 512, pod_size=256)
    assert p2 == 2 and crosses2              # partner is 256 away: DCN


def test_filter_and_stack_specs():
    s = P(("pod", "data"), None, "model")
    assert filter_spec(s, ("data", "model")) == P(("data",), None, "model")
    assert filter_spec(s, ("data",)) == P(("data",), None, None)
    stacked = stack_specs({"w": P("data", "model")})
    assert stacked["w"] == P(None, "data", "model")


def test_fit_spec_drops_indivisible():
    from conftest import run_subprocess_script
    # fit_spec needs a mesh; run under 8 host devices
    run_subprocess_script("""
from jax.sharding import PartitionSpec as P
from repro.launch.dryrun import fit_spec
from repro.runtime import substrate
mesh = substrate.make_mesh((4, 2), ("data", "model"))
assert fit_spec(P("data", "model"), (8, 6), mesh) == P("data", "model")
assert fit_spec(P("data", "model"), (1, 6), mesh) == P(None, "model")
assert fit_spec(P(("data", "model"),), (7,), mesh) == P(None)
assert fit_spec(P("data"), (), mesh) == P(None)
print("OK")
""", timeout=240)


def test_model_flops_formulas():
    from conftest import run_subprocess_script
    run_subprocess_script("""
from repro.launch.dryrun import model_flops, active_param_count
from repro.configs import get_config
from repro.models import build_model
# dense: active == total
n = build_model(get_config("qwen2-72b")).param_count()
assert active_param_count(get_config("qwen2-72b")) == n
assert model_flops("qwen2-72b", "train_4k") == 6.0 * n * 4096 * 256
# moe: active far below total
cfg = get_config("qwen3-moe-30b-a3b")
total = build_model(cfg).param_count()
active = active_param_count(cfg)
assert active < 0.2 * total, (active, total)
print("OK")
""", timeout=240)


# ---------------------------------------------------------------------------
# Compile cache, device table, launcher entry points
# ---------------------------------------------------------------------------

def test_compile_cache_env_dir_is_left_to_jax(monkeypatch, tmp_path):
    from repro.runtime import compile_cache
    was = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert compile_cache.enable() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == was


def test_compile_cache_defaults_to_checkout(monkeypatch):
    import os
    from repro.runtime import compile_cache
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    was = jax.config.jax_compilation_cache_dir
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    try:
        path = compile_cache.enable()
        assert path == os.path.join(repo, ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == path
    finally:
        jax.config.update("jax_compilation_cache_dir", was)
    with open(os.path.join(repo, ".gitignore")) as f:
        assert ".jax_cache/" in f.read().split()


def test_compile_cache_lands_in_env_dir(tmp_path):
    from conftest import run_subprocess_script
    import os
    code = f"""
import os
os.environ["JAX_COMPILATION_CACHE_DIR"] = {str(tmp_path)!r}
import jax, jax.numpy as jnp
from repro.runtime import compile_cache
compile_cache.enable()
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
jax.jit(lambda x: jnp.sin(x) * 3)(jnp.ones(8)).block_until_ready()
"""
    run_subprocess_script(code, devices=1, timeout=120)
    assert os.listdir(tmp_path)


def test_topology_links_come_from_device_kind():
    from repro.core import topology as T
    from repro.runtime import substrate
    mesh = substrate.make_mesh((1,), ("data",))
    topo = T.topology_from_mesh(mesh)         # the test host's devices
    assert topo.link("data") == T.DEVICES["cpu"].ici
    topo = T.topology_from_mesh(substrate.abstract_mesh((4,), ("data",)))
    assert topo.link("data") == T.DEVICES[T.V5E].ici
    with pytest.raises(KeyError, match="no link constants"):
        T.topology_from_mesh_shape(("data",), (4,), device_kind="TPU v9")


def test_serve_launcher_full_flag_and_cache_dtype():
    from repro.launch import serve as S
    args = S.build_parser().parse_args(["--full", "--prompt-lens", "5,7"])
    assert not args.reduced
    assert S.build_parser().parse_args([]).reduced
    args = S.build_parser().parse_args(
        ["--arch", "mamba2-1.3b", "--requests", "3", "--max-new", "2",
         "--prompt-lens", "5,7", "--max-len", "16", "--batch", "2"])
    assert [len(r.prompt) for r in S.make_requests(args, 256)] == [5, 7, 5]
    run = S.serve(args)
    assert len(run.done) == 3 and not run.shed
    # the cache follows the params' dtype (float32 in the reduced config)
    assert run.scfg.cache_dtype == run.model.cfg.param_dtype


def test_train_launcher_entry_points():
    from repro.launch import train as T
    args = T.parse_args(["--arch", "mamba2-1.3b", "--steps", "2",
                         "--seq-len", "16", "--global-batch", "4",
                         "--sync", "composed", "--optimizer", "adafactor"])
    run = T.train(args, T.setup(args))
    assert len(run.losses) == 2
    assert all(np.isfinite(run.losses))
    assert run.compile_s > 0


def test_chip_smoke_refuses_without_tpu(tmp_path):
    import os
    import shutil
    import subprocess
    import sys
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run([sys.executable, os.path.join(repo,
                                                        "chip_smoke.py")],
                          env=env, capture_output=True, text=True,
                          timeout=120)
    assert proc.returncode != 0
    assert "no TPU found" in proc.stderr
    assert '"ok"' not in proc.stdout
    # alone in a directory, without the repository's src/
    shutil.copy(os.path.join(repo, "chip_smoke.py"), tmp_path)
    proc = subprocess.run([sys.executable, str(tmp_path / "chip_smoke.py")],
                          env=env, capture_output=True, text=True,
                          timeout=120, cwd=tmp_path)
    assert proc.returncode != 0
    assert '"ok"' not in proc.stdout
