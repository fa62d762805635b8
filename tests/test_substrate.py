"""Device substrate: mesh build, context enter/exit, mode queries,
shard_hint behaviour, spec filtering — on the installed JAX version,
single-device and 8-fake-device (subprocess) paths."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from conftest import run_subprocess_script
from repro.parallel.sharding import (active_mesh, auto_axis_names,
                                     filter_spec, shard_hint)
from repro.runtime import substrate


def test_backend_selected_and_described():
    # one backend: JAX's explicit-axis API, meshes all-Auto by default
    assert substrate.AxisType is jax.sharding.AxisType
    mesh = substrate.make_mesh((1,), ("data",))
    assert mesh.axis_types == (jax.sharding.AxisType.Auto,)
    am = substrate.abstract_mesh((2,), ("data",))
    assert am.axis_types == (jax.sharding.AxisType.Auto,)


def test_make_mesh_single_device():
    mesh = substrate.make_mesh((1,), ("data",))
    assert mesh.axis_names == ("data",)
    assert dict(mesh.shape) == {"data": 1}
    assert not substrate.is_abstract(mesh)


def test_make_mesh_too_many_devices_raises():
    with pytest.raises(ValueError):
        substrate.make_mesh((len(jax.devices()) + 1,), ("data",))


def test_set_mesh_context_enter_exit():
    assert active_mesh() is None
    mesh = substrate.make_mesh((1,), ("data",))
    with substrate.set_mesh(mesh):
        m = active_mesh()
        assert m is not None
        assert tuple(m.axis_names) == ("data",)
    assert active_mesh() is None


def test_set_mesh_nested():
    m1 = substrate.make_mesh((1,), ("data",))
    m2 = substrate.make_mesh((1, 1), ("data", "model"))
    with substrate.set_mesh(m1):
        with substrate.set_mesh(m2):
            assert tuple(active_mesh().axis_names) == ("data", "model")
        assert tuple(active_mesh().axis_names) == ("data",)
    assert active_mesh() is None


def test_shard_hint_noop_outside_mesh():
    x = jnp.ones((4, 4))
    assert shard_hint(x, P("data")) is x


def test_shard_hint_applies_inside_mesh():
    mesh = substrate.make_mesh((1,), ("data",))
    x = jnp.ones((4, 4))
    with substrate.set_mesh(mesh):
        y = shard_hint(x, P(("pod", "data"), None))
        assert y.shape == x.shape
        np.testing.assert_array_equal(np.asarray(y), np.asarray(x))


def test_abstract_mesh_and_context():
    am = substrate.abstract_mesh((4, 2), ("data", "model"))
    assert substrate.is_abstract(am)
    assert dict(am.shape) == {"data": 4, "model": 2}
    with substrate.use_abstract_mesh(am):
        m = active_mesh()
        assert m is not None and substrate.is_abstract(m)
        # constraints must silently no-op where unsupported
        x = jnp.ones((8, 4))
        y = shard_hint(x, P("data"))
        assert y.shape == x.shape
    assert active_mesh() is None


def test_auto_axis_names_never_raises():
    mesh = substrate.make_mesh((1,), ("data",))
    assert auto_axis_names(mesh) == ("data",)
    am = substrate.abstract_mesh((2, 2), ("data", "model"))
    assert set(auto_axis_names(am)) <= {"data", "model"}
    assert auto_axis_names(None) == ()


def test_spec_filtering():
    s = P(("pod", "data"), None, "model")
    assert filter_spec(s, ("data", "model")) == P(("data",), None, "model")
    assert filter_spec(s, ()) == P(None, None, None)


def test_shard_map_full_manual_single_device():
    mesh = substrate.make_mesh((1,), ("data",))
    f = substrate.shard_map(
        lambda v: jax.lax.psum(v, "data"),
        mesh=mesh, in_specs=P("data"), out_specs=P("data"), check_vma=False)
    out = jax.jit(f)(jnp.arange(4.0))
    np.testing.assert_allclose(np.asarray(out), np.arange(4.0))


def test_shard_map_partial_manual_single_device():
    mesh = substrate.make_mesh((1, 1), ("data", "model"))
    f = substrate.shard_map(
        lambda v: jax.lax.psum(v.sum(), "data"),
        mesh=mesh, in_specs=P("data"), out_specs=P(),
        axis_names={"data"}, check_vma=False)
    out = jax.jit(f)(jnp.arange(4.0))
    np.testing.assert_allclose(float(out), 6.0)


def test_engine_init_binds_active_mesh():
    from repro.core import CollectiveEngine, compose_library, registry
    eng = CollectiveEngine(
        None, library=compose_library(registry.ALL_FUNCTIONS))
    mesh = substrate.make_mesh((1,), ("data",))
    with substrate.set_mesh(mesh):
        eng.init()
    assert eng.topology.axis_sizes == {"data": 1}


def test_substrate_eight_devices_subprocess():
    run_subprocess_script("""
import jax, numpy as np, jax.numpy as jnp
from functools import partial
from jax.sharding import PartitionSpec as P
from repro.parallel.sharding import active_mesh, named_shardings, shard_hint
from repro.runtime import substrate

# mesh build over 8 fake devices
mesh = substrate.make_mesh((4, 2), ("data", "model"))
assert dict(mesh.shape) == {"data": 4, "model": 2}

# context + shard_hint + device_put round trip
x = jnp.asarray(np.arange(32, dtype=np.float32).reshape(8, 4))
with substrate.set_mesh(mesh):
    assert active_mesh() is not None
    y = jax.jit(lambda v: shard_hint(v, P(("pod", "data"), None)))(x)
    np.testing.assert_array_equal(np.asarray(y), np.asarray(x))
    sh = named_shardings(mesh, {"x": P("data", "model")})
    xs = jax.device_put({"x": x}, sh)
    np.testing.assert_array_equal(np.asarray(xs["x"]), np.asarray(x))
assert active_mesh() is None

# full-manual shard_map: psum == column sums
@partial(substrate.shard_map, mesh=mesh, in_specs=P(("data", "model")),
         out_specs=P(("data", "model")), check_vma=False)
def allsum(v):
    return jax.lax.psum(v, ("data", "model"))
out = jax.jit(allsum)(x)
np.testing.assert_allclose(np.asarray(out),
                           np.broadcast_to(np.asarray(x).sum(0), x.shape),
                           rtol=1e-6)

# partial-manual (data manual, model auto): scan inside the body
@partial(substrate.shard_map, mesh=mesh, in_specs=(P(), P("data")),
         out_specs=P(), axis_names={"data"}, check_vma=False)
def g(w, v):
    def body(c, wi):
        return jnp.tanh(c @ wi), None
    c, _ = jax.lax.scan(body, v, w)
    return jax.lax.psum(c.sum(), "data")
w = jnp.full((2, 4, 4), 0.1)
tot = jax.jit(g)(w, x)
def ref(w, v):
    def body(c, wi):
        return jnp.tanh(c @ wi), None
    c, _ = jax.lax.scan(body, v, w)
    return c.sum()
np.testing.assert_allclose(float(tot), float(ref(w, x)), rtol=1e-5)
print("OK")
""", timeout=300)
