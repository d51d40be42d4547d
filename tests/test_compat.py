"""Regression tests for the jax helper layer (repro.compat).

The repo runs on jax 0.9.0: meshes carry explicit `AxisType.Auto` axis
types, `jax.shard_map` takes `check_vma`, and `cost_analysis()` returns a
dict. These tests pin that surface so an API drift fails loudly here
instead of nine tests deep in the distributed suite.
"""
import jax
import jax.numpy as jnp
import numpy as np

from repro import compat


def test_mesh_modules_import_under_installed_jax():
    """Importing and calling the mesh constructors works on the installed
    jax (they build meshes through `compat.make_mesh`)."""
    from repro.launch import mesh as mesh_mod
    from repro.runtime import elastic

    assert callable(mesh_mod.make_production_mesh)
    assert callable(mesh_mod.make_debug_mesh)
    # elastic degrades to whatever devices exist (1 in the test process)
    m = elastic.make_mesh_for(n_devices=1, model_parallel=4)
    assert tuple(m.axis_names) == ("data", "model")
    assert m.devices.size == 1


def test_compat_make_mesh_single_device():
    m = compat.make_mesh((1,), ("data",))
    assert tuple(m.axis_names) == ("data",)


def test_compat_shard_map_runs():
    from jax.sharding import PartitionSpec as P
    mesh = compat.make_mesh((1,), ("data",))
    fn = compat.shard_map(lambda x: x * 2.0, mesh=mesh, in_specs=P("data"),
                          out_specs=P("data"), check_replication=False)
    y = fn(jnp.arange(4.0))
    np.testing.assert_allclose(np.asarray(y), np.arange(4.0) * 2.0)


def test_compat_cost_analysis_is_flat_dict():
    compiled = jax.jit(lambda x: x + 1.0).lower(jnp.zeros((4,))).compile()
    cost = compat.cost_analysis(compiled)
    assert isinstance(cost, dict)
    # flat scalar entries
    assert all(np.isscalar(v) for v in cost.values())
