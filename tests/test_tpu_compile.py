"""Compiles for a described TPU v5e chip, with no chip attached.

The Pallas kernels at the widths and in the modes `chip_smoke.py` runs, and
the served model's one-token serve step and prefill step at full width,
each compiled by the TPU compiler for one chip of a `v5e:2x2` topology.
This catches what interpret mode cannot: blocks that break Mosaic's tiling
rules, operations Mosaic does not lower, kernels over their VMEM limit, and
steps that do not fit the chip's 16 GB.

The topology is described only inside the `topo` fixture, never while a
module is imported: one process at a time may load the TPU library, so
under several test workers only the worker that runs this file loads it.
"""
import os
import sys

import pytest
import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))
import chip_smoke  # noqa: E402

V5E_HBM_BYTES = 16 * 10 ** 9
KERNEL_NAMES = [c[0] for c in chip_smoke.kernel_cases()]


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_compile_cache():
    """A compile for a described chip cannot be read back without one:
    keep these programs out of the persistent cache."""
    from jax.experimental.compilation_cache import compilation_cache
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    compilation_cache.reset_cache()


def _shapes(tree, sharding):
    return jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding),
        tree)


@pytest.mark.parametrize("name", KERNEL_NAMES)
def test_kernel_compiles_with_mosaic(name, one_chip, no_compile_cache):
    _, _, call, shapes = next(c for c in chip_smoke.kernel_cases()
                              if c[0] == name)
    args = [jax.ShapeDtypeStruct(s, jnp.bfloat16, sharding=one_chip)
            for s in shapes]
    compiled = jax.jit(call).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.fixture(scope="module")
def served(one_chip):
    """qwen3-1.7b with decode-time TAF: the model, its parameter shapes
    and the decode cache shapes at 8 slots x 512 positions."""
    from repro.models import build
    model = build(chip_smoke.served_config())
    params = _shapes(jax.eval_shape(model.init, jax.random.PRNGKey(0)),
                     one_chip)
    cache = _shapes(jax.eval_shape(lambda: model.init_cache(
        chip_smoke.N_REQUESTS, chip_smoke.MAX_LEN)), one_chip)
    return model, params, cache


def _fits_one_chip(compiled):
    m = compiled.memory_analysis()
    used = m.argument_size_in_bytes + m.temp_size_in_bytes
    assert used < V5E_HBM_BYTES, f"{used / 1e9:.2f} GB"


def test_serve_step_fits_one_chip(served, one_chip, no_compile_cache):
    from repro.launch import steps
    model, params, cache = served
    cfg = model.cfg
    assert (cfg.n_layers, cfg.d_model, cfg.d_ff) == (28, 2048, 6144)
    assert cfg.tie_embeddings and cfg.param_dtype == "bfloat16"
    tokens = jax.ShapeDtypeStruct((chip_smoke.N_REQUESTS,), jnp.int32,
                                  sharding=one_chip)
    pos = jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip)
    compiled = jax.jit(steps.make_serve_step(model)).lower(
        params, cache, tokens, pos).compile()
    _fits_one_chip(compiled)


def test_prefill_step_fits_one_chip(served, one_chip, no_compile_cache):
    from repro.launch import steps
    model, params, _ = served
    prompts = jax.ShapeDtypeStruct(
        (chip_smoke.N_REQUESTS, chip_smoke.PROMPT_LEN), jnp.int32,
        sharding=one_chip)
    compiled = jax.jit(steps.make_prefill_step(
        model, chip_smoke.MAX_LEN)).lower(params, {"tokens": prompts}
                                          ).compile()
    _fits_one_chip(compiled)
