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
import dataclasses
import os
import re
import sys

import pytest
import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))
import chip_smoke  # noqa: E402
from repro.configs import get_config  # noqa: E402

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


_INSTR = re.compile(r"^\s*(?:ROOT )?%(\S+) = \w+\[([\d,]*)\]\S* ([\w-]+)\(")
_COMPUTATION = re.compile(r"^(ENTRY )?%(\S+) .*\{$")
_CALLS = re.compile(r"calls=%([\w.-]+)")


def _cache_sized_buffers(hlo: str, shapes):
    """(computation, instruction, opcode) of each instruction of the
    compiled text, fusion bodies left out (their values are not buffers),
    whose result has one of `shapes`; parameters, tuple elements and
    bitcasts are views and are left out too."""
    fused = set()
    for line in hlo.splitlines():
        if " fusion(" in line:
            fused.update(_CALLS.findall(line))
    out, comp = [], None
    for line in hlo.splitlines():
        c = _COMPUTATION.match(line)
        if c:
            comp = ("ENTRY" if c.group(1) else "") + c.group(2)
            continue
        m = _INSTR.match(line)
        if not m or comp in fused or m.group(3) in (
                "parameter", "get-tuple-element", "bitcast"):
            continue
        dims = tuple(int(d) for d in m.group(2).split(",") if d)
        if dims in shapes:
            out.append((comp, m.group(1), m.group(3)))
    return out


@pytest.mark.parametrize("decode", ["precise", "taf"])
def test_serve_step_writes_rows_into_the_donated_cache(
        decode, served, one_chip, no_compile_cache):
    """The serve step as `ServingEngine` jits it (cache donated) aliases
    the whole cache, and no layer's (B, H, S, D) cache slice nor the
    stacked cache is copied: the only cache-sized results are the final
    in-place writes of the new rows."""
    from repro.core.types import ApproxSpec
    from repro.launch import steps
    from repro.models import build
    model, params, cache = served
    if decode == "precise":
        model = build(dataclasses.replace(model.cfg,
                                          approx_decode=ApproxSpec()))
        cache = {k: v for k, v in cache.items() if k != "taf"}
    tokens = jax.ShapeDtypeStruct((chip_smoke.N_REQUESTS,), jnp.int32,
                                  sharding=one_chip)
    pos = jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip)
    compiled = jax.jit(steps.make_serve_step(model), donate_argnums=(1,)
                       ).lower(params, cache, tokens, pos).compile()
    kv = cache["dense"]
    kv_bytes = sum(a.size * a.dtype.itemsize for a in kv.values())
    assert compiled.memory_analysis().alias_size_in_bytes >= kv_bytes
    stacked = {a.shape for a in kv.values()}
    found = _cache_sized_buffers(
        compiled.as_text(), stacked | {s[1:] for s in stacked})
    writes = [f for f in found if f[0].startswith("ENTRY")
              and f[2] == "dynamic-update-slice"]
    assert len(writes) <= len(kv)
    assert [f for f in found if f not in writes] == []


@pytest.fixture(scope="module")
def moonlight(one_chip):
    """Moonlight-16B-A3B as the benchmark serves it (8 of 64 experts held,
    bf16), with its decode cache at the cell's 128 slots x 768 positions."""
    from repro.models import build
    cfg = get_config("moonlight-16b-a3b")
    cfg = dataclasses.replace(cfg, remat=False, moe=dataclasses.replace(
        cfg.moe, n_held=8))
    model = build(cfg)
    params = _shapes(jax.eval_shape(model.init, jax.random.PRNGKey(0)),
                     one_chip)
    cache = _shapes(jax.eval_shape(lambda: model.init_cache(128, 768)),
                    one_chip)
    return model, params, cache


def test_moonlight_decode_reads_the_latent_cache_in_place(
        moonlight, one_chip, no_compile_cache):
    """The donated serve step aliases the latent cache and holds no copy of
    a whole layer's `ckv`, nor of a stacked one; the step fits one chip."""
    from repro.launch import steps
    model, params, cache = moonlight
    tokens = jax.ShapeDtypeStruct((128,), jnp.int32, sharding=one_chip)
    pos = jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip)
    compiled = jax.jit(steps.make_serve_step(model), donate_argnums=(1,)
                       ).lower(params, cache, tokens, pos).compile()
    _fits_one_chip(compiled)
    latent = [cache[k][name] for k in ("dense", "moe")
              for name in ("ckv", "k_rope")]
    assert compiled.memory_analysis().alias_size_in_bytes >= sum(
        a.size * a.dtype.itemsize for a in latent)
    ckv = {cache[k]["ckv"].shape for k in ("dense", "moe")}
    found = _cache_sized_buffers(compiled.as_text(),
                                 ckv | {s[1:] for s in ckv})
    writes = [f for f in found if f[0].startswith("ENTRY")
              and f[2] == "dynamic-update-slice"]
    assert len(writes) <= 2
    assert [f for f in found if f not in writes] == []


def test_moonlight_first_wave_prefill_fits_one_chip(
        moonlight, one_chip, no_compile_cache):
    """The engine's first admission prefills all 128 prompts of 256 tokens
    at once (the grouped expert product)."""
    from repro.launch import steps
    model, params, _ = moonlight
    prompts = jax.ShapeDtypeStruct((128, 256), jnp.int32, sharding=one_chip)
    compiled = jax.jit(steps.make_prefill_step(model, 768)).lower(
        params, {"tokens": prompts}).compile()
    _fits_one_chip(compiled)
    assert "ragged" in compiled.as_text()
