"""Tests for the section-Perf optimization features (EXPERIMENTS.md):
int8 KV cache, causally-exact herded KV perforation, grouped-GQA decode,
shard_hint no-mesh fallback, expert perforation."""
import dataclasses

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.configs import get_smoke_config
from repro.core.types import parse_pragma
from repro.models import build, common

KEY = jax.random.PRNGKey(0)


def test_int8_kv_cache_decode_close_to_exact():
    base = dataclasses.replace(get_smoke_config("qwen3-1.7b"), remat=False,
                               compute_dtype="float32")
    cfg8 = dataclasses.replace(base, kv_cache_dtype="int8")
    m0, m8 = build(base), build(cfg8)
    params = m0.init(KEY)
    rng = np.random.RandomState(0)
    tokens = jnp.asarray(rng.randint(0, base.vocab_size, (2, 12)), jnp.int32)
    batch = {"tokens": tokens[:, :8], "max_len": 12}
    _, c0 = m0.prefill(params, batch)
    _, c8 = m8.prefill(params, batch)
    assert c8["dense"]["k"].dtype == jnp.int8 and "k_scale" in c8["dense"]
    for t in range(3):
        l0, c0 = m0.decode_step(params, c0, tokens[:, 8 + t], jnp.int32(8 + t))
        l8, c8 = m8.decode_step(params, c8, tokens[:, 8 + t], jnp.int32(8 + t))
        scale = float(jnp.abs(l0).max()) + 1e-6
        assert float(jnp.abs(l0 - l8).max()) / scale < 0.05


def test_int8_cache_is_half_the_bytes():
    cfg8 = dataclasses.replace(get_smoke_config("qwen3-1.7b"),
                               kv_cache_dtype="int8")
    m8 = build(cfg8)
    c8 = jax.eval_shape(lambda: m8.init_cache(4, 64))
    cbf = jax.eval_shape(lambda: build(get_smoke_config("qwen3-1.7b"))
                         .init_cache(4, 64))
    bytes8 = sum(np.prod(l.shape) * l.dtype.itemsize
                 for l in jax.tree.leaves(c8) if l.dtype in
                 (jnp.int8, jnp.bfloat16))
    bytesbf = sum(np.prod(l.shape) * l.dtype.itemsize
                  for l in jax.tree.leaves(cbf))
    assert bytes8 < 0.7 * bytesbf


def test_herded_kv_perforation_is_causally_exact():
    """Kept-position masking == full attention with dropped blocks masked."""
    rng = np.random.RandomState(1)
    q = jnp.asarray(rng.randn(1, 2, 32, 16), jnp.float32)
    k = jnp.asarray(rng.randn(1, 2, 64, 16), jnp.float32)
    v = jnp.asarray(rng.randn(1, 2, 64, 16), jnp.float32)
    kv_pos = np.concatenate([np.arange(0, 32), np.arange(48, 64)])
    kk = jnp.take(k, jnp.asarray(kv_pos), 2)
    vv = jnp.take(v, jnp.asarray(kv_pos), 2)
    out = common.chunked_attention(q, kk, vv, causal=True, q_chunk=8,
                                   kv_chunk=8, kv_positions=kv_pos)
    logits = jnp.einsum("bhqd,bhkd->bhqk", q, k) / 4.0
    qi = jnp.arange(32)[:, None] + 32
    ki = jnp.arange(64)[None, :]
    mask = (ki <= qi) & ((ki < 32) | (ki >= 48))
    probs = jax.nn.softmax(jnp.where(mask[None, None], logits, -1e30), -1)
    ref = jnp.einsum("bhqk,bhkd->bhqd", probs, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


def test_perforated_training_runs_and_shrinks_compute():
    cfg = dataclasses.replace(
        get_smoke_config("deepseek-7b"), remat=False,
        approx_attention=parse_pragma("perfo(ini:0.5)"),
        approx_ffn=parse_pragma("perfo(small:4)"))
    model = build(cfg)
    params = model.init(KEY)
    rng = np.random.RandomState(2)
    batch = {"tokens": jnp.asarray(rng.randint(0, cfg.vocab_size, (2, 256))),
             "labels": jnp.asarray(rng.randint(0, cfg.vocab_size, (2, 256)))}
    loss, _ = model.loss(params, batch)
    assert bool(jnp.isfinite(loss))
    g = jax.grad(lambda p: model.loss(p, batch)[0])(params)
    assert all(np.isfinite(np.asarray(x, np.float32)).all()
               for x in jax.tree.leaves(g))


def test_expert_perforation_uses_fewer_experts():
    cfg = dataclasses.replace(
        get_smoke_config("olmoe-1b-7b"), remat=False,
        approx_ffn=parse_pragma("perfo(small:2)"))
    model = build(cfg)
    params = model.init(KEY)
    rng = np.random.RandomState(3)
    batch = {"tokens": jnp.asarray(rng.randint(0, cfg.vocab_size, (2, 64))),
             "labels": jnp.asarray(rng.randint(0, cfg.vocab_size, (2, 64)))}
    loss, _ = model.loss(params, batch)
    assert bool(jnp.isfinite(loss))


def test_shard_hint_identity_without_mesh():
    x = jnp.ones((8, 4))
    y = common.shard_hint(x, "data", "model")
    np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
    # inside jit without mesh context: still fine
    z = jax.jit(lambda a: common.shard_hint(a * 2, ("pod", "data"), None))(x)
    np.testing.assert_allclose(np.asarray(z), 2.0)


def test_grouped_gqa_decode_matches_repeat_form():
    rng = np.random.RandomState(4)
    q = jnp.asarray(rng.randn(2, 8, 1, 16), jnp.float32)
    k = jnp.asarray(rng.randn(2, 2, 32, 16), jnp.float32)
    v = jnp.asarray(rng.randn(2, 2, 32, 16), jnp.float32)
    out = common.decode_attention(q, k, v, valid_len=20)
    kr = jnp.repeat(k, 4, axis=1)
    vr = jnp.repeat(v, 4, axis=1)
    logits = jnp.einsum("bhqd,bhkd->bhqk", q, kr) / 4.0
    mask = jnp.arange(32)[None, None, None, :] < 20
    probs = jax.nn.softmax(jnp.where(mask, logits, -1e30), -1)
    ref = jnp.einsum("bhqk,bhkd->bhqd", probs, vr)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


@pytest.mark.parametrize("perforated", [False, True],
                         ids=["dense", "perforated"])
def test_decode_attention_new_row_matches_written_cache(perforated):
    """The token's own row given beside the cache (`k_new`/`v_new`, the
    cache read only) attends like the same row written into the cache at
    `valid_len`, perforated blocks masked alike."""
    rng = np.random.RandomState(5)
    q = jnp.asarray(rng.randn(2, 8, 1, 16), jnp.float32)
    k = jnp.asarray(rng.randn(2, 2, 300, 16), jnp.float32)
    v = jnp.asarray(rng.randn(2, 2, 300, 16), jnp.float32)
    k_row = jnp.asarray(rng.randn(2, 2, 1, 16), jnp.float32)
    v_row = jnp.asarray(rng.randn(2, 2, 1, 16), jnp.float32)
    keep = None
    if perforated:      # the middle 128-block dropped, the new row in it
        keep = jnp.asarray(np.r_[np.ones(128), np.zeros(128),
                                 np.ones(44)].astype(bool))
    for pos in (0, 37, 150, 299):
        written = common.decode_attention(
            q, k.at[:, :, pos].set(k_row[:, :, 0]),
            v.at[:, :, pos].set(v_row[:, :, 0]), valid_len=pos + 1,
            keep_mask=keep)
        split = common.decode_attention(q, k, v, valid_len=pos,
                                        keep_mask=keep, k_new=k_row,
                                        v_new=v_row)
        np.testing.assert_allclose(np.asarray(split), np.asarray(written),
                                   rtol=1e-5, atol=1e-6)


def test_grad_accumulation_matches_full_batch():
    """accum_steps=4 == full-batch step (same grads up to fp tolerance)."""
    from repro.launch import steps as steps_mod
    from repro.optim import adamw
    cfg = dataclasses.replace(get_smoke_config("qwen3-1.7b"), remat=False,
                              compute_dtype="float32")
    model = build(cfg)
    params = model.init(KEY)
    opt = adamw.init(params)
    rng = np.random.RandomState(7)
    batch = {"tokens": jnp.asarray(rng.randint(0, cfg.vocab_size, (8, 32))),
             "labels": jnp.asarray(rng.randint(0, cfg.vocab_size, (8, 32)))}
    full = steps_mod.make_train_step(model, adamw.AdamWConfig(lr=1e-3))
    acc = steps_mod.make_train_step_accum(model, adamw.AdamWConfig(lr=1e-3),
                                          accum_steps=4)
    p1, _, m1 = jax.jit(full)(params, opt, batch)
    p2, _, m2 = jax.jit(acc)(params, opt, batch)
    assert abs(float(m1["loss"]) - float(m2["loss"])) < 1e-4
    dmax = max(float(jnp.abs(a - b).max())
               for a, b in zip(jax.tree.leaves(p1), jax.tree.leaves(p2)))
    assert dmax < 1e-4, dmax
