"""Per-architecture smoke tests (reduced configs; brief requirement) +
model math correctness (decode==forward, mamba2 SSD parity, TAF decode)."""
import dataclasses

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.configs import get_smoke_config, list_archs
from repro.core.types import ApproxSpec, Level, TAFParams, Technique
from repro.models import build, mamba2

KEY = jax.random.PRNGKey(0)
B, S = 2, 16


def _batch(cfg, rng, s=S):
    batch = {"tokens": jnp.asarray(rng.randint(0, cfg.vocab_size, (B, s)),
                                   jnp.int32),
             "labels": jnp.asarray(rng.randint(0, cfg.vocab_size, (B, s)),
                                   jnp.int32)}
    if cfg.frontend == "vision_patches":
        batch["patch_embeds"] = jnp.asarray(
            rng.standard_normal((B, cfg.n_patch_tokens, cfg.d_model)) * 0.02,
            jnp.float32)
    if cfg.frontend == "audio_frames":
        batch["frames"] = jnp.asarray(
            rng.standard_normal((B, cfg.max_source_positions, cfg.d_model))
            * 0.02, jnp.float32)
    return batch


@pytest.mark.parametrize("arch", list_archs())
class TestArchSmoke:
    def test_forward_and_train_step(self, arch):
        """Brief: instantiate reduced config, one forward/train step on CPU,
        assert output shapes + no NaNs."""
        cfg = get_smoke_config(arch)
        model = build(cfg)
        params = model.init(KEY)
        rng = np.random.RandomState(0)
        batch = _batch(cfg, rng)

        loss, metrics = jax.jit(model.loss)(params, batch)
        assert loss.shape == ()
        assert bool(jnp.isfinite(loss))

        grads = jax.grad(lambda p: model.loss(p, batch)[0])(params)
        gleaves = jax.tree.leaves(grads)
        assert all(np.isfinite(np.asarray(g, np.float32)).all()
                   for g in gleaves)
        # shapes of grads mirror params
        for g, p in zip(gleaves, jax.tree.leaves(params)):
            assert g.shape == p.shape

    def test_hidden_shape(self, arch):
        cfg = get_smoke_config(arch)
        model = build(cfg)
        params = model.init(KEY)
        rng = np.random.RandomState(1)
        batch = _batch(cfg, rng)
        h = model.hidden(params, batch)
        expect_s = S + (cfg.n_patch_tokens
                        if cfg.frontend == "vision_patches" else 0)
        assert h.shape == (B, expect_s, cfg.d_model)
        assert np.isfinite(np.asarray(h, np.float32)).all()


@pytest.mark.parametrize("arch", ["deepseek-7b", "qwen3-1.7b",
                                  "starcoder2-3b", "qwen1.5-4b",
                                  "rwkv6-1.6b", "zamba2-7b",
                                  "whisper-large-v3", "moonlight-16b-a3b"])
def test_decode_matches_forward(arch):
    """Greedy decode with KV cache == teacher-forced forward (f32)."""
    cfg = dataclasses.replace(get_smoke_config(arch), remat=False,
                              compute_dtype="float32")
    model = build(cfg)
    params = model.init(KEY)
    rng = np.random.RandomState(2)
    tokens = jnp.asarray(rng.randint(0, cfg.vocab_size, (B, S + 4)),
                         jnp.int32)
    batch = {"tokens": tokens[:, :S], "max_len": S + 4}
    if cfg.frontend == "audio_frames":
        batch["frames"] = jnp.asarray(
            rng.standard_normal((B, cfg.max_source_positions, cfg.d_model))
            * 0.02, jnp.float32)
    _, cache = model.prefill(params, batch)
    for t in range(3):
        logits, cache = model.decode_step(params, cache, tokens[:, S + t],
                                          jnp.int32(S + t))
        fb = {"tokens": tokens[:, :S + t + 2]}
        if cfg.frontend == "audio_frames":
            fb["frames"] = batch["frames"]
        h = model.hidden(params, fb)
        head = (params["embed"].T if cfg.tie_embeddings else params["head"])
        ref = h[:, S + t] @ head
        scale = float(jnp.abs(ref).max()) + 1e-6
        err = float(jnp.abs(logits - ref).max()) / scale
        assert err < 0.02, f"{arch}: decode diverges {err:.4f}"


def test_moe_decode_matches_forward_high_capacity():
    """With generous capacity (no token drops) MoE decode == forward."""
    cfg = get_smoke_config("olmoe-1b-7b")
    cfg = dataclasses.replace(
        cfg, remat=False, compute_dtype="float32",
        moe=dataclasses.replace(cfg.moe, capacity_factor=8.0))
    model = build(cfg)
    params = model.init(KEY)
    rng = np.random.RandomState(3)
    tokens = jnp.asarray(rng.randint(0, cfg.vocab_size, (B, S + 2)),
                         jnp.int32)
    _, cache = model.prefill(params, {"tokens": tokens[:, :S],
                                      "max_len": S + 2})
    logits, _ = model.decode_step(params, cache, tokens[:, S], jnp.int32(S))
    h = model.hidden(params, {"tokens": tokens[:, :S + 2]})
    ref = h[:, S] @ params["head"]
    scale = float(jnp.abs(ref).max()) + 1e-6
    assert float(jnp.abs(logits - ref).max()) / scale < 0.02


def test_mamba2_chunked_equals_recurrent():
    """SSD chunked scan == stepwise recurrence, bit-tight in f32."""
    cfg = dataclasses.replace(get_smoke_config("zamba2-7b"),
                              compute_dtype="float32")
    p = mamba2.init_params(jax.random.PRNGKey(3), cfg, jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(4), (2, 20, cfg.d_model)) * 0.5
    y_full, state = mamba2.forward(p, cfg, x, return_state=True)
    cache = mamba2.init_cache(cfg, 2, jnp.float32)
    ys = []
    for t in range(20):
        yt, cache = mamba2.decode_step(p, cfg, x[:, t:t + 1], cache)
        ys.append(yt)
    y_step = jnp.concatenate(ys, axis=1)
    np.testing.assert_allclose(np.asarray(y_full), np.asarray(y_step),
                               atol=1e-4)
    np.testing.assert_allclose(np.asarray(state["ssm"]),
                               np.asarray(cache["ssm"]), atol=1e-5)


def test_taf_decode_skips_and_stays_finite():
    """Decode-time TAF (the paper's technique as a serving feature):
    stable decoding skips layer-steps; logits stay finite."""
    cfg = dataclasses.replace(
        get_smoke_config("deepseek-7b"), remat=False,
        compute_dtype="float32",
        approx_decode=ApproxSpec(Technique.TAF, Level.BLOCK,
                                 taf=TAFParams(2, 4, 50.0)))
    model = build(cfg)
    params = model.init(KEY)
    rng = np.random.RandomState(5)
    tokens = jnp.asarray(rng.randint(0, cfg.vocab_size, (B, 8)), jnp.int32)
    _, cache = model.prefill(params, {"tokens": tokens, "max_len": 24})
    tok = tokens[:, -1]
    skipped = 0
    for t in range(12):
        logits, cache = model.decode_step(params, cache, tok,
                                          jnp.int32(8 + t))
        assert np.isfinite(np.asarray(logits)).all()
        tok = jnp.argmax(logits, -1).astype(jnp.int32)
        skipped += int((np.asarray(cache["taf"]["remaining"]) > 0).sum())
    assert skipped > 0, "huge threshold must trigger TAF skips"


def test_vocab_padding():
    cfg = get_smoke_config("whisper-large-v3")
    assert cfg.padded_vocab_size % cfg.vocab_pad_multiple == 0
    assert cfg.padded_vocab_size >= cfg.vocab_size


def test_param_counts_match_targets():
    """Analytic counts line up with the briefs' model sizes."""
    from repro.configs import get_config
    targets = {"deepseek-v3-671b": (600e9, 750e9),
               "olmoe-1b-7b": (6e9, 8e9),
               "pixtral-12b": (10e9, 14e9),
               "deepseek-7b": (6e9, 8e9),
               "starcoder2-3b": (2.5e9, 4e9),
               "moonlight-16b-a3b": (15.9e9, 16.1e9)}
    for arch, (lo, hi) in targets.items():
        n = get_config(arch).param_count()
        assert lo < n < hi, f"{arch}: {n/1e9:.1f}B outside [{lo},{hi}]"
    assert 30e9 < get_config("deepseek-v3-671b").active_param_count() < 45e9
