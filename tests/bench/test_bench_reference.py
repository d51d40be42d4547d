"""The plain float32 reference against the program's prefill and its
decode steps through the cache, at smoke size on the CPU, for both model
families (qwen3: qk-norm, GQA, tied head; qwen1.5: QKV bias, MHA, untied
head). Weights come from bench/weights.py, so this also checks the
permutation of RoPE's layout into the program's."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import cells, weights
from bench.reference import dense
from conftest import SMOKE

SEED = 2 ** 32 + 5


@pytest.mark.parametrize("name", sorted(SMOKE))
def test_prefill_then_decode_matches_reference(name):
    from repro.launch import steps
    from repro.models import build
    conf = dict(SMOKE[name], name=name)
    cfg = cells.program_config(conf, approx=False)
    assert cfg.compute_dtype == "float32"
    model = build(cfg)
    params = weights.program_params(SEED, conf, cfg.padded_vocab_size)
    ref_shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    assert jax.tree.structure(params) == jax.tree.structure(ref_shapes)
    for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(ref_shapes)):
        assert a.shape == b.shape and a.dtype == b.dtype

    P, G, B = 8, 6, 2
    rng = np.random.default_rng(0)
    prompts = rng.integers(0, conf["vocab_size"], (B, P)).astype(np.int32)
    prefill = jax.jit(steps.make_prefill_step(model, P + G))
    serve = jax.jit(steps.make_serve_step(model))
    logits, cache = prefill(params, {"tokens": jnp.asarray(prompts)})
    got = [np.asarray(logits)]
    tokens = jnp.argmax(logits, -1).astype(jnp.int32)
    seq = [prompts, np.asarray(tokens)[:, None]]
    for t in range(G):
        tokens, logits, cache = serve(params, cache, tokens, jnp.int32(P + t))
        got.append(np.asarray(logits))
        seq.append(np.asarray(tokens)[:, None])
    rows = np.concatenate(seq, axis=1)[:, :P + G]
    h = dense.final_hidden(conf, SEED, rows)
    want = np.stack([np.asarray(dense.head_logits(conf, SEED, h[:, P - 1 + t]))
                     for t in range(G + 1)], axis=1)
    got = np.stack(got, axis=1)[..., :conf["vocab_size"]]
    scale = np.abs(want).max()
    assert np.abs(got - want).max() <= 1e-4 * scale
    assert (got.argmax(-1) == want.argmax(-1)).all()


def test_fp8_control_rounds_every_matrix():
    w = jnp.asarray(np.random.default_rng(1).normal(size=(64, 32)),
                    jnp.float32)
    q = dense.quantize_fp8(w)
    rel = float(jnp.abs(q - w).max() / jnp.abs(w).max())
    assert 1e-3 < rel < 0.1            # e4m3 keeps 3 mantissa bits
    assert float(jnp.abs(q).max(0).min()) > 0
