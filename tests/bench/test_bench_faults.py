"""A run of the harness on the CPU at smoke size with its serve step
broken underneath must come out not correct: once for each fault a
serving cell can have (a step that returns its state unchanged, half of
the batch left out, a token altered where it is produced), and so must
the fp8 control put in the program's place, judged by the limit the
float32 program meets."""
import time

import jax
import jax.numpy as jnp
import pytest

from bench import harness

SEED = 2 ** 33 + 17


def _run(root, workload, hook=None, seconds=1.0, seed=SEED):
    return harness.run_cell(root, workload, seed, seconds, False,
                            time.perf_counter(), require_chip=False,
                            engine_hook=hook, log=lambda m: None)


def _stale_state(engine):
    serve = engine._serve

    def step(params, cache, tokens, pos):
        # the step donates the cache it is given: keep a copy to return
        old = jax.tree.map(jnp.copy, cache)
        nt, logits, _ = serve(params, cache, tokens, pos)
        return nt, logits, old
    engine._serve = step


def _half_batch(engine):
    serve = engine._serve

    def step(params, cache, tokens, pos):
        nt, logits, nc = serve(params, cache, tokens, pos)
        half = nt.shape[0] // 2
        return nt.at[half:].set(tokens[half:]), logits, nc
    engine._serve = step


def _altered_token(engine):
    serve = engine._serve
    vocab = engine.model.cfg.vocab_size

    def step(params, cache, tokens, pos):
        nt, logits, nc = serve(params, cache, tokens, pos)
        return (nt + 1) % vocab, logits, nc
    engine._serve = step


@pytest.mark.parametrize("fault", [_stale_state, _half_batch,
                                   _altered_token],
                         ids=["state_unchanged", "half_batch_left_out",
                              "token_altered"])
@pytest.mark.parametrize("workload", ["qwen3-smoke.smoke-precise",
                                      "qwen1.5-smoke.smoke-qos"])
def test_broken_serve_step_is_not_correct(bench_root, workload, fault):
    r = _run(bench_root, workload, hook=fault)
    assert not r["correct"], r["checks"]
    assert r["checks"]["max_gap_std"]["value"] > 1e-3


@pytest.mark.parametrize("workload", ["qwen3-smoke.smoke-control",
                                      "qwen1.5-smoke.smoke-control"])
def test_fp8_control_fails_the_limit(bench_root, workload):
    """The control: at the positions of the same prompts and served
    tokens, the tokens the fp8 reference puts first go through the same
    checks and limits as the program's and come out not correct, while
    the float32 program comes out correct."""
    r = harness.run_cell(bench_root, workload, SEED, 1.0, False,
                         time.perf_counter(), require_chip=False,
                         control=True, log=lambda m: None)
    assert r["correct"], r["checks"]
    assert not r["control"]["correct"], r["control"]
    ctl = r["control"]["checks"]["max_gap_std"]
    assert ctl["tokens"] == r["checks"]["max_gap_std"]["tokens"]
