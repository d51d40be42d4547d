"""The latent-attention mixture-of-experts family (`bench/reference/
mla_moe.py`) at smoke size on the CPU: its parameter tree is the
program's, the program's prefill and decode steps through the cache agree
with its float32 reference, the shares of the experts add up to the uncut
layer, a configuration of the family runs through the harness, and its
counts match the published shapes worked out by hand."""
import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import cells, harness
from bench.reference import mla_moe

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
SEED = 2 ** 32 + 11

# Moonlight's layout at smoke size: 3 layers (1 dense), 8 router experts of
# which 4 held, top-2, 2 shared, no query LoRA; float32 weights
SMOKE = {
    "name": "moonlight-smoke", "program": "moonlight-16b-a3b",
    "reference": "mla_moe", "hidden_size": 64, "intermediate_size": 128,
    "num_hidden_layers": 3, "num_attention_heads": 4,
    "num_key_value_heads": 4, "vocab_size": 256, "rope_theta": 50000,
    "rms_norm_eps": 1e-05, "tie_word_embeddings": False,
    "torch_dtype": "float32", "q_lora_rank": None, "kv_lora_rank": 16,
    "qk_nope_head_dim": 16, "qk_rope_head_dim": 8, "v_head_dim": 16,
    "moe_intermediate_size": 32, "n_routed_experts": 4,
    "n_router_experts": 8, "expert_offset": 0, "n_shared_experts": 2,
    "num_experts_per_tok": 2, "first_k_dense_replace": 1,
    "routed_scaling_factor": 2.446, "scoring_func": "sigmoid",
    "topk_method": "noaux_tc", "n_group": 1, "topk_group": 1,
    "norm_topk_prob": True}


def _program(conf):
    from repro.models import build
    cfg = cells.program_config(conf, approx=False)
    return cfg, build(cfg)


def test_parameter_tree_is_the_programs():
    cfg, model = _program(SMOKE)
    assert cfg.moe.held == 4 and cfg.moe.n_experts == 8
    assert cfg.mla.q_lora_rank is None
    params = mla_moe.program_params(SEED, SMOKE, cfg.padded_vocab_size)
    want = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    assert jax.tree.structure(params) == jax.tree.structure(want)
    for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(want)):
        assert a.shape == b.shape, (a.shape, b.shape)


def test_prefill_then_decode_matches_reference():
    """The program (float32 compute) against the reference's full forward
    pass. Tolerance 1e-4 of the largest logit: both compute in float32, so
    they differ by the order of summation only (the program's absorbed
    decode against the reference's expanded attention); a routing choice
    that flipped would move logits by far more."""
    from repro.launch import steps
    cfg, model = _program(SMOKE)
    assert cfg.compute_dtype == "float32"
    params = mla_moe.program_params(SEED, SMOKE, cfg.padded_vocab_size)
    P, G, B = 8, 6, 3
    rng = np.random.default_rng(0)
    prompts = rng.integers(0, SMOKE["vocab_size"], (B, P)).astype(np.int32)
    prefill = jax.jit(steps.make_prefill_step(model, P + G))
    serve = jax.jit(steps.make_serve_step(model))
    logits, cache = prefill(params, {"tokens": jnp.asarray(prompts)})
    got = [np.asarray(logits)]
    tokens = jnp.argmax(logits, -1).astype(jnp.int32)
    seq = [prompts, np.asarray(tokens)[:, None]]
    for t in range(G):
        tokens, logits, cache = serve(params, cache, tokens,
                                      jnp.int32(P + t))
        got.append(np.asarray(logits))
        seq.append(np.asarray(tokens)[:, None])
    rows = np.concatenate(seq, axis=1)[:, :P + G]
    h = mla_moe.final_hidden(SMOKE, SEED, rows)
    want = np.stack([np.asarray(mla_moe.head_logits(SMOKE, SEED,
                                                    h[:, P - 1 + t]))
                     for t in range(G + 1)], axis=1)
    got = np.stack(got, axis=1)[..., :SMOKE["vocab_size"]]
    scale = np.abs(want).max()
    assert np.abs(got - want).max() <= 1e-4 * scale
    assert (got.argmax(-1) == want.argmax(-1)).all()


def test_shares_add_up_to_the_uncut_layer():
    """Two devices holding experts 0-3 and 4-7: their layers' outputs, with
    the shared experts (which every device computes) counted once, add up
    to the reference layer that holds all 8."""
    from repro.models import moe
    uncut = dict(SMOKE, n_routed_experts=8)
    key = jax.random.PRNGKey(7)
    x = jax.random.normal(key, (2, 16, SMOKE["hidden_size"]), jnp.float32)
    seed_key = jax.random.PRNGKey(3)
    with jax.default_matmul_precision("highest"):
        w = mla_moe.moe_layer_weights(seed_key, 1, uncut)
        want = mla_moe.moe(w, x, uncut)
        shared = mla_moe.mlp(w["shared_experts"], x)
    parts = []
    for offset in (0, 4):
        conf = dict(SMOKE, expert_offset=offset)
        cfg, _ = _program(conf)
        ws = mla_moe.moe_layer_weights(seed_key, 1, conf)
        p = {"router": ws["gate"], "router_bias": ws["e_score_correction_bias"],
             "w_gate": ws["experts"]["gate_proj"],
             "w_up": ws["experts"]["up_proj"],
             "w_down": ws["experts"]["down_proj"],
             "shared": {"w_gate": ws["shared_experts"]["gate_proj"],
                        "w_up": ws["shared_experts"]["up_proj"],
                        "w_down": ws["shared_experts"]["down_proj"]}}
        with jax.default_matmul_precision("highest"):
            out, _, routed = moe.forward(p, cfg, x)
        parts.append(out)
        assert int(routed.sum()) > 0
    total = parts[0] + parts[1] - shared
    np.testing.assert_allclose(np.asarray(total), np.asarray(want),
                               rtol=1e-4, atol=1e-4)


def test_family_runs_through_the_harness(tmp_path):
    from conftest import make_bench_root
    root = make_bench_root(tmp_path, [])
    workload = "moonlight-smoke.smoke-precise"
    for path, obj in ((("configs", "moonlight-smoke.json"), SMOKE),
                      (("limits", f"{workload}.json"),
                       {"max_gap_std": {"limit": 1e-3}})):
        path = os.path.join(root, "bench", *path)
        os.makedirs(os.path.dirname(path), exist_ok=True)
        with open(path, "w") as f:
            json.dump(obj, f)
    path = os.path.join(root, "BENCHMARK.json")
    with open(path) as f:
        bench = json.load(f)
    bench["workloads"].append({"name": workload, "config": "moonlight-smoke",
                               "traffic": "smoke-precise", "chips": 1,
                               "why": "smoke"})
    with open(path, "w") as f:
        json.dump(bench, f)
    r = harness.run_cell(root, workload, SEED, 1.0, False,
                         time.perf_counter(), require_chip=False,
                         log=lambda m: None)
    assert r["correct"], r["checks"]
    assert r["failed"] == 0 and r["attempted"] >= 4


def test_published_config_holds_the_catalog_sizes():
    conf = cells.load_config(ROOT, "moonlight-16b-a3b")
    assert conf["reference"] == "mla_moe"
    assert (conf["hidden_size"], conf["num_attention_heads"],
            conf["kv_lora_rank"], conf["qk_nope_head_dim"],
            conf["qk_rope_head_dim"], conf["v_head_dim"]) == \
        (2048, 16, 512, 128, 64, 128)
    assert conf["q_lora_rank"] is None
    assert (conf["intermediate_size"], conf["moe_intermediate_size"],
            conf["num_experts_per_tok"], conf["n_shared_experts"]) == \
        (11264, 1408, 6, 2)
    assert (conf["vocab_size"], conf["num_hidden_layers"]) == (163840, 27)
    assert conf["reduced"] == ["n_routed_experts"]
    assert (conf["n_routed_experts"], conf["n_router_experts"],
            conf["published"]["n_routed_experts"]) == (8, 64, 64)
    cfg = cells.program_config(conf, approx=False)
    assert cfg.moe.held == 8 and cfg.moe.n_experts == 64
    assert cfg.moe.dropless


def test_counts_by_hand():
    """Moonlight's counts worked out from the published shapes."""
    conf = cells.load_config(ROOT, "moonlight-16b-a3b")
    fam = cells.family(conf)
    assert fam is mla_moe
    # attention per layer: q 2048x3072, kv_a 2048x576, kv_b 512x4096,
    # o 2048x2048
    attn = 2048 * 3072 + 2048 * 576 + 512 * 4096 + 2048 * 2048
    assert mla_moe.attention_params(conf) == attn == 13_762_560
    expert = 3 * 2048 * 1408
    # bf16 weights: 27 attentions with their norms (kv 512, ln1, ln2), the
    # dense MLP, 26 x (router 2048x64 + 8 held + 2 shared experts), final
    # norm and head; plus 26 float32 correction biases of 64
    n = (27 * (attn + 512 + 2 * 2048) + 3 * 2048 * 11264
         + 26 * (2048 * 64 + 10 * expert) + 2048 * (163840 + 1))
    assert mla_moe.weight_bytes(conf) == 2 * n + 26 * 4 * 64
    assert 6.0e9 < mla_moe.weight_bytes(conf) < 6.2e9
    assert mla_moe.latent_bytes_per_token(conf) == 27 * 576 * 2 == 31104
    assert mla_moe.decode_step_bytes(conf, 128, 511) == \
        mla_moe.weight_bytes(conf) + 128 * 512 * 31104
    # expected routed rows per token on this chip: 6 x 8 / 64
    assert mla_moe.routed_rows_per_token(conf) == 0.75
    ffn = (2 * 3 * 2048 * 11264
           + 26 * (2 * 2048 * 64 + 2 * expert * 2 + 2 * expert * 0.75))
    proj = 2048 * 3072 + 2048 * 576 + 16 * 128 * 512 * 2 + 2048 * 2048
    per_key = 16 * (512 + 64 + 512)
    tok = 27 * 2 * (proj + per_key * 300) + ffn + 2 * 2048 * 163840
    assert mla_moe.decode_step_flops(conf, 4, 299) == pytest.approx(4 * tok)
    # prefill: projections and expansion of 256 positions, causal
    # attention at 192 + 128 widths, the last position's head
    t = 256
    pre = (t * (2 * 27 * attn + ffn) + 27 * 2 * 16 * 320 * t * (t + 1) // 2
           + 2 * 2048 * 163840)
    assert mla_moe.prefill_flops(conf, 256, 2) == pytest.approx(2 * pre)


def test_fp8_control_rounds_the_expert_stacks():
    conf = dict(SMOKE)
    w = mla_moe.moe_layer_weights(jax.random.PRNGKey(0), 1, conf)
    q = mla_moe._prepare(w, "fp8")
    f = mla_moe._prepare(w, "float32")
    for name in ("gate", "q_proj", "kv_b_proj"):
        assert float(jnp.abs(q[name] - f[name]).max()) > 0
    e = q["experts"]["down_proj"] - f["experts"]["down_proj"]
    assert float(jnp.abs(e).max()) > 0
    assert float(jnp.abs(q["e_score_correction_bias"]
                         - f["e_score_correction_bias"]).max()) == 0
