"""Moonlight-16B-A3B's program pieces at smoke size on the CPU: the
DeepSeek-V3 sigmoid router with its correction bias, dropless routing over
a held share of the experts, latent-attention decode that reads its cache
in place, the expert row counters that `EngineStats` exposes, the named
scopes in the compiled step, and the parameter counts."""
import dataclasses
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, get_smoke_config
from repro.core.types import ApproxSpec, PerforationParams, Technique
from repro.models import build, common, mla, moe

KEY = jax.random.PRNGKey(0)
REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _cfg(**moe_fields):
    cfg = dataclasses.replace(get_smoke_config("moonlight-16b-a3b"),
                              compute_dtype="float32")
    return dataclasses.replace(cfg, moe=dataclasses.replace(cfg.moe,
                                                            **moe_fields))


def _moe_params(cfg, bias=None):
    p = moe.init_params(KEY, cfg, jnp.float32)
    if bias is not None:
        p["router_bias"] = jnp.asarray(bias, jnp.float32)
    return p


def test_router_bias_selects_and_does_not_weight():
    m = _cfg().moe                              # 8 experts, top-2
    x = jnp.eye(8, dtype=jnp.float32)
    router = jnp.diag(jnp.linspace(2.0, -2.0, 8))   # token i: expert i high
    p = {"router": router, "router_bias": jnp.zeros(8)}
    w, idx = moe.route(p, m, x)
    scores = jax.nn.sigmoid(x @ router)
    # without the bias token 0 picks expert 0 and the next highest score
    assert int(idx[0, 0]) == 0
    # a large bias on expert 7 makes every token choose it...
    p["router_bias"] = jnp.zeros(8).at[7].set(10.0)
    w_b, idx_b = moe.route(p, m, x)
    assert (np.asarray(idx_b[:, 0]) == 7).all()
    # ...weighted by its score alone, normalised over the k, scaled
    s = np.take_along_axis(np.asarray(scores), np.asarray(idx_b), -1)
    want = s / s.sum(-1, keepdims=True) * 2.446
    np.testing.assert_allclose(np.asarray(w_b), want, rtol=1e-6)
    np.testing.assert_allclose(np.asarray(w_b).sum(-1), 2.446, rtol=1e-6)
    assert not np.allclose(np.asarray(w_b), np.asarray(w))


def _reference_layer(p, cfg, x):
    """The held experts' part of the layer, every routed pair computed, in
    plain float32, plus the shared experts."""
    m = cfg.moe
    x2 = x.reshape(-1, x.shape[-1])
    logits = x2 @ p["router"]
    scores = jax.nn.sigmoid(logits)
    _, idx = jax.lax.top_k(scores + p["router_bias"], m.experts_per_token)
    wt = np.take_along_axis(np.asarray(scores), np.asarray(idx), -1)
    wt = wt / wt.sum(-1, keepdims=True) * m.routed_scaling_factor
    out = np.zeros(x2.shape, np.float32)
    for t in range(x2.shape[0]):
        for j in range(m.experts_per_token):
            e = int(idx[t, j]) - m.expert_offset
            if 0 <= e < m.held:
                xe = x2[t]
                h = jax.nn.silu(xe @ p["w_gate"][e]) * (xe @ p["w_up"][e])
                out[t] += wt[t, j] * np.asarray(h @ p["w_down"][e])
    sh = p["shared"]
    out += np.asarray((jax.nn.silu(x2 @ sh["w_gate"]) * (x2 @ sh["w_up"]))
                      @ sh["w_down"])
    return out.reshape(x.shape)


def _forms(monkeypatch, grouped):
    """Make 32-token chunks take the grouped (or the masked) product."""
    monkeypatch.setattr(moe, "MASKED_MAX_TOKENS", 0 if grouped else 256)


@pytest.mark.parametrize("grouped", [False, True])
def test_dropless_under_total_skew(grouped, monkeypatch):
    """Every token picks the same two held experts: all 2 x 32 pairs are
    computed, as the reference computes them; GShard's capacity would
    keep 2 x 32 x 1.25 / 8 = 10 of them."""
    cfg = _cfg()
    p = _moe_params(cfg, bias=[0, 9, 9, 0, 0, 0, 0, 0])
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 16, 64)) * 0.5
    _forms(monkeypatch, grouped)
    with jax.default_matmul_precision("highest"):
        out, aux, routed = moe.dropless_forward(p, cfg, x)
        want = _reference_layer(p, cfg, x)
    np.testing.assert_allclose(np.asarray(out), want, rtol=2e-4, atol=2e-5)
    assert np.asarray(routed).tolist() == [0, 32, 32, 0]
    # the masked product computes all 4 held experts on the 32 tokens, the
    # grouped one the routed rows alone
    assert moe.rows_computed(cfg, 32) == (None if grouped else 32 * 4)
    assert float(aux) == 0.0


@pytest.mark.parametrize("offset", [0, 4])
def test_grouped_and_masked_products_agree(offset, monkeypatch):
    cfg = _cfg(expert_offset=offset)
    p = _moe_params(cfg, bias=np.linspace(-0.2, 0.2, 8))
    x = jax.random.normal(jax.random.PRNGKey(2), (4, 16, 64))
    with jax.default_matmul_precision("highest"):
        _forms(monkeypatch, False)
        a = moe.dropless_forward(p, cfg, x)
        _forms(monkeypatch, True)
        b = moe.dropless_forward(p, cfg, x)
        want = _reference_layer(p, cfg, x)
    np.testing.assert_allclose(np.asarray(a[0]), want, rtol=2e-4, atol=2e-5)
    np.testing.assert_allclose(np.asarray(b[0]), want, rtol=2e-4, atol=2e-5)
    assert (np.asarray(a[2]) == np.asarray(b[2])).all()


def test_expert_perforation_over_the_held_experts():
    """Herded perforation drops every 2nd held expert (ids 1 and 3) from the
    router's choice and from the weights read."""
    cfg = _cfg()
    p = _moe_params(cfg, bias=[0, 9, 9, 9, 0, 0, 0, 0])
    x = jax.random.normal(jax.random.PRNGKey(3), (2, 16, 64))
    spec = ApproxSpec(Technique.PERFORATION,
                      perforation=PerforationParams(skip=2))
    out, _, routed = moe.dropless_forward(p, cfg, x, approx=spec)
    routed = np.asarray(routed)
    assert routed[1] == 0 and routed[3] == 0 and routed[2] == 32
    assert moe.rows_computed(cfg, 32, spec) == 32 * 2
    assert np.isfinite(np.asarray(out)).all()


def _local_copy_decode(p, cfg, x, cache, pos):
    """The decode that wrote the token's rows into a copy of the layer
    cache and attended to the copy as a whole."""
    m = cfg.mla
    b, h = x.shape[0], cfg.n_heads
    positions = jnp.full((1,), pos, jnp.int32)
    q = mla._queries(p, cfg, x, positions)
    ckv_t, k_rope_t = mla._latent(p, cfg, x, positions)
    rows = {"ckv": ckv_t.astype(cache["ckv"].dtype),
            "k_rope": k_rope_t.astype(cache["k_rope"].dtype)}
    cache = common.write_rows(cache, rows, pos)
    ckv, k_rope = cache["ckv"], cache["k_rope"][:, 0]
    w_uk = p["w_uk"].reshape(m.kv_lora_rank, h, m.qk_nope_head_dim)
    q_lat = jnp.einsum("bhqd,rhd->bhqr", q[..., :m.qk_nope_head_dim], w_uk)
    logits = (jnp.einsum("bhqr,bsr->bhqs", q_lat, ckv)
              + jnp.einsum("bhqd,bsd->bhqs", q[..., m.qk_nope_head_dim:],
                           k_rope))
    logits = logits / ((m.qk_nope_head_dim + m.qk_rope_head_dim) ** 0.5)
    logits = jnp.where(jnp.arange(ckv.shape[1]) <= pos, logits, -1e30)
    pr = jax.nn.softmax(logits, axis=-1)
    ctx_lat = jnp.einsum("bhqs,bsr->bhqr", pr, ckv)
    w_uv = p["w_uv"].reshape(m.kv_lora_rank, h, m.v_head_dim)
    ctx = jnp.einsum("bhqr,rhd->bhqd", ctx_lat, w_uv)
    ctx = ctx.transpose(0, 2, 1, 3).reshape(b, 1, -1)
    return ctx @ p["wo"], rows


@pytest.mark.parametrize("pos", [0, 5, 11])
def test_mla_decode_in_place_matches_the_local_copy(pos):
    cfg = _cfg()
    p = mla.init_params(KEY, cfg, jnp.float32)
    cache = {"ckv": jax.random.normal(jax.random.PRNGKey(4), (3, 12, 16)),
             "k_rope": jax.random.normal(jax.random.PRNGKey(5),
                                         (3, 1, 12, 8))}
    x = jax.random.normal(jax.random.PRNGKey(6), (3, 1, 64))
    with jax.default_matmul_precision("highest"):
        out, rows = mla.decode_step(p, cfg, x, cache, jnp.int32(pos))
        want, want_rows = _local_copy_decode(p, cfg, x, cache, pos)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=1e-5, atol=1e-5)
    for k in rows:
        np.testing.assert_array_equal(np.asarray(rows[k]),
                                      np.asarray(want_rows[k]))


def test_counters_match_a_count_by_hand():
    """Every token routes to held experts 1 and 2 (bias), so each decode
    step adds, per expert layer, one row per lane to each of them and
    lanes x 4 computed rows (the masked product); the admission splice
    keeps the counts, and a new cache starts them again at 0."""
    from repro.serving import Request, ServingEngine
    cfg = _cfg()
    model = build(cfg)
    params = model.init(KEY)
    params["moe_blocks"]["moe"]["router_bias"] = jnp.zeros(
        (2, 8)).at[:, 1:3].set(20.0)
    eng = ServingEngine(model, params, slots=4, max_len=24, prompt_len=6)
    assert eng.stats.moe_rows_routed == 0           # no cache yet
    rng = np.random.default_rng(0)
    for uid in range(4):
        eng.submit(Request(uid=uid, prompt=rng.integers(0, 256, 6),
                           max_new_tokens=3 + uid))
    eng.run_until_drained()
    ticks = eng.stats.ticks
    assert ticks == 6
    n_moe, lanes = 2, 4
    assert eng.stats.moe_rows_routed == 2 * n_moe * lanes * ticks
    assert eng.stats.moe_rows_max == lanes * ticks
    assert eng.stats.moe_rows_computed == n_moe * lanes * 4 * ticks
    eng.submit(Request(uid=9, prompt=rng.integers(0, 256, 6),
                       max_new_tokens=2))
    eng.run_until_drained()
    ticks = eng.stats.ticks
    assert ticks == 8
    assert eng.stats.moe_rows_routed == 2 * n_moe * lanes * ticks
    assert eng.stats.moe_rows_max == lanes * ticks
    assert eng.stats.moe_rows_computed == n_moe * lanes * 4 * ticks
    # a one-slot engine prefills a new cache for each request: the counts
    # are the second request's 3 steps alone
    one = ServingEngine(model, params, slots=1, max_len=24, prompt_len=6)
    for uid, n in ((0, 5), (1, 3)):
        one.submit(Request(uid=uid, prompt=rng.integers(0, 256, 6),
                           max_new_tokens=n))
    one.run_until_drained()
    assert one.stats.ticks == 8
    assert one.stats.moe_rows_routed == 2 * n_moe * 3
    assert one.stats.moe_rows_computed == n_moe * 4 * 3


_SHARDED = r"""
import dataclasses
import jax, jax.numpy as jnp, numpy as np
from repro.configs import get_smoke_config
from repro.models import build
from repro.serving import Request, ServingEngine

cfg = dataclasses.replace(get_smoke_config("moonlight-16b-a3b"),
                          compute_dtype="float32")
model = build(cfg)
params = model.init(jax.random.PRNGKey(0))
params["moe_blocks"]["moe"]["router_bias"] = jnp.zeros(
    (2, 8)).at[:, 1:3].set(20.0)

def serve(devices, shards):
    eng = ServingEngine(model, params, slots=8, max_len=24, prompt_len=6,
                        devices=devices, shards=shards)
    eng.warmup()
    rng = np.random.default_rng(0)
    reqs = [Request(uid=u, prompt=rng.integers(0, 256, 6),
                    max_new_tokens=3 + u % 4) for u in range(12)]
    for r in reqs:
        eng.submit(r)
    eng.run_until_drained()
    return eng, [r.output for r in reqs]

es, outs = serve(4, 4)
ep, outp = serve(None, None)
assert es.mesh_shape == (4, 1) and es.cache["moe_rows"].shape == (4, 2, 4)
assert outs == outp, "decode outputs"
ticks = es.stats.ticks
assert ticks == ep.stats.ticks
# every lane of every shard routes to held experts 1 and 2 each step
n_moe, slots = 2, 8
assert es.stats.moe_rows_routed == 2 * n_moe * slots * ticks
assert es.stats.moe_rows_max == slots * ticks
assert es.stats.moe_rows_computed == n_moe * slots * 4 * ticks
for name in ("moe_rows_routed", "moe_rows_max", "moe_rows_computed"):
    assert getattr(es.stats, name) == getattr(ep.stats, name), name
print("SHARDED_OK", ticks)
"""


def test_sharded_engine_serves_a_dropless_model():
    """Four shards on four CPU devices: the per-shard row counters sum to
    the unsharded engine's counts, and the tokens are the same."""
    env = dict(os.environ,
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.path.join(REPO, "src"))
    out = subprocess.run([sys.executable, "-c", _SHARDED], env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    assert "SHARDED_OK" in out.stdout


def test_named_scopes_reach_the_compiled_step():
    from repro.launch import steps
    cfg = _cfg()
    model = build(cfg)
    params = model.init(KEY)
    cache = model.init_cache(2, 8)
    text = jax.jit(steps.make_serve_step(model)).lower(
        params, cache, jnp.zeros((2,), jnp.int32), jnp.int32(3)
    ).compile().as_text()
    for scope in ("mla", "moe.route", "moe.experts", "moe.shared"):
        assert f"/{scope}/" in text, scope


def test_param_counts():
    """The cut (8 of 64 experts held): 3.36 B parameters, 6.73 GB in
    bf16; the published whole model: 16.0 B."""
    cut = get_config("moonlight-16b-a3b")
    assert cut.moe.held == 64 and cut.mla.q_lora_rank is None
    whole = cut.param_count()
    assert 15.9e9 < whole < 16.05e9
    share = dataclasses.replace(cut, moe=dataclasses.replace(cut.moe,
                                                             n_held=8))
    assert 3.35e9 < share.param_count() < 3.37e9
    assert 6.70e9 < 2 * share.param_count() < 6.75e9
    # the count is the parameters the program holds
    shapes = jax.eval_shape(build(share).init, KEY)
    held = sum(a.size for a in jax.tree.leaves(shapes))
    norms = 27 * (2 * 2048 + 512) + 2048 + 26 * 64   # scales, biases
    assert held == share.param_count() + norms
