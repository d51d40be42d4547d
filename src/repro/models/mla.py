"""Multi-head Latent Attention (DeepSeek-V2/V3).

KV are low-rank compressed, and queries too unless `q_lora_rank` is None
(one plain projection, no query norm: Moonlight). The KV cache stores ONLY
the compressed latent (kv_lora_rank) plus the shared rope key
(qk_rope_head_dim) per position -- 576 floats/token for dsv3 instead of
2*128*128: the reason decode_32k fits. Prefill expands K/V from the latent
(the published form); decode absorbs the expansion into the query and the
output (`decode_step`).
"""
from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from . import common


def init_params(key, cfg: ModelConfig, dtype) -> Dict:
    m = cfg.mla
    d = cfg.d_model
    h = cfg.n_heads
    ks = jax.random.split(key, 7)
    qk_head = m.qk_nope_head_dim + m.qk_rope_head_dim
    if m.q_lora_rank is None:
        q = {"w_q": common.dense_init(ks[0], (d, h * qk_head), dtype=dtype)}
    else:
        q = {
            "w_dq": common.dense_init(ks[0], (d, m.q_lora_rank), dtype=dtype),
            "q_norm": common.rmsnorm_params(m.q_lora_rank, dtype),
            "w_uq": common.dense_init(ks[1], (m.q_lora_rank, h * qk_head),
                                      dtype=dtype),
        }
    return {
        **q,
        "w_dkv": common.dense_init(
            ks[2], (d, m.kv_lora_rank + m.qk_rope_head_dim), dtype=dtype),
        "kv_norm": common.rmsnorm_params(m.kv_lora_rank, dtype),
        "w_uk": common.dense_init(ks[3], (m.kv_lora_rank,
                                          h * m.qk_nope_head_dim), dtype=dtype),
        "w_uv": common.dense_init(ks[4], (m.kv_lora_rank, h * m.v_head_dim),
                                  dtype=dtype),
        "wo": common.dense_init(ks[5], (h * m.v_head_dim, d), dtype=dtype),
    }


def _queries(p, cfg: ModelConfig, x, positions):
    m = cfg.mla
    b, s, _ = x.shape
    h = cfg.n_heads
    if m.q_lora_rank is None:
        w_q = common.shard_hint(p["w_q"], None, "model")
        q = jnp.einsum("bsd,dh->bsh", x, w_q.astype(x.dtype))
    else:
        w_dq = common.shard_hint(p["w_dq"], None, "model")
        cq = common.rmsnorm(p["q_norm"],
                            jnp.einsum("bsd,dr->bsr", x,
                                       w_dq.astype(x.dtype)),
                            cfg.norm_eps)
        w_uq = common.shard_hint(p["w_uq"], None, "model")
        q = jnp.einsum("bsr,rh->bsh", cq, w_uq.astype(x.dtype))
    q = q.reshape(b, s, h, m.qk_nope_head_dim + m.qk_rope_head_dim)
    q = q.transpose(0, 2, 1, 3)
    q_nope = q[..., :m.qk_nope_head_dim]
    q_rope = common.apply_rope(q[..., m.qk_nope_head_dim:], positions,
                               cfg.rope_theta)
    return jnp.concatenate([q_nope, q_rope], axis=-1)


def _latent(p, cfg: ModelConfig, x, positions):
    """Compressed latent ckv (B,S,R) + shared rope key (B,1,S,rope_d)."""
    m = cfg.mla
    w_dkv = common.shard_hint(p["w_dkv"], None, "model")
    dkv = jnp.einsum("bsd,dr->bsr", x, w_dkv.astype(x.dtype))
    ckv = common.rmsnorm(p["kv_norm"], dkv[..., :m.kv_lora_rank], cfg.norm_eps)
    k_rope = dkv[..., m.kv_lora_rank:][:, None]              # (B,1,S,rd)
    k_rope = common.apply_rope(k_rope, positions, cfg.rope_theta)
    return ckv, k_rope


def _expand_kv(p, cfg: ModelConfig, ckv, k_rope):
    """Expand latent to per-head K (nope||rope) and V."""
    m = cfg.mla
    b, s, _ = ckv.shape
    h = cfg.n_heads
    w_uk = common.shard_hint(p["w_uk"], None, "model")
    k_nope = jnp.einsum("bsr,rh->bsh", ckv, w_uk.astype(ckv.dtype))
    k_nope = k_nope.reshape(b, s, h, m.qk_nope_head_dim).transpose(0, 2, 1, 3)
    w_uv = common.shard_hint(p["w_uv"], None, "model")
    v = jnp.einsum("bsr,rh->bsh", ckv, w_uv.astype(ckv.dtype))
    v = v.reshape(b, s, h, m.v_head_dim).transpose(0, 2, 1, 3)
    k_rope_b = jnp.broadcast_to(k_rope, (b, h, s, m.qk_rope_head_dim))
    k = jnp.concatenate([k_nope, k_rope_b], axis=-1)
    return k, v


def forward(p, cfg: ModelConfig, x: jnp.ndarray, positions,
            causal: bool = True, approx=None) -> jnp.ndarray:
    b, s, _ = x.shape
    q = _queries(p, cfg, x, positions)
    ckv, k_rope = _latent(p, cfg, x, positions)
    k, v = _expand_kv(p, cfg, ckv, k_rope)
    ctx = common.chunked_attention(q, k, v, causal=causal)
    ctx = ctx.transpose(0, 2, 1, 3).reshape(b, s, -1)
    return jnp.einsum("bsh,hd->bsd", ctx, p["wo"].astype(x.dtype))


def init_cache(cfg: ModelConfig, batch: int, max_len: int, dtype) -> Dict:
    m = cfg.mla
    return {
        "ckv": jnp.zeros((batch, max_len, m.kv_lora_rank), dtype),
        "k_rope": jnp.zeros((batch, 1, max_len, m.qk_rope_head_dim), dtype),
    }


def prefill(p, cfg: ModelConfig, x, cache, approx=None) -> Tuple[jnp.ndarray, Dict]:
    b, s, _ = x.shape
    positions = jnp.arange(s)
    q = _queries(p, cfg, x, positions)
    ckv, k_rope = _latent(p, cfg, x, positions)
    k, v = _expand_kv(p, cfg, ckv, k_rope)
    ctx = common.chunked_attention(q, k, v, causal=True)
    ctx = ctx.transpose(0, 2, 1, 3).reshape(b, s, -1)
    out = jnp.einsum("bsh,hd->bsd", ctx, p["wo"].astype(x.dtype))
    cache = {
        "ckv": jax.lax.dynamic_update_slice(
            cache["ckv"], ckv.astype(cache["ckv"].dtype), (0, 0, 0)),
        "k_rope": jax.lax.dynamic_update_slice(
            cache["k_rope"], k_rope.astype(cache["k_rope"].dtype),
            (0, 0, 0, 0)),
    }
    return out, cache


def decode_step(p, cfg: ModelConfig, x, cache, pos,
                approx=None) -> Tuple[jnp.ndarray, Dict]:
    """ABSORBED MLA decode (section Perf iteration B6, the DeepSeek serving form):

      logits[s] = (q_nope W_uk) . ckv[s] + q_rope . k_rope[s]
      ctx       = (softmax . ckv) W_uv

    K/V are never expanded: per layer the step reads the (B,S,R) latent
    cache once (dsv3: 268 MB/dev) instead of materializing (B,H,S,192+128)
    expansions (~26 GB/dev). More latent-side FLOPs (R=512 vs 192 per
    score), the right trade for a memory-bound decode.

    The layer cache is read in place: its positions < `pos` and the
    token's own latent and rope key (which the cache need not hold yet)
    enter one float32 softmax, as `common.decode_attention(k_new=...)`
    does for dense attention. Returns (out, rows): the token's rows at
    `pos`, for the caller to write (`common.write_rows`).
    """
    m = cfg.mla
    b = x.shape[0]
    h = cfg.n_heads
    positions = jnp.full((1,), pos, jnp.int32)
    q = _queries(p, cfg, x, positions)                       # (B,H,1,qk)
    q_nope = q[..., :m.qk_nope_head_dim]
    q_rope = q[..., m.qk_nope_head_dim:]
    ckv_t, k_rope_t = _latent(p, cfg, x, positions)
    rows = {"ckv": ckv_t.astype(cache["ckv"].dtype),
            "k_rope": k_rope_t.astype(cache["k_rope"].dtype)}
    ckv = cache["ckv"].astype(x.dtype)                       # (B,S,R)
    k_rope = cache["k_rope"].astype(x.dtype)[:, 0]           # (B,S,rd)
    ckv_new = rows["ckv"].astype(x.dtype)                    # (B,1,R)
    k_rope_new = rows["k_rope"].astype(x.dtype)[:, 0]        # (B,1,rd)
    skv = ckv.shape[1]
    da = common.data_axes_hint()
    scale = (m.qk_nope_head_dim + m.qk_rope_head_dim) ** 0.5
    # absorb W_uk into the query: (R, H*nope) -> (H, nope, R)
    w_uk = common.shard_hint(p["w_uk"], None, "model").astype(x.dtype)
    w_uk = w_uk.reshape(m.kv_lora_rank, h, m.qk_nope_head_dim)
    q_lat = jnp.einsum("bhqd,rhd->bhqr", q_nope, w_uk)       # (B,H,1,R)

    def scores(lat, rope):
        s = jnp.einsum("bhqr,bsr->bhqs", q_lat, lat,
                       preferred_element_type=jnp.float32)
        s = s + jnp.einsum("bhqd,bsd->bhqs", q_rope, rope,
                           preferred_element_type=jnp.float32)
        return s / scale

    logits = common.shard_hint(scores(ckv, k_rope), da, None, None, "model")
    logit_new = scores(ckv_new, k_rope_new)                  # (B,H,1,1)
    mask = jnp.arange(skv)[None, None, None, :] < pos
    logits = jnp.where(mask, logits, -1e30)
    mx = jnp.maximum(jnp.max(logits, axis=-1, keepdims=True), logit_new)
    pr = jnp.where(mask, jnp.exp(logits - mx), 0.0)
    p_new = jnp.exp(logit_new - mx)
    l = jnp.sum(pr, axis=-1, keepdims=True) + p_new
    ctx_lat = jnp.einsum("bhqs,bsr->bhqr", pr.astype(x.dtype), ckv,
                         preferred_element_type=jnp.float32)
    ctx_lat = ctx_lat + jnp.einsum("bhqs,bsr->bhqr", p_new.astype(x.dtype),
                                   ckv_new,
                                   preferred_element_type=jnp.float32)
    ctx_lat = (ctx_lat / jnp.maximum(l, 1e-30)).astype(x.dtype)
    # absorb W_uv on the way out: (R, H*dv) -> (H, R, dv)
    w_uv = common.shard_hint(p["w_uv"], None, "model").astype(x.dtype)
    w_uv = w_uv.reshape(m.kv_lora_rank, h, m.v_head_dim)
    ctx = jnp.einsum("bhqr,rhd->bhqd", ctx_lat, w_uv)        # (B,H,1,dv)
    ctx = ctx.transpose(0, 2, 1, 3).reshape(b, 1, -1)
    return jnp.einsum("bsh,hd->bsd", ctx, p["wo"].astype(x.dtype)), rows
