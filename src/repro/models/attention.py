"""GQA attention with RoPE, optional qk-norm (qwen3), optional QKV bias
(qwen1.5), KV cache, and the paper's herded KV-block perforation as a
first-class option (ApproxSpec on the config).

Three lowering paths share one module:
  * train/prefill: chunked flash-style jnp attention (differentiable,
    memory O(chunk^2)); on TPU the Pallas kernel from
    kernels/perforated_attention.py takes over via `use_pallas`.
  * decode: single-token attention against the cache (linear in S).
"""
from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.core.types import ApproxSpec, Technique
from repro.core.perforation import kept_indices
from . import common


def init_params(key, cfg: ModelConfig, dtype) -> Dict:
    d = cfg.d_model
    hd = cfg.resolved_head_dim
    ks = jax.random.split(key, 6)
    p = {
        "wq": common.dense_init(ks[0], (d, cfg.n_heads * hd), dtype=dtype),
        "wk": common.dense_init(ks[1], (d, cfg.n_kv_heads * hd), dtype=dtype),
        "wv": common.dense_init(ks[2], (d, cfg.n_kv_heads * hd), dtype=dtype),
        "wo": common.dense_init(ks[3], (cfg.n_heads * hd, d), dtype=dtype),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((cfg.n_heads * hd,), dtype)
        p["bk"] = jnp.zeros((cfg.n_kv_heads * hd,), dtype)
        p["bv"] = jnp.zeros((cfg.n_kv_heads * hd,), dtype)
    if cfg.qk_norm:
        p["q_norm"] = common.rmsnorm_params(hd, dtype)
        p["k_norm"] = common.rmsnorm_params(hd, dtype)
    return p


def _project_qkv(p, cfg: ModelConfig, x: jnp.ndarray, positions: jnp.ndarray):
    b, s, d = x.shape
    hd = cfg.resolved_head_dim
    wq = common.shard_hint(p["wq"], None, "model")
    wk = common.shard_hint(p["wk"], None, "model")
    wv = common.shard_hint(p["wv"], None, "model")
    q = jnp.einsum("bsd,dh->bsh", x, wq.astype(x.dtype))
    k = jnp.einsum("bsd,dh->bsh", x, wk.astype(x.dtype))
    v = jnp.einsum("bsd,dh->bsh", x, wv.astype(x.dtype))
    if cfg.qkv_bias:
        q = q + p["bq"].astype(x.dtype)
        k = k + p["bk"].astype(x.dtype)
        v = v + p["bv"].astype(x.dtype)
    q = q.reshape(b, s, cfg.n_heads, hd).transpose(0, 2, 1, 3)
    k = k.reshape(b, s, cfg.n_kv_heads, hd).transpose(0, 2, 1, 3)
    v = v.reshape(b, s, cfg.n_kv_heads, hd).transpose(0, 2, 1, 3)
    if cfg.qk_norm:
        q = common.rmsnorm(p["q_norm"], q, cfg.norm_eps)
        k = common.rmsnorm(p["k_norm"], k, cfg.norm_eps)
    q = common.apply_rope(q, positions, cfg.rope_theta)
    k = common.apply_rope(k, positions, cfg.rope_theta)
    return q, k, v


def _maybe_perforate_kv(k, v, spec: ApproxSpec, block: int = 128):
    """Herded KV-block perforation on the jnp path: the kept set is static,
    so the KV tensors are structurally shortened -- same semantics as the
    Pallas kernel's shortened grid (kernels/perforated_attention.py).
    Returns (k, v, kv_positions | None): original timeline positions of the
    kept rows so the causal mask stays exact."""
    if spec is None or spec.technique != Technique.PERFORATION:
        return k, v, None
    skv = k.shape[2]
    nblocks = max(skv // block, 1)
    kept = kept_indices(nblocks, spec.perforation)
    if len(kept) == nblocks:
        return k, v, None
    import numpy as np
    idx = np.concatenate([np.arange(b * block, (b + 1) * block)
                          for b in kept])
    idx = idx[idx < skv]
    jidx = jnp.asarray(idx)
    return jnp.take(k, jidx, axis=2), jnp.take(v, jidx, axis=2), idx


def forward(p, cfg: ModelConfig, x: jnp.ndarray, positions: jnp.ndarray,
            causal: bool = True,
            approx: Optional[ApproxSpec] = None) -> jnp.ndarray:
    """Self-attention over a full sequence (train / prefill)."""
    b, s, d = x.shape
    q, k, v = _project_qkv(p, cfg, x, positions)
    k, v, kv_pos = _maybe_perforate_kv(k, v, approx)
    ctx = common.chunked_attention(q, k, v, causal=causal,
                                   kv_positions=kv_pos)
    ctx = ctx.transpose(0, 2, 1, 3).reshape(b, s, -1)
    wo = common.shard_hint(p["wo"], "model", None)
    return jnp.einsum("bsh,hd->bsd", ctx, wo.astype(x.dtype))


def init_cache(cfg: ModelConfig, batch: int, max_len: int, dtype) -> Dict:
    hd = cfg.resolved_head_dim
    if cfg.kv_cache_dtype == "int8":
        return {
            "k": jnp.zeros((batch, cfg.n_kv_heads, max_len, hd), jnp.int8),
            "v": jnp.zeros((batch, cfg.n_kv_heads, max_len, hd), jnp.int8),
            "k_scale": jnp.zeros((batch, cfg.n_kv_heads, max_len, 1),
                                 jnp.bfloat16),
            "v_scale": jnp.zeros((batch, cfg.n_kv_heads, max_len, 1),
                                 jnp.bfloat16),
        }
    return {
        "k": jnp.zeros((batch, cfg.n_kv_heads, max_len, hd), dtype),
        "v": jnp.zeros((batch, cfg.n_kv_heads, max_len, hd), dtype),
    }


def _quantize_kv(x: jnp.ndarray):
    """Symmetric per-(b, h, s) int8 quantization of K/V rows."""
    m = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=-1, keepdims=True)
    scale = jnp.maximum(m, 1e-6) / 127.0
    q = jnp.clip(jnp.round(x.astype(jnp.float32) / scale), -127, 127)
    return q.astype(jnp.int8), scale.astype(jnp.bfloat16)


def prefill(p, cfg: ModelConfig, x: jnp.ndarray, cache: Dict,
            approx: Optional[ApproxSpec] = None) -> Tuple[jnp.ndarray, Dict]:
    """Full-sequence forward that also fills the cache[0:S]."""
    b, s, _ = x.shape
    positions = jnp.arange(s)
    q, k, v = _project_qkv(p, cfg, x, positions)
    kk, vv, kv_pos = _maybe_perforate_kv(k, v, approx)
    ctx = common.chunked_attention(q, kk, vv, causal=True,
                                   kv_positions=kv_pos)
    ctx = ctx.transpose(0, 2, 1, 3).reshape(b, s, -1)
    out = jnp.einsum("bsh,hd->bsd", ctx, p["wo"].astype(x.dtype))
    if cfg.kv_cache_dtype == "int8":
        kq, ks = _quantize_kv(k)
        vq, vs = _quantize_kv(v)
        cache = {
            "k": jax.lax.dynamic_update_slice(cache["k"], kq, (0, 0, 0, 0)),
            "v": jax.lax.dynamic_update_slice(cache["v"], vq, (0, 0, 0, 0)),
            "k_scale": jax.lax.dynamic_update_slice(
                cache["k_scale"], ks, (0, 0, 0, 0)),
            "v_scale": jax.lax.dynamic_update_slice(
                cache["v_scale"], vs, (0, 0, 0, 0)),
        }
        return out, cache
    cache = {
        "k": jax.lax.dynamic_update_slice(cache["k"], k, (0, 0, 0, 0)),
        "v": jax.lax.dynamic_update_slice(cache["v"], v, (0, 0, 0, 0)),
    }
    return out, cache


def _decode_step_int8(p, cfg: ModelConfig, q, k, v, x, cache: Dict, pos,
                      approx: Optional[ApproxSpec]) -> Tuple[jnp.ndarray, Dict]:
    """int8-KV decode (section Perf cell A, beyond-paper): the cache stores int8
    rows + per-(b,h,s) scales; logits/context absorb the scales exactly:
      logits[.., s] = (q . k_int8[s]) * k_scale[s]
      ctx = sum_s (p[s] * v_scale[s]) * v_int8[s]
    The quantized row goes into a local copy of the layer's cache, which is
    attended as a whole; the rows are returned for the caller to write.
    """
    b = x.shape[0]
    kq, ks = _quantize_kv(k)
    vq, vs = _quantize_kv(v)
    rows = {"k": kq, "v": vq, "k_scale": ks, "v_scale": vs}
    ck, cv, cks, cvs = (
        jax.lax.dynamic_update_slice(cache[n], rows[n], (0, 0, pos, 0))
        for n in ("k", "v", "k_scale", "v_scale"))
    hq = q.shape[1]
    hkv = ck.shape[1]
    group = hq // hkv
    d = q.shape[-1]
    skv = ck.shape[2]
    da = common.data_axes_hint()
    scale = 1.0 / (d ** 0.5)
    qg = q.reshape(b, hkv, group, d)
    logits = jnp.einsum("bhgd,bhsd->bhgs", qg, ck.astype(q.dtype),
                        preferred_element_type=jnp.float32)
    logits = logits * cks[:, :, None, :, 0].astype(jnp.float32) * scale
    logits = common.shard_hint(logits, da, None, None, "model")
    mask = jnp.arange(skv)[None, None, None, :] <= pos
    logits = jnp.where(mask, logits, -1e30)
    m = jnp.max(logits, axis=-1, keepdims=True)
    pr = jnp.exp(logits - m)
    pr = jnp.where(mask, pr, 0.0)
    l = jnp.sum(pr, axis=-1, keepdims=True)
    pv = (pr * cvs[:, :, None, :, 0].astype(jnp.float32)).astype(q.dtype)
    ctx = jnp.einsum("bhgs,bhsd->bhgd", pv, cv.astype(q.dtype),
                     preferred_element_type=jnp.float32)
    ctx = ctx / jnp.maximum(l, 1e-30)
    ctx = ctx.reshape(b, hq, 1, d).astype(x.dtype)
    ctx = ctx.transpose(0, 2, 1, 3).reshape(b, 1, -1)
    out = jnp.einsum("bsh,hd->bsd", ctx, p["wo"].astype(x.dtype))
    return out, rows


def _perforation_keep_mask(approx: Optional[ApproxSpec], skv: int):
    """Herded KV perforation at decode: the (S,) mask of cache positions
    in kept blocks, or None without perforation."""
    if approx is None or approx.technique != Technique.PERFORATION:
        return None
    import numpy as np
    block = 128
    nblocks = max(skv // block, 1)
    keep_np = np.zeros((skv,), bool)
    for kb in kept_indices(nblocks, approx.perforation):
        keep_np[kb * block:(kb + 1) * block] = True
    keep_np[skv - skv % block:] = True  # tail beyond whole blocks stays
    return jnp.asarray(keep_np)


def decode_step(p, cfg: ModelConfig, x: jnp.ndarray, cache: Dict,
                pos: jnp.ndarray,
                approx: Optional[ApproxSpec] = None) -> Tuple[jnp.ndarray, Dict]:
    """One-token decode: x (B, 1, d) -> (out (B, 1, d), rows).

    `cache` is read, never written: the token attends to the cache's
    positions < pos plus its own key and value, under one f32 softmax
    whose max and sum run over both. `rows` holds the token's cache
    entries at `pos` (each leaf of the cache with a sequence extent of 1,
    in the cache's dtype) for the caller to write (`common.write_rows`).
    Linear in cache length."""
    b = x.shape[0]
    positions = jnp.full((1,), pos, jnp.int32)
    q, k, v = _project_qkv(p, cfg, x, positions)
    if cfg.kv_cache_dtype == "int8":
        return _decode_step_int8(p, cfg, q, k, v, x, cache, pos, approx)
    rows = {"k": k.astype(cache["k"].dtype), "v": v.astype(cache["v"].dtype)}
    ctx = common.decode_attention(
        q, cache["k"], cache["v"], valid_len=pos,
        keep_mask=_perforation_keep_mask(approx, cache["k"].shape[2]),
        k_new=rows["k"], v_new=rows["v"])
    ctx = ctx.transpose(0, 2, 1, 3).reshape(b, 1, -1)
    out = jnp.einsum("bsh,hd->bsd", ctx, p["wo"].astype(x.dtype))
    return out, rows
