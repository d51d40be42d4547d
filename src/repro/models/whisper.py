"""Whisper-large-v3 backbone: encoder-decoder transformer.

Per the brief the conv frontend is a STUB: `input_specs()` provides
precomputed log-mel frame embeddings (B, S_enc, d_model); the encoder runs
bidirectional attention over them, the decoder runs causal self-attention +
cross-attention. Decode shapes exercise the decoder with a KV cache against
a precomputed encoder memory.
"""
from __future__ import annotations

from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from . import attention, common, lm, mlp


def _dtype(name: str):
    return {"float32": jnp.float32, "bfloat16": jnp.bfloat16}[name]


def _init_enc_block(key, cfg: ModelConfig, dtype):
    k1, k2 = jax.random.split(key)
    return {
        "ln1": common.norm_params("ln", cfg.d_model, dtype),
        "attn": attention.init_params(k1, cfg, dtype),
        "ln2": common.norm_params("ln", cfg.d_model, dtype),
        "ffn": mlp.init_params(k2, cfg.d_model, cfg.d_ff, "gelu", dtype),
    }


def _init_dec_block(key, cfg: ModelConfig, dtype):
    k1, k2, k3 = jax.random.split(key, 3)
    return {
        "ln1": common.norm_params("ln", cfg.d_model, dtype),
        "self_attn": attention.init_params(k1, cfg, dtype),
        "ln_x": common.norm_params("ln", cfg.d_model, dtype),
        "cross_attn": attention.init_params(k2, cfg, dtype),
        "ln2": common.norm_params("ln", cfg.d_model, dtype),
        "ffn": mlp.init_params(k3, cfg.d_model, cfg.d_ff, "gelu", dtype),
    }


def _enc_block(p, cfg, x, positions):
    h = common.layernorm(p["ln1"], x, cfg.norm_eps)
    x = x + attention.forward(p["attn"], cfg, h, positions, causal=False,
                              approx=cfg.approx_attention)
    h = common.layernorm(p["ln2"], x, cfg.norm_eps)
    return x + mlp.forward(p["ffn"], cfg, h, "gelu", approx=cfg.approx_ffn)


def _cross_attention(p, cfg, x, memory, positions_q):
    """Queries from decoder x; K/V from encoder memory (no causal mask)."""
    b, s, d = x.shape
    hd = cfg.resolved_head_dim
    q = jnp.einsum("bsd,dh->bsh", x, p["wq"].astype(x.dtype))
    k = jnp.einsum("bsd,dh->bsh", memory, p["wk"].astype(x.dtype))
    v = jnp.einsum("bsd,dh->bsh", memory, p["wv"].astype(x.dtype))
    q = q.reshape(b, s, cfg.n_heads, hd).transpose(0, 2, 1, 3)
    k = k.reshape(b, memory.shape[1], cfg.n_kv_heads, hd).transpose(0, 2, 1, 3)
    v = v.reshape(b, memory.shape[1], cfg.n_kv_heads, hd).transpose(0, 2, 1, 3)
    ctx = common.chunked_attention(q, k, v, causal=False)
    ctx = ctx.transpose(0, 2, 1, 3).reshape(b, s, -1)
    return jnp.einsum("bsh,hd->bsd", ctx, p["wo"].astype(x.dtype))


def _dec_block(p, cfg, x, memory, positions):
    h = common.layernorm(p["ln1"], x, cfg.norm_eps)
    x = x + attention.forward(p["self_attn"], cfg, h, positions, causal=True,
                              approx=cfg.approx_attention)
    h = common.layernorm(p["ln_x"], x, cfg.norm_eps)
    x = x + _cross_attention(p["cross_attn"], cfg, h, memory, positions)
    h = common.layernorm(p["ln2"], x, cfg.norm_eps)
    return x + mlp.forward(p["ffn"], cfg, h, "gelu", approx=cfg.approx_ffn)


def build(cfg: ModelConfig) -> "lm.Model":
    pdt = _dtype(cfg.param_dtype)
    cdt = _dtype(cfg.compute_dtype)
    L = cfg.n_layers

    def init(key):
        k1, k2, k3, k4 = jax.random.split(key, 4)
        return {
            "embed": common.embed_init(k1, (cfg.padded_vocab_size, cfg.d_model), pdt),
            "enc_blocks": lm._stack_init(
                lambda k: _init_enc_block(k, cfg, pdt), k2, L),
            "dec_blocks": lm._stack_init(
                lambda k: _init_dec_block(k, cfg, pdt), k3, L),
            "enc_norm": common.norm_params("ln", cfg.d_model, pdt),
            "dec_norm": common.norm_params("ln", cfg.d_model, pdt),
            "head": common.dense_init(k4, (cfg.d_model, cfg.padded_vocab_size),
                                      dtype=pdt),
        }

    def encode(params, frames):
        x = frames.astype(cdt) + common.sinusoidal_positions(
            frames.shape[1], cfg.d_model).astype(cdt)[None]
        positions = jnp.arange(x.shape[1])

        def body(h, layer_p):
            return _enc_block(layer_p, cfg, h, positions), None

        body_fn = jax.checkpoint(body) if cfg.remat else body
        x, _ = common.scan_layers(cfg.unroll_layers, body_fn, x,
                                  params["enc_blocks"])
        return common.layernorm(params["enc_norm"], x, cfg.norm_eps)

    def hidden(params, batch):
        memory = encode(params, batch["frames"])
        x = jnp.take(params["embed"], batch["tokens"], axis=0).astype(cdt)
        positions = jnp.arange(x.shape[1])

        def body(h, layer_p):
            return _dec_block(layer_p, cfg, h, memory, positions), None

        body_fn = jax.checkpoint(body) if cfg.remat else body
        x, _ = common.scan_layers(cfg.unroll_layers, body_fn, x,
                                  params["dec_blocks"])
        return common.layernorm(params["dec_norm"], x, cfg.norm_eps)

    def loss(params, batch):
        x = hidden(params, batch)
        total, count = lm.chunked_xent(x, params["head"], batch["labels"])
        out = total / jnp.maximum(count, 1.0)
        return out, {"xent": out}

    def init_cache(batch_size: int, max_len: int):
        return {
            "self": jax.vmap(
                lambda _: attention.init_cache(cfg, batch_size, max_len, cdt)
            )(jnp.arange(L)),
            # encoder memory is computed at prefill and kept
            "memory": jnp.zeros((batch_size, cfg.max_source_positions,
                                 cfg.d_model), cdt),
        }

    def prefill(params, batch):
        memory = encode(params, batch["frames"])
        x = jnp.take(params["embed"], batch["tokens"], axis=0).astype(cdt)
        cache = init_cache(x.shape[0], batch["max_len"])
        cache["memory"] = jnp.zeros_like(cache["memory"]) \
            .at[:, :memory.shape[1]].set(memory)
        positions = jnp.arange(x.shape[1])

        def body(h, inp):
            layer_p, layer_c = inp
            hh = common.layernorm(layer_p["ln1"], h, cfg.norm_eps)
            out, new_c = attention.prefill(layer_p["self_attn"], cfg, hh,
                                           layer_c)
            h = h + out
            hh = common.layernorm(layer_p["ln_x"], h, cfg.norm_eps)
            h = h + _cross_attention(layer_p["cross_attn"], cfg, hh, memory,
                                     positions)
            hh = common.layernorm(layer_p["ln2"], h, cfg.norm_eps)
            h = h + mlp.forward(layer_p["ffn"], cfg, hh, "gelu")
            return h, new_c

        x, new_self = common.scan_layers(cfg.unroll_layers, body, x,
                                         (params["dec_blocks"],
                                          cache["self"]))
        cache["self"] = new_self
        x = common.layernorm(params["dec_norm"], x, cfg.norm_eps)
        logits = jnp.einsum("bd,dv->bv", x[:, -1], params["head"].astype(cdt))
        return logits.astype(jnp.float32), cache

    def decode_step(params, cache, tokens, pos):
        x = jnp.take(params["embed"], tokens[:, None], axis=0).astype(cdt)
        memory = cache["memory"].astype(cdt)
        positions = jnp.full((1,), pos, jnp.int32)

        def body(h, inp):
            layer_p, layer_c = inp
            hh = common.layernorm(layer_p["ln1"], h, cfg.norm_eps)
            out, rows = attention.decode_step(
                layer_p["self_attn"], cfg, hh, layer_c, pos,
                approx=cfg.approx_decode)
            h = h + out
            hh = common.layernorm(layer_p["ln_x"], h, cfg.norm_eps)
            h = h + _cross_attention(layer_p["cross_attn"], cfg, hh, memory,
                                     positions)
            hh = common.layernorm(layer_p["ln2"], h, cfg.norm_eps)
            h = h + mlp.forward(layer_p["ffn"], cfg, hh, "gelu")
            return h, rows

        x, rows = common.scan_layers(cfg.unroll_layers, body, x,
                                     (params["dec_blocks"], cache["self"]))
        new_cache = dict(cache)
        new_cache["self"] = common.write_rows(cache["self"], rows, pos)
        x = common.layernorm(params["dec_norm"], x, cfg.norm_eps)
        logits = jnp.einsum("bd,dv->bv", x[:, 0], params["head"].astype(cdt))
        return logits.astype(jnp.float32), new_cache

    return lm.Model(cfg=cfg, init=init, hidden=hidden, loss=loss,
                    init_cache=init_cache, prefill=prefill,
                    decode_step=decode_step)
