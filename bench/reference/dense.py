"""The plain float32 reference of a dense decoder (Qwen2 / Qwen3 layout).

Straightforward `jax.numpy` under `jax.default_matmul_precision("highest")`:
pre-norm residual blocks, RMSNorm, rotate-half RoPE, full causal softmax
attention with grouped key/value heads, an optional bias on the query, key
and value projections (Qwen1.5), an optional RMSNorm over each query and key
head before RoPE (Qwen3), a SiLU-gated MLP, and a tied or separate output
head. No cache, no batching tricks, no kernels.

It runs layer by layer: each layer's weights are made from the seed on
their own (`bench.weights.layer_weights`), cast to float32, applied to the
hidden states of every sampled sequence, and dropped, so a 4B model never
holds more than one float32 layer beside its activations.

`precision="fp8"` is the control: every matrix of the model is rounded to
float8 (e4m3) with one scale per output column before use, the step a
weight-quantising change would take below the published bfloat16.

The module is the dense family (`bench.cells.family`): beside the
reference it binds the program's field map, the program's parameter tree
(`bench/weights.py`) and the operation and byte counts (`bench/counts.py`)
under the family's names.
"""
from __future__ import annotations

import functools
import math
from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np

from bench import counts, weights as W

# configuration keys (published names) -> the program's ModelConfig fields
PROGRAM_FIELDS = {
    "num_hidden_layers": "n_layers",
    "hidden_size": "d_model",
    "num_attention_heads": "n_heads",
    "num_key_value_heads": "n_kv_heads",
    "intermediate_size": "d_ff",
    "vocab_size": "vocab_size",
    "head_dim": "head_dim",
    "rope_theta": "rope_theta",
    "rms_norm_eps": "norm_eps",
    "tie_word_embeddings": "tie_embeddings",
    "attention_bias": "qkv_bias",
    "qk_norm": "qk_norm",
    "torch_dtype": "param_dtype",
}

program_params = W.program_params
prefill_flops = counts.prefill_flops
decode_step_flops = counts.decode_step_flops
decode_step_bytes = counts.decode_step_bytes

_MATRICES = ("q_proj", "k_proj", "v_proj", "o_proj", "gate_proj", "up_proj",
             "down_proj", "lm_head")


def quantize_fp8(w):
    """Round a (in, out) matrix to float8 e4m3 with a per-column scale."""
    amax = jnp.max(jnp.abs(w), axis=0, keepdims=True)
    scale = jnp.maximum(amax, 1e-30) / 448.0
    return (w / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def _prepare(ws: Dict, precision: str) -> Dict:
    out = {k: v.astype(jnp.float32) for k, v in ws.items()}
    if precision == "fp8":
        for k in _MATRICES:
            if k in out:
                out[k] = quantize_fp8(out[k])
    elif precision != "float32":
        raise ValueError(f"unknown precision {precision!r}")
    return out


def rms_norm(x, scale, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * scale


def rope(x, theta):
    """Rotate-half RoPE over (N, T, heads, hd), positions 0..T-1."""
    t, hd = x.shape[1], x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * inv[None, :]
    cos = jnp.concatenate([jnp.cos(ang)] * 2, -1)[None, :, None, :]
    sin = jnp.concatenate([jnp.sin(ang)] * 2, -1)[None, :, None, :]
    half = hd // 2
    rot = jnp.concatenate([-x[..., half:], x[..., :half]], -1)
    return x * cos + rot * sin


def decoder_layer(w: Dict, h, conf: Dict):
    """One pre-norm block over h (N, T, d), float32."""
    n, t, d = h.shape
    nh, nkv = conf["num_attention_heads"], conf["num_key_value_heads"]
    hd = conf.get("head_dim") or d // nh
    eps = conf["rms_norm_eps"]
    x = rms_norm(h, w["input_layernorm"], eps)
    q, k, v = x @ w["q_proj"], x @ w["k_proj"], x @ w["v_proj"]
    if conf["attention_bias"]:
        q, k, v = q + w["q_bias"], k + w["k_bias"], v + w["v_bias"]
    q = q.reshape(n, t, nh, hd)
    k = k.reshape(n, t, nkv, hd)
    v = v.reshape(n, t, nkv, hd)
    if conf["qk_norm"]:
        q = rms_norm(q, w["q_norm"], eps)
        k = rms_norm(k, w["k_norm"], eps)
    q, k = rope(q, conf["rope_theta"]), rope(k, conf["rope_theta"])
    k = jnp.repeat(k, nh // nkv, axis=2)
    v = jnp.repeat(v, nh // nkv, axis=2)
    s = jnp.einsum("nqhd,nkhd->nhqk", q, k) / math.sqrt(hd)
    causal = jnp.tril(jnp.ones((t, t), bool))
    s = jnp.where(causal[None, None], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    ctx = jnp.einsum("nhqk,nkhd->nqhd", p, v).reshape(n, t, nh * hd)
    h = h + ctx @ w["o_proj"]
    x = rms_norm(h, w["post_attention_layernorm"], eps)
    return h + (jax.nn.silu(x @ w["gate_proj"]) * (x @ w["up_proj"])) \
        @ w["down_proj"]


def _conf_key(conf: Dict):
    """A hashable view of the configuration for jit's static arguments."""
    return tuple(sorted((k, v) for k, v in conf.items()
                        if isinstance(v, (int, float, str, bool))))


@functools.partial(jax.jit, static_argnames=("ck", "precision"))
def _layer_step(key, layer, h, ck, precision):
    conf = dict(ck)
    with jax.default_matmul_precision("highest"):
        w = _prepare(W.layer_weights(key, layer, conf), precision)
        return decoder_layer(w, h, conf)


@functools.partial(jax.jit, static_argnames=("ck",))
def _embed(key, tokens, ck):
    g = W.global_weights(key, dict(ck))
    return jnp.take(g["embed_tokens"].astype(jnp.float32), tokens, axis=0)


@functools.partial(jax.jit, static_argnames=("ck", "precision"))
def _head(key, h, ck, precision):
    """Final norm and output head over rows h (M, d) -> logits (M, V)."""
    conf = dict(ck)
    with jax.default_matmul_precision("highest"):
        g = W.global_weights(key, conf)
        head = (g["embed_tokens"].T if conf["tie_word_embeddings"]
                else g["lm_head"])
        head = _prepare({"lm_head": head}, precision)["lm_head"]
        x = rms_norm(h, g["norm"].astype(jnp.float32), conf["rms_norm_eps"])
        return x @ head


def final_hidden(conf: Dict, seed: int, tokens: np.ndarray,
                 precision: str = "float32"):
    """Hidden states (N, T, d) of the last layer, before the final norm,
    for token rows (N, T)."""
    ck = _conf_key(conf)
    key = W.base_key(seed)
    h = _embed(key, jnp.asarray(tokens, jnp.int32), ck)
    for i in range(conf["num_hidden_layers"]):
        h = _layer_step(key, jnp.int32(i), h, ck, precision)
    return h


def head_logits(conf: Dict, seed: int, rows, precision: str = "float32"):
    """Logits (M, vocab) for final hidden rows (M, d)."""
    return _head(W.base_key(seed), rows, _conf_key(conf), precision)
