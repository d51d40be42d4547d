"""Random weights of a dense decoder, made from the seed.

The weights are the benchmark's, not the program's: the reference
(`bench/reference/`) makes the same numbers again layer by layer, so it
takes nothing from the program. `layer_weights` and `global_weights` give
the published layout (Hugging Face names, matrices as (in, out), RoPE in
the rotate-half layout) in the served dtype; the reference casts them to
float32. `program_params` builds the program's parameter tree from them in one
jitted call on the device, in the served dtype.

Seeds may exceed 32 bits: `base_key` folds the high word in.
"""
from __future__ import annotations

import math
from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np

EMBED_STD = 0.02
NORM_STD = 0.05      # norm scales are 1 + N(0, NORM_STD)
BIAS_STD = 0.1
GLOBAL_STREAM = 0x7FFFFFFF   # key stream of the non-layer weights


def base_key(seed: int):
    seed = int(seed)
    if seed < 0:
        raise ValueError(f"seed must be >= 0, got {seed}")
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, seed >> 32)


def served_dtype(conf: Dict):
    return {"bfloat16": jnp.bfloat16, "float32": jnp.float32}[
        conf["torch_dtype"]]


def _dims(conf: Dict):
    d = conf["hidden_size"]
    hd = conf.get("head_dim") or d // conf["num_attention_heads"]
    return d, hd, conf["num_attention_heads"], conf["num_key_value_heads"]


def _served(x, conf):
    return x.astype(served_dtype(conf))


def _linear(key, n_in, n_out, conf):
    w = jax.random.normal(key, (n_in, n_out), jnp.float32) / math.sqrt(n_in)
    return _served(w, conf)


def _scale(key, n, conf):
    return _served(1.0 + NORM_STD * jax.random.normal(key, (n,)), conf)


def layer_weights(seed_key, layer, conf: Dict) -> Dict[str, jnp.ndarray]:
    """One decoder layer's weights in the served dtype, published layout.
    `layer` may be traced (the program's weights vmap over it)."""
    d, hd, nh, nkv = _dims(conf)
    ff = conf["intermediate_size"]
    ks = jax.random.split(jax.random.fold_in(seed_key, layer), 13)
    w = {
        "input_layernorm": _scale(ks[0], d, conf),
        "post_attention_layernorm": _scale(ks[1], d, conf),
        "q_proj": _linear(ks[2], d, nh * hd, conf),
        "k_proj": _linear(ks[3], d, nkv * hd, conf),
        "v_proj": _linear(ks[4], d, nkv * hd, conf),
        "o_proj": _linear(ks[5], nh * hd, d, conf),
        "gate_proj": _linear(ks[6], d, ff, conf),
        "up_proj": _linear(ks[7], d, ff, conf),
        "down_proj": _linear(ks[8], ff, d, conf),
    }
    if conf["attention_bias"]:
        for name, k, n in (("q_bias", ks[9], nh * hd),
                           ("k_bias", ks[10], nkv * hd),
                           ("v_bias", ks[11], nkv * hd)):
            w[name] = _served(BIAS_STD * jax.random.normal(k, (n,)), conf)
    if conf["qk_norm"]:
        kq, kk = jax.random.split(ks[12])
        w["q_norm"] = _scale(kq, hd, conf)
        w["k_norm"] = _scale(kk, hd, conf)
    return w


def global_weights(seed_key, conf: Dict) -> Dict[str, jnp.ndarray]:
    """Embedding, final norm and (untied) output head, served dtype."""
    d = conf["hidden_size"]
    v = conf["vocab_size"]
    k_e, k_n, k_h = jax.random.split(
        jax.random.fold_in(seed_key, GLOBAL_STREAM), 3)
    w = {
        "embed_tokens": _served(
            EMBED_STD * jax.random.normal(k_e, (v, d), jnp.float32), conf),
        "norm": _scale(k_n, d, conf),
    }
    if not conf["tie_word_embeddings"]:
        w["lm_head"] = _linear(k_h, d, v, conf)
    return w


def rope_permutation(hd: int) -> np.ndarray:
    """Column order that turns rotate-half RoPE (pairs i, i + hd/2) into
    the program's interleaved RoPE (pairs 2j, 2j + 1)."""
    half = hd // 2
    perm = np.empty(hd, np.int64)
    perm[0::2] = np.arange(half)
    perm[1::2] = np.arange(half) + half
    return perm


def _permute_heads(x, n_heads, hd, perm):
    """Permute the head-dim columns of every head of the last axis."""
    lead = x.shape[:-1]
    x = x.reshape(lead + (n_heads, hd))[..., perm]
    return x.reshape(lead + (n_heads * hd,))


def program_params(seed: int, conf: Dict, padded_vocab: int):
    """The program's parameter tree (`repro.models.lm` dense layout), made
    on the device in one jitted call, in the served dtype. The padded
    vocabulary rows are zero."""
    _, hd, nh, nkv = _dims(conf)
    n_layers = conf["num_hidden_layers"]
    v = conf["vocab_size"]
    perm = rope_permutation(hd)

    def make(key):
        g = global_weights(key, conf)
        L = jax.vmap(lambda i: layer_weights(key, i, conf))(
            jnp.arange(n_layers))
        attn = {
            "wq": _permute_heads(L["q_proj"], nh, hd, perm),
            "wk": _permute_heads(L["k_proj"], nkv, hd, perm),
            "wv": L["v_proj"],
            "wo": L["o_proj"],
        }
        if conf["attention_bias"]:
            attn["bq"] = _permute_heads(L["q_bias"], nh, hd, perm)
            attn["bk"] = _permute_heads(L["k_bias"], nkv, hd, perm)
            attn["bv"] = L["v_bias"]
        if conf["qk_norm"]:
            attn["q_norm"] = {"scale": L["q_norm"][:, perm]}
            attn["k_norm"] = {"scale": L["k_norm"][:, perm]}
        pad = padded_vocab - v
        p = {
            "embed": jnp.pad(g["embed_tokens"], ((0, pad), (0, 0))),
            "final_norm": {"scale": g["norm"]},
            "dense_blocks": {
                "ln1": {"scale": L["input_layernorm"]},
                "ln2": {"scale": L["post_attention_layernorm"]},
                "attn": attn,
                "ffn": {"w_gate": L["gate_proj"], "w_up": L["up_proj"],
                        "w_down": L["down_proj"]},
            },
        }
        if not conf["tie_word_embeddings"]:
            p["head"] = jnp.pad(g["lm_head"], ((0, 0), (0, pad)))
        return p

    # the key is an argument, not a constant: one program serves every seed
    return jax.jit(make)(base_key(seed))
