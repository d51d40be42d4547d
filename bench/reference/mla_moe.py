"""The latent-attention mixture-of-experts family (DeepSeek-V3 layout,
`model_type: deepseek_v3`: Moonlight), served as one device's share of an
expert-parallel deployment.

A configuration file of this family states the published sizes, with
`n_routed_experts` cut to the experts this device holds and, beside it,
`n_router_experts` (the router's published width) and `expert_offset`
(the first expert held): the device holds experts [expert_offset,
expert_offset + n_routed_experts) of every expert layer, the whole
attention, the dense layers and the shared experts, and the router still
scores all `n_router_experts`. Pairs routed to experts held elsewhere add
nothing here, in the program and in this reference alike.

The plain float32 reference follows the published DeepSeek-V3 modelling
under `jax.default_matmul_precision("highest")`: pre-norm RMSNorm blocks;
latent attention in its expanded form (`q_proj` with no query LoRA,
`kv_a_proj_with_mqa`, `kv_a_layernorm`, `kv_b_proj` split per head into
the no-position key part and the value, RoPE on the rope dims as the
published code applies it: pairs (2j, 2j + 1) gathered into halves, then
rotate-half; softmax scale (nope + rope)^-1/2, full causal softmax); the
leading dense layers' SiLU-gated MLP; the `noaux_tc` router (sigmoid
scores, the correction bias added for the choice only, top-k weights
normalised and scaled) with the held experts computed on every token and
weighted by their routing weight; the shared experts; an untied head. No
cache, no batching tricks, no kernels. It runs layer by layer from the
seed, each layer's weights made, used and dropped.

`precision="fp8"` is the control: every matrix, the router's included,
rounded to float8 (e4m3) with one scale per output column.

Beside the reference the module binds the family's names
(`bench.cells.family`): the field map, the program's parameter tree and
the operation and byte counts, from the published shapes alone.
"""
from __future__ import annotations

import functools
import math
from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np

from bench import weights as W
from bench.reference.dense import _conf_key, quantize_fp8, rms_norm

# configuration keys (published names) -> the program's ModelConfig fields
PROGRAM_FIELDS = {
    "num_hidden_layers": "n_layers",
    "hidden_size": "d_model",
    "num_attention_heads": "n_heads",
    "num_key_value_heads": "n_kv_heads",
    "vocab_size": "vocab_size",
    "rope_theta": "rope_theta",
    "rms_norm_eps": "norm_eps",
    "tie_word_embeddings": "tie_embeddings",
    "torch_dtype": "param_dtype",
    "q_lora_rank": "mla.q_lora_rank",
    "kv_lora_rank": "mla.kv_lora_rank",
    "qk_nope_head_dim": "mla.qk_nope_head_dim",
    "qk_rope_head_dim": "mla.qk_rope_head_dim",
    "v_head_dim": "mla.v_head_dim",
    "intermediate_size": "moe.d_ff_dense",
    "moe_intermediate_size": "moe.d_ff_expert",
    "n_routed_experts": "moe.n_held",
    "n_router_experts": "moe.n_experts",
    "expert_offset": "moe.expert_offset",
    "n_shared_experts": "moe.n_shared_experts",
    "num_experts_per_tok": "moe.experts_per_token",
    "first_k_dense_replace": "moe.n_dense_layers",
    "routed_scaling_factor": "moe.routed_scaling_factor",
}

CORRECTION_BIAS_STD = 0.05
# key streams inside a layer's key
ATTN, MLP, ROUTER, EXPERT, SHARED = 1, 2, 3, 4, 5


def _check(conf: Dict) -> None:
    if conf["q_lora_rank"] is not None:
        raise ValueError("this family has no query LoRA (q_lora_rank null)")
    if (conf["n_group"], conf["topk_group"]) != (1, 1):
        raise ValueError("group-limited routing is not implemented "
                         "(n_group = topk_group = 1 only)")
    if (conf["scoring_func"], conf["topk_method"], conf["norm_topk_prob"]) \
            != ("sigmoid", "noaux_tc", True):
        raise ValueError("only the sigmoid noaux_tc router with normalised "
                         "top-k weights is implemented")
    if conf["tie_word_embeddings"]:
        raise ValueError("this family's head is untied")


def _mla(conf: Dict):
    return (conf["num_attention_heads"], conf["kv_lora_rank"],
            conf["qk_nope_head_dim"], conf["qk_rope_head_dim"],
            conf["v_head_dim"])


def held_ids(conf: Dict) -> np.ndarray:
    return conf["expert_offset"] + np.arange(conf["n_routed_experts"])


# --------------------------------------------------------------------------
# weights from the seed, published layout (matrices as (in, out))
# --------------------------------------------------------------------------

def _mlp(key, d, ff, conf):
    ks = jax.random.split(key, 3)
    return {"gate_proj": W._linear(ks[0], d, ff, conf),
            "up_proj": W._linear(ks[1], d, ff, conf),
            "down_proj": W._linear(ks[2], ff, d, conf)}


def attention_weights(layer_key, conf: Dict) -> Dict:
    d = conf["hidden_size"]
    h, r, nope, rope, vd = _mla(conf)
    ks = jax.random.split(jax.random.fold_in(layer_key, ATTN), 7)
    return {
        "input_layernorm": W._scale(ks[0], d, conf),
        "post_attention_layernorm": W._scale(ks[1], d, conf),
        "q_proj": W._linear(ks[2], d, h * (nope + rope), conf),
        "kv_a_proj_with_mqa": W._linear(ks[3], d, r + rope, conf),
        "kv_a_layernorm": W._scale(ks[4], r, conf),
        "kv_b_proj": W._linear(ks[5], r, h * (nope + vd), conf),
        "o_proj": W._linear(ks[6], h * vd, d, conf),
    }


def dense_layer_weights(seed_key, layer, conf: Dict) -> Dict:
    """A leading dense layer: attention and the SiLU-gated MLP."""
    key = jax.random.fold_in(seed_key, layer)
    w = attention_weights(key, conf)
    w["mlp"] = _mlp(jax.random.fold_in(key, MLP), conf["hidden_size"],
                    conf["intermediate_size"], conf)
    return w


def expert_weights(layer_key, expert, conf: Dict) -> Dict:
    """Routed expert `expert` (its global id) of a layer: the same numbers
    whichever device holds it."""
    key = jax.random.fold_in(jax.random.fold_in(layer_key, EXPERT), expert)
    return _mlp(key, conf["hidden_size"], conf["moe_intermediate_size"],
                conf)


def moe_layer_weights(seed_key, layer, conf: Dict) -> Dict:
    """An expert layer: attention, the router over all `n_router_experts`
    with its correction bias (float32, as published), the held experts
    stacked in id order, and the shared experts as one MLP."""
    d = conf["hidden_size"]
    e = conf["n_router_experts"]
    key = jax.random.fold_in(seed_key, layer)
    w = attention_weights(key, conf)
    kr, kb = jax.random.split(jax.random.fold_in(key, ROUTER))
    w["gate"] = W._linear(kr, d, e, conf)
    w["e_score_correction_bias"] = CORRECTION_BIAS_STD * jax.random.normal(
        kb, (e,), jnp.float32)
    w["experts"] = jax.vmap(lambda i: expert_weights(key, i, conf))(
        jnp.asarray(held_ids(conf)))
    w["shared_experts"] = _mlp(
        jax.random.fold_in(key, SHARED), d,
        conf["moe_intermediate_size"] * conf["n_shared_experts"], conf)
    return w


def _attn_tree(L: Dict, conf: Dict) -> Dict:
    """The program's latent-attention leaves from the published ones
    (stacked over layers). `kv_b_proj` splits per head into W_uk and
    W_uv; RoPE needs no permutation, the program rotates the same pairs."""
    h, r, nope, rope, vd = _mla(conf)
    kv_b = L["kv_b_proj"].reshape(L["kv_b_proj"].shape[:-1]
                                  + (h, nope + vd))
    lead = kv_b.shape[:-2]
    return {
        "w_q": L["q_proj"],
        "w_dkv": L["kv_a_proj_with_mqa"],
        "kv_norm": {"scale": L["kv_a_layernorm"]},
        "w_uk": kv_b[..., :nope].reshape(lead + (h * nope,)),
        "w_uv": kv_b[..., nope:].reshape(lead + (h * vd,)),
        "wo": L["o_proj"],
    }


def _norms(L: Dict) -> Dict:
    return {"ln1": {"scale": L["input_layernorm"]},
            "ln2": {"scale": L["post_attention_layernorm"]}}


def program_params(seed: int, conf: Dict, padded_vocab: int):
    """The program's parameter tree (`repro.models.lm`, moe layout with
    latent attention), made on the device in the served dtype: the
    embedding and head in one jitted call, the layers in others that make
    one layer at a time, so no float32 temporary larger than one layer or
    one vocabulary matrix is held."""
    _check(conf)
    n_dense = conf["first_k_dense_replace"]
    n_layers = conf["num_hidden_layers"]
    v = conf["vocab_size"]
    pad = padded_vocab - v

    def globals_(key):
        g = W.global_weights(key, conf)
        return {"embed": jnp.pad(g["embed_tokens"], ((0, pad), (0, 0))),
                "final_norm": {"scale": g["norm"]},
                "head": jnp.pad(g["lm_head"], ((0, 0), (0, pad)))}

    def dense_(key):
        L = jax.lax.map(lambda i: dense_layer_weights(key, i, conf),
                        jnp.arange(n_dense))
        return dict(_norms(L), attn=_attn_tree(L, conf), ffn={
            "w_gate": L["mlp"]["gate_proj"], "w_up": L["mlp"]["up_proj"],
            "w_down": L["mlp"]["down_proj"]})

    def moe_(key):
        L = jax.lax.map(lambda i: moe_layer_weights(key, i, conf),
                        jnp.arange(n_dense, n_layers))
        ex, sh = L["experts"], L["shared_experts"]
        return dict(_norms(L), attn=_attn_tree(L, conf), moe={
            "router": L["gate"],
            "router_bias": L["e_score_correction_bias"],
            "w_gate": ex["gate_proj"], "w_up": ex["up_proj"],
            "w_down": ex["down_proj"],
            "shared": {"w_gate": sh["gate_proj"], "w_up": sh["up_proj"],
                       "w_down": sh["down_proj"]}})

    # the key is an argument, not a constant: one program serves every seed
    key = W.base_key(seed)
    p = jax.jit(globals_)(key)
    p["dense_blocks"] = jax.jit(dense_)(key)
    p["moe_blocks"] = jax.jit(moe_)(key)
    return p


# --------------------------------------------------------------------------
# operations and bytes, from the published shapes
# --------------------------------------------------------------------------

def _sizes(conf: Dict):
    d = conf["hidden_size"]
    h, r, nope, rope, vd = _mla(conf)
    L = conf["num_hidden_layers"]
    n_dense = conf["first_k_dense_replace"]
    return d, h, r, nope, rope, vd, L, n_dense, L - n_dense


def attention_params(conf: Dict) -> int:
    """Matrix weights of one layer's latent attention."""
    d, h, r, nope, rope, vd, *_ = _sizes(conf)
    return (d * h * (nope + rope) + d * (r + rope) + r * h * (nope + vd)
            + h * vd * d)


def expert_params(conf: Dict) -> int:
    return 3 * conf["hidden_size"] * conf["moe_intermediate_size"]


def routed_rows_per_token(conf: Dict) -> float:
    """Expected (token, expert) pairs per token routed to the held
    experts: k x held / router width."""
    return (conf["num_experts_per_tok"] * conf["n_routed_experts"]
            / conf["n_router_experts"])


def _ffn_token_flops(conf: Dict) -> float:
    """Feed-forward operations of one token through every layer: the
    dense MLPs, the router, the shared experts, and the held experts'
    expected routed rows (never the rows a masked product pads)."""
    d, *_, n_dense, n_moe = _sizes(conf)
    dense = 2 * 3 * d * conf["intermediate_size"]
    moe = (2 * d * conf["n_router_experts"]
           + 2 * expert_params(conf) * conf["n_shared_experts"]
           + 2 * expert_params(conf) * routed_rows_per_token(conf))
    return n_dense * dense + n_moe * moe


def head_flops(conf: Dict) -> int:
    return 2 * conf["hidden_size"] * conf["vocab_size"]


def prefill_flops(conf: Dict, prompt_len: int, batch: int = 1) -> float:
    """A prefill of `batch` prompts in the expanded (published) form: the
    projections and the per-head expansion of every position, causal
    attention's two products at (nope + rope) and v widths, the
    feed-forward work, and the head at the last position only."""
    d, h, r, nope, rope, vd, L, *_ = _sizes(conf)
    t = prompt_len
    per_seq = (t * (2 * L * attention_params(conf) + _ffn_token_flops(conf))
               + L * 2 * h * (nope + rope + vd) * t * (t + 1) // 2
               + head_flops(conf))
    return batch * per_seq


def decode_token_flops(conf: Dict, position: int) -> float:
    """One token's decode at `position` in the absorbed form: the query,
    latent and output projections, W_uk folded into the query and W_uv
    into the context per head, and per key (positions 0..position) the
    score over the latent and the rope key and the context over the
    latent."""
    d, h, r, nope, rope, vd, L, *_ = _sizes(conf)
    proj = (d * h * (nope + rope) + d * (r + rope) + h * nope * r
            + h * r * vd + h * vd * d)
    per_key = h * (r + rope + r)
    return (L * 2 * (proj + per_key * (position + 1))
            + _ffn_token_flops(conf) + head_flops(conf))


def decode_step_flops(conf: Dict, live: int, position: int) -> float:
    """One decode step of `live` lanes that each write `position`."""
    return live * decode_token_flops(conf, position)


def weight_bytes(conf: Dict) -> int:
    """Bytes of every held weight a decode step reads once: attention and
    norms of every layer, the dense MLPs, per expert layer the router, its
    correction bias (float32), the held experts and the shared experts,
    the final norm and the head. The embedding is read only at the
    batch's rows and is left out."""
    d, h, r, *_, L, n_dense, n_moe = _sizes(conf)
    b = jnp.dtype(W.served_dtype(conf)).itemsize
    per_layer = attention_params(conf) + r + 2 * d
    dense = 3 * d * conf["intermediate_size"]
    moe = (d * conf["n_router_experts"]
           + expert_params(conf) * (conf["n_routed_experts"]
                                    + conf["n_shared_experts"]))
    n = L * per_layer + n_dense * dense + n_moe * moe + d * (
        conf["vocab_size"] + 1)
    return n * b + n_moe * 4 * conf["n_router_experts"]


def latent_bytes_per_token(conf: Dict) -> int:
    """Cache bytes of one position: the latent and the rope key of every
    layer, in the served dtype."""
    _, _, r, _, rope, *_ = _sizes(conf)
    return conf["num_hidden_layers"] * (r + rope) * jnp.dtype(
        W.served_dtype(conf)).itemsize


def decode_step_bytes(conf: Dict, live: int, position: int) -> int:
    """Least bytes of one decode step: every held weight once, plus the
    latent rows of each live lane's filled positions 0..position. Padding
    up to the cache's length is never counted."""
    return weight_bytes(conf) + live * (position + 1) * \
        latent_bytes_per_token(conf)


# --------------------------------------------------------------------------
# the reference
# --------------------------------------------------------------------------

def _prepare(w: Dict, precision: str) -> Dict:
    """float32 leaves; under fp8 every matrix (2-D, or a stack of them)
    rounded per output column."""
    def one(a):
        a = a.astype(jnp.float32)
        if precision == "fp8" and a.ndim >= 2:
            return (jax.vmap(quantize_fp8)(a) if a.ndim == 3
                    else quantize_fp8(a))
        return a

    if precision not in ("float32", "fp8"):
        raise ValueError(f"unknown precision {precision!r}")
    return jax.tree.map(one, w)


def rope(x, theta):
    """RoPE over (N, T, heads, rd), positions 0..T-1, as the published
    DeepSeek-V3 code applies it: the pairs (2j, 2j + 1) gathered into
    halves, then rotate-half with frequency j."""
    n, t, nh, rd = x.shape
    x = x.reshape(n, t, nh, rd // 2, 2).swapaxes(-1, -2).reshape(
        n, t, nh, rd)
    inv = 1.0 / (theta ** (jnp.arange(0, rd, 2, dtype=jnp.float32) / rd))
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * inv[None, :]
    cos = jnp.concatenate([jnp.cos(ang)] * 2, -1)[None, :, None, :]
    sin = jnp.concatenate([jnp.sin(ang)] * 2, -1)[None, :, None, :]
    half = rd // 2
    rot = jnp.concatenate([-x[..., half:], x[..., :half]], -1)
    return x * cos + rot * sin


def attention(w: Dict, x, conf: Dict):
    """Latent attention, expanded, over normed x (N, T, d)."""
    n, t, _ = x.shape
    h, r, nope, rd, vd = _mla(conf)
    q = (x @ w["q_proj"]).reshape(n, t, h, nope + rd)
    kv_a = x @ w["kv_a_proj_with_mqa"]
    ckv = rms_norm(kv_a[..., :r], w["kv_a_layernorm"], conf["rms_norm_eps"])
    kv = (ckv @ w["kv_b_proj"]).reshape(n, t, h, nope + vd)
    q_pe = rope(q[..., nope:], conf["rope_theta"])
    k_pe = rope(kv_a[..., r:][:, :, None, :], conf["rope_theta"])
    q = jnp.concatenate([q[..., :nope], q_pe], -1)
    k = jnp.concatenate([kv[..., :nope],
                         jnp.broadcast_to(k_pe, (n, t, h, rd))], -1)
    s = jnp.einsum("nqhd,nkhd->nhqk", q, k) / math.sqrt(nope + rd)
    causal = jnp.tril(jnp.ones((t, t), bool))
    s = jnp.where(causal[None, None], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    ctx = jnp.einsum("nhqk,nkhd->nqhd", p, kv[..., nope:])
    return ctx.reshape(n, t, h * vd) @ w["o_proj"]


def mlp(w: Dict, x):
    return (jax.nn.silu(x @ w["gate_proj"]) * (x @ w["up_proj"])) \
        @ w["down_proj"]


def router(w: Dict, x, conf: Dict):
    """`noaux_tc` top-k over all experts: (weights, ids), each (..., k)."""
    scores = jax.nn.sigmoid(x @ w["gate"])
    _, ids = jax.lax.top_k(scores + w["e_score_correction_bias"],
                           conf["num_experts_per_tok"])
    wt = jnp.take_along_axis(scores, ids, axis=-1)
    wt = wt / (jnp.sum(wt, axis=-1, keepdims=True) + 1e-20)
    return wt * conf["routed_scaling_factor"], ids


def moe(w: Dict, x, conf: Dict):
    """The expert layer over normed x: each held expert on every token,
    weighted by the token's routing weight for it (0 unless routed to it),
    plus the shared experts."""
    wt, ids = router(w, x, conf)
    out = mlp(w["shared_experts"], x)
    for j, e in enumerate(held_ids(conf)):
        gate = jnp.sum(jnp.where(ids == e, wt, 0.0), axis=-1)
        ex = jax.tree.map(lambda a: a[j], w["experts"])
        out = out + gate[..., None] * mlp(ex, x)
    return out


def decoder_layer(w: Dict, h, conf: Dict, ffn):
    eps = conf["rms_norm_eps"]
    h = h + attention(w, rms_norm(h, w["input_layernorm"], eps), conf)
    return h + ffn(rms_norm(h, w["post_attention_layernorm"], eps))


@functools.partial(jax.jit, static_argnames=("ck", "precision"))
def _dense_step(key, layer, h, ck, precision):
    conf = dict(ck)
    with jax.default_matmul_precision("highest"):
        w = _prepare(dense_layer_weights(key, layer, conf), precision)
        return decoder_layer(w, h, conf, lambda x: mlp(w["mlp"], x))


@functools.partial(jax.jit, static_argnames=("ck", "precision"))
def _moe_step(key, layer, h, ck, precision):
    conf = dict(ck)
    with jax.default_matmul_precision("highest"):
        w = _prepare(moe_layer_weights(key, layer, conf), precision)
        return decoder_layer(w, h, conf, lambda x: moe(w, x, conf))


@functools.partial(jax.jit, static_argnames=("ck",))
def _embed(key, tokens, ck):
    g = W.global_weights(key, dict(ck))
    return jnp.take(g["embed_tokens"].astype(jnp.float32), tokens, axis=0)


@functools.partial(jax.jit, static_argnames=("ck", "precision"))
def _head(key, h, ck, precision):
    conf = dict(ck)
    with jax.default_matmul_precision("highest"):
        g = W.global_weights(key, conf)
        head = _prepare({"lm_head": g["lm_head"]}, precision)["lm_head"]
        x = rms_norm(h, g["norm"].astype(jnp.float32), conf["rms_norm_eps"])
        return x @ head


def final_hidden(conf: Dict, seed: int, tokens: np.ndarray,
                 precision: str = "float32"):
    """Hidden states (N, T, d) of the last layer, before the final norm,
    for token rows (N, T)."""
    _check(conf)
    ck = _conf_key(conf)
    key = W.base_key(seed)
    h = _embed(key, jnp.asarray(tokens, jnp.int32), ck)
    for i in range(conf["num_hidden_layers"]):
        step = _dense_step if i < conf["first_k_dense_replace"] else _moe_step
        h = step(key, jnp.int32(i), h, ck, precision)
    return h


def head_logits(conf: Dict, seed: int, rows, precision: str = "float32"):
    """Logits (M, vocab) for final hidden rows (M, d)."""
    return _head(W.base_key(seed), rows, _conf_key(conf), precision)
