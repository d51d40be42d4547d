"""Operations and bytes of a dense decoder, from the configuration's
published shapes alone (never from the compiled program, so a change to
the program cannot change the yardstick). The dense family
(`bench/reference/dense.py`) binds them; readers reach them through
`bench.cells.family`.

Counted are the matrix products (2 operations per multiply-add) and
causal attention's two products; norms, RoPE, softmax and the embedding
lookup are left out. A token at position p (0-based) attends to p + 1
keys.
"""
from __future__ import annotations

from typing import Dict

BYTES = {"bfloat16": 2, "float32": 4}


def _dims(conf: Dict):
    d = conf["hidden_size"]
    nh = conf["num_attention_heads"]
    hd = conf.get("head_dim") or d // nh
    return d, nh, conf["num_key_value_heads"], hd


def layer_matrix_params(conf: Dict) -> int:
    """Weights of one layer's matrices: q, k, v, o and the gated MLP."""
    d, nh, nkv, hd = _dims(conf)
    ff = conf["intermediate_size"]
    return d * (nh + 2 * nkv) * hd + nh * hd * d + 3 * d * ff


def head_params(conf: Dict) -> int:
    return conf["hidden_size"] * conf["vocab_size"]


def weight_bytes(conf: Dict) -> int:
    """Bytes of every weight a decode step reads once: the layers'
    matrices, biases and norm scales, the final norm and the output head.
    The embedding table is read only at the batch's rows and is left out
    (with tied embeddings the head is that table, counted once)."""
    d, nh, nkv, hd = _dims(conf)
    per_layer = layer_matrix_params(conf) + 2 * d
    if conf["attention_bias"]:
        per_layer += (nh + 2 * nkv) * hd
    if conf["qk_norm"]:
        per_layer += 2 * hd
    n = conf["num_hidden_layers"] * per_layer + head_params(conf) + d
    return n * BYTES[conf["torch_dtype"]]


def kv_bytes_per_token(conf: Dict) -> int:
    """Cache bytes of one position: keys and values of every layer, in
    the served dtype."""
    d, nh, nkv, hd = _dims(conf)
    return conf["num_hidden_layers"] * 2 * nkv * hd * BYTES[
        conf["torch_dtype"]]


def token_flops(conf: Dict, position: int) -> int:
    """Operations of one token's forward pass at `position`, head
    included."""
    d, nh, nkv, hd = _dims(conf)
    mats = conf["num_hidden_layers"] * layer_matrix_params(conf) \
        + head_params(conf)
    attn = conf["num_hidden_layers"] * 4 * nh * hd * (position + 1)
    return 2 * mats + attn


def prefill_flops(conf: Dict, prompt_len: int, batch: int = 1) -> int:
    """A prefill of `batch` prompts of `prompt_len` tokens. Only the last
    position goes through the head, as the program's prefill does."""
    d, nh, nkv, hd = _dims(conf)
    L = conf["num_hidden_layers"]
    per_seq = (2 * L * layer_matrix_params(conf) * prompt_len
               + L * 4 * nh * hd * prompt_len * (prompt_len + 1) // 2
               + 2 * head_params(conf))
    return batch * per_seq


def decode_step_flops(conf: Dict, live: int, position: int) -> int:
    """One decode step of `live` lanes that each write `position`."""
    return live * token_flops(conf, position)


def decode_step_bytes(conf: Dict, live: int, position: int) -> int:
    """Least bytes of one decode step: every weight once, plus the keys
    and values of each live lane's filled positions 0..position (the one
    at `position` is written, the rest read). Padding up to the cache's
    length is never counted."""
    return weight_bytes(conf) + live * (position + 1) * kv_bytes_per_token(
        conf)
