"""Decoder blocks: (pre-norm attention + FFN/MoE residual) and the zamba2
hybrid grouping. All block params are built to STACK on a leading layer axis
so the layer loop is a lax.scan (compile-time O(1) in depth).
"""
from __future__ import annotations

import contextlib
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.core.types import ApproxSpec
from . import attention, common, mamba2, mla, mlp, moe


# ----------------------------------------------------------------------------
# standard decoder block (dense / vlm / moe)
# ----------------------------------------------------------------------------

def init_block(key, cfg: ModelConfig, dtype, use_moe: bool) -> Dict:
    k1, k2, k3, k4 = jax.random.split(key, 4)
    p = {
        "ln1": common.norm_params(cfg.norm, cfg.d_model, dtype),
        "ln2": common.norm_params(cfg.norm, cfg.d_model, dtype),
    }
    if cfg.use_mla:
        p["attn"] = mla.init_params(k1, cfg, dtype)
    else:
        p["attn"] = attention.init_params(k1, cfg, dtype)
    if use_moe:
        p["moe"] = moe.init_params(k2, cfg, dtype)
    else:
        dff = cfg.moe.d_ff_dense if (cfg.moe and cfg.moe.d_ff_dense) else cfg.d_ff
        p["ffn"] = mlp.init_params(k2, cfg.d_model, dff, cfg.mlp, dtype)
    return p


def _pin_residual(x, cfg: ModelConfig):
    """Canonical residual-stream layout (section Perf cell B2): batch over the
    data axes, d_model REPLICATED over model. Without this pin XLA may defer
    the row-parallel reduction and contract the next matmul over a sharded
    d_model, all-reducing (B,S,d_ff)-sized partials instead of (B,S,d).

    Only applied where XLA's default goes pathological (FSDP-sharded weights
    / MoE dispatch); for plain dense TP the unpinned schedule measured
    slightly better (section Perf C1) and the pin is skipped."""
    if not cfg.fsdp:
        return x
    return common.shard_hint(x, common.data_axes_hint(), None, None)


def _attn_scope(cfg: ModelConfig):
    """The `mla` name scope around latent attention (it reaches the
    compiled HLO's op metadata); dense attention keeps its names."""
    return jax.named_scope("mla") if cfg.use_mla else contextlib.nullcontext()


def block_forward(p: Dict, cfg: ModelConfig, x: jnp.ndarray,
                  positions: jnp.ndarray, use_moe: bool,
                  approx_attn: Optional[ApproxSpec] = None,
                  approx_ffn: Optional[ApproxSpec] = None,
                  causal: bool = True) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Returns (x, aux_loss)."""
    x = _pin_residual(x, cfg)
    h = common.apply_norm(cfg.norm, p["ln1"], x, cfg.norm_eps)
    with _attn_scope(cfg):
        attn_mod = mla if cfg.use_mla else attention
        out = attn_mod.forward(p["attn"], cfg, h, positions, causal=causal,
                               approx=approx_attn)
    x = _pin_residual(x + out, cfg)
    h = common.apply_norm(cfg.norm, p["ln2"], x, cfg.norm_eps)
    aux = jnp.float32(0.0)
    if use_moe:
        out, aux, _ = moe.forward(p["moe"], cfg, h, approx=approx_ffn)
        x = _pin_residual(x + out, cfg)
    else:
        x = _pin_residual(
            x + mlp.forward(p["ffn"], cfg, h, cfg.mlp, approx=approx_ffn),
            cfg)
    return x, aux


def block_prefill(p: Dict, cfg: ModelConfig, x, cache, use_moe: bool,
                  approx_attn=None, approx_ffn=None):
    h = common.apply_norm(cfg.norm, p["ln1"], x, cfg.norm_eps)
    with _attn_scope(cfg):
        attn_mod = mla if cfg.use_mla else attention
        out, cache = attn_mod.prefill(p["attn"], cfg, h, cache,
                                      approx=approx_attn)
    x = x + out
    h = common.apply_norm(cfg.norm, p["ln2"], x, cfg.norm_eps)
    if use_moe:
        out, _, _ = moe.forward(p["moe"], cfg, h, approx=approx_ffn)
        x = x + out
    else:
        x = x + mlp.forward(p["ffn"], cfg, h, cfg.mlp, approx=approx_ffn)
    return x, cache


def block_decode(p: Dict, cfg: ModelConfig, x, cache, pos, use_moe: bool,
                 approx_attn=None, approx_ffn=None):
    """One-token decode of a block: (x, rows, routed). The layer cache is
    read only; `rows` are its new entries at `pos` (see
    `attention.decode_step`); `routed` are a dropless expert layer's rows
    routed to each held expert (`moe.dropless_forward`), else None."""
    h = common.apply_norm(cfg.norm, p["ln1"], x, cfg.norm_eps)
    with _attn_scope(cfg):
        attn_mod = mla if cfg.use_mla else attention
        out, rows = attn_mod.decode_step(p["attn"], cfg, h, cache, pos,
                                         approx=approx_attn)
    x = x + out
    h = common.apply_norm(cfg.norm, p["ln2"], x, cfg.norm_eps)
    routed = None
    if use_moe:
        out, _, routed = moe.forward(p["moe"], cfg, h, approx=approx_ffn)
        x = x + out
    else:
        x = x + mlp.forward(p["ffn"], cfg, h, cfg.mlp, approx=approx_ffn)
    return x, rows, routed


def init_block_cache(cfg: ModelConfig, batch: int, max_len: int, dtype) -> Dict:
    if cfg.use_mla:
        return mla.init_cache(cfg, batch, max_len, dtype)
    return attention.init_cache(cfg, batch, max_len, dtype)


# ----------------------------------------------------------------------------
# zamba2 hybrid: groups of (attn_period-1) mamba layers + 1 SHARED attn block
# ----------------------------------------------------------------------------

def hybrid_layout(cfg: ModelConfig) -> Tuple[int, int, int]:
    """(n_groups, mamba_per_group, n_tail_mamba): n_layers =
    n_groups*(mamba_per_group+1) + tail; shared attn applied once per group."""
    period = cfg.hybrid.attn_period
    n_groups = cfg.n_layers // period
    mamba_per_group = period - 1
    tail = cfg.n_layers - n_groups * period
    return n_groups, mamba_per_group, tail


def init_hybrid(key, cfg: ModelConfig, dtype) -> Dict:
    n_groups, mpg, tail = hybrid_layout(cfg)
    k1, k2, k3, k4 = jax.random.split(key, 4)

    def one_mamba(k):
        # Zamba2 mamba blocks are MIXER-ONLY (no per-layer MLP); the d_ff
        # MLP lives in the single SHARED attention block.
        return {
            "ln": common.norm_params(cfg.norm, cfg.d_model, dtype),
            "mixer": mamba2.init_params(k, cfg, dtype),
        }

    main_keys = jax.random.split(k1, n_groups * mpg)
    main = jax.vmap(one_mamba)(main_keys)
    main = jax.tree.map(
        lambda a: a.reshape((n_groups, mpg) + a.shape[1:]), main)
    tail_p = (jax.vmap(one_mamba)(jax.random.split(k2, tail))
              if tail else None)
    shared = init_block(k3, cfg, dtype, use_moe=False)  # ONE shared attn block
    return {"main": main, "tail": tail_p, "shared_attn": shared}


def mamba_sublayer(p, cfg: ModelConfig, x, approx_ffn=None):
    del approx_ffn  # mamba blocks have no FFN (zamba2 layout)
    h = common.apply_norm(cfg.norm, p["ln"], x, cfg.norm_eps)
    return x + mamba2.forward(p["mixer"], cfg, h)


def mamba_sublayer_prefill(p, cfg: ModelConfig, x, approx_ffn=None):
    """Full-sequence sublayer that also emits the decode cache (state
    handoff for prefill -> decode)."""
    del approx_ffn
    h = common.apply_norm(cfg.norm, p["ln"], x, cfg.norm_eps)
    out, state = mamba2.forward(p["mixer"], cfg, h, return_state=True)
    return x + out, state


def mamba_sublayer_decode(p, cfg: ModelConfig, x, cache, approx_ffn=None):
    del approx_ffn
    h = common.apply_norm(cfg.norm, p["ln"], x, cfg.norm_eps)
    out, new_cache = mamba2.decode_step(p["mixer"], cfg, h, cache)
    return x + out, new_cache
