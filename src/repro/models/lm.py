"""Causal LM assembly for all decoder-only families:

  dense / vlm  -- GQA (or MLA) transformer, optionally with patch-embedding
                  prefix (pixtral: frontend stubbed per the brief)
  moe          -- transformer with MoE FFN (+ leading dense layers, MTP)
  hybrid       -- zamba2: Mamba2 backbone + shared attention block
  ssm          -- rwkv6 (attention-free)

Layer loops are lax.scan over STACKED block params (compile-time O(1) in
depth; remat via jax.checkpoint when cfg.remat). The head loss is computed
in sequence chunks so the (B, S, V) logits tensor is never materialized.

Decode-time TAF (paper section 3.1.3 as a serving feature): with
cfg.approx_decode = TAF, each transformer layer carries a TAF state machine
across decode steps; when a layer's recent output deltas are RSD-stable the
whole layer's compute is SKIPPED (block-level lax.cond -- the hierarchy
insight) and the memoized delta + K/V rows are reused.

Decode reads each layer's KV cache in place: a layer returns only its new
rows at the decode position, and the step writes the rows of every layer
once, after the layer scan (`common.write_rows`), into the cache it was
given -- in place when the caller donates the cache.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.core.types import Level, Technique
from . import attention, blocks, common, mamba2, mlp, moe, rwkv6

PyTree = Any


def _dtype(name: str):
    return {"float32": jnp.float32, "bfloat16": jnp.bfloat16}[name]


def _stack_init(init_fn, key, n: int):
    """vmap an init function over n split keys -> stacked params."""
    keys = jax.random.split(key, n)
    return jax.vmap(init_fn)(keys)


def chunked_xent(h: jnp.ndarray, head_w: jnp.ndarray, labels: jnp.ndarray,
                 mask: Optional[jnp.ndarray] = None,
                 chunk: int = 512) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Cross-entropy without materializing (B, S, V). Returns (sum_nll, count)."""
    b, s, d = h.shape
    chunk = min(chunk, s)
    while s % chunk != 0:
        chunk //= 2
    nc = s // chunk
    hc = h.reshape(b, nc, chunk, d).transpose(1, 0, 2, 3)
    yc = labels.reshape(b, nc, chunk).transpose(1, 0, 2)
    mc = (mask if mask is not None else
          jnp.ones_like(labels, jnp.float32)).reshape(
              b, nc, chunk).transpose(1, 0, 2)

    def body(carry, inp):
        h_i, y_i, m_i = inp
        logits = jnp.einsum("bcd,dv->bcv", h_i,
                            head_w.astype(h_i.dtype)).astype(jnp.float32)
        logz = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(
            logits, y_i[..., None].astype(jnp.int32), axis=-1)[..., 0]
        nll = (logz - gold) * m_i
        s_nll, s_cnt = carry
        return (s_nll + jnp.sum(nll), s_cnt + jnp.sum(m_i)), None

    (total, count), _ = jax.lax.scan(body, (jnp.float32(0), jnp.float32(0)),
                                     (hc, yc, mc))
    return total, count


@dataclasses.dataclass
class Model:
    """Bound functional interface for one architecture."""

    cfg: ModelConfig
    init: Any
    hidden: Any          # (params, batch) -> (B, S, d) final hidden states
    loss: Any            # (params, batch) -> (loss, metrics)
    init_cache: Any      # (batch_size, max_len) -> cache pytree
    prefill: Any         # (params, batch) -> (last_logits, cache)
    decode_step: Any     # (params, cache, tokens(B,), pos) -> (logits, cache)


# ============================================================================
# decode-time TAF sharding (the serving data plane's per-shard knob layout)
# ============================================================================

# The TAF detector-state leaves of `_taf_init_cache`: per-layer scalars or
# small vectors with NO batch dim. These are the leaves that become
# PER-SHARD under a sharded serving engine -- each logical shard runs its
# own stability detector (window/filled/remaining) and its own traced
# threshold knob, so a QoS controller can tighten one shard while another
# keeps approximating, without recompiling. The memo_* leaves already carry
# the batch dim and shard along it like the KV cache.
TAF_SHARD_STATE = ("threshold", "window", "filled", "remaining")


# The row counter of a dropless expert stack (`moe.dropless_forward`): per
# expert layer, the rows routed to each held expert, (n_moe, E_held) int32,
# summed over every decode step since the cache was made (prefill counts
# nothing). It has no batch dim, so the admission splice keeps its live
# value; under a sharded engine it leads with a shard dim, like the TAF
# detector state. An int32 count wraps after 2**31 rows to one expert: at
# Moonlight's mean of 128 lanes x 6 / 64 = 12 rows a step, after 1.8e8
# steps (about 40 days of 21 ms steps).
MOE_ROWS = "moe_rows"


def shard_taf_state(cache, n_shards: int):
    """Return `cache` with the TAF detector state replicated per shard.

    Each `TAF_SHARD_STATE` leaf (n_layers, ...) gains a LEADING shard dim:
    (n_shards, n_layers, ...). `make_sharded_serve_step` vmaps the decode
    step over that dim, so every shard evolves an independent detector --
    the batch-global stability statistic (`jnp.mean(delta)` in
    `_decode_layer_taf`) becomes a per-shard statistic over the shard's own
    lanes. A no-op for caches without a "taf" entry (precise models).
    """
    if "taf" not in cache:
        return cache
    taf = dict(cache["taf"])
    for key in TAF_SHARD_STATE:
        leaf = taf[key]
        taf[key] = jnp.broadcast_to(leaf[None], (n_shards,) + leaf.shape)
    return dict(cache, taf=taf)


def shard_decode_state(cache, n_shards: int):
    """Return a freshly prefilled `cache` with its per-shard state led by a
    shard dim: the TAF detector state (`shard_taf_state`) and the expert
    row counter, which counts from 0 on every shard. The sharded serve step
    vmaps the decode step over that dim."""
    cache = shard_taf_state(cache, n_shards)
    if MOE_ROWS in cache:
        rows = cache[MOE_ROWS]
        cache = dict(cache, **{MOE_ROWS: jnp.zeros((n_shards,) + rows.shape,
                                                   rows.dtype)})
    return cache


def moe_row_counts(cfg: ModelConfig, cache, steps: int, lanes: int):
    """The expert row counts of a dropless expert stack over `steps` decode
    steps of `cache`, each of `lanes` lanes per shard: (routed, computed).
    `routed` (n_moe, E_held) int64 is the device counter summed over
    shards, read off the device here; `computed` is worked out on the host,
    since every step computes the same rows (`moe.rows_computed`), or
    equals the routed rows where the grouped product runs. None for a
    cache without the counter."""
    if cache is None or MOE_ROWS not in cache:
        return None
    routed = np.asarray(jax.device_get(cache[MOE_ROWS]), np.int64)
    n_shards = routed.shape[0] if routed.ndim == 3 else 1
    routed = routed.reshape((-1,) + routed.shape[-2:]).sum(axis=0)
    per_layer = moe.rows_computed(cfg, lanes, cfg.approx_ffn)
    if per_layer is None:
        return routed, int(routed.sum())
    return routed, steps * n_shards * routed.shape[0] * per_layer


# ============================================================================
# transformer families: dense / vlm / moe
# ============================================================================

def _build_transformer(cfg: ModelConfig) -> Model:
    pdt = _dtype(cfg.param_dtype)
    cdt = _dtype(cfg.compute_dtype)
    n_dense = cfg.moe.n_dense_layers if cfg.moe else cfg.n_layers
    n_moe = cfg.n_layers - n_dense if cfg.moe else 0
    if cfg.moe is None:
        n_dense = cfg.n_layers

    def init(key) -> PyTree:
        k_embed, k_dense, k_moe, k_norm, k_head, k_mtp = jax.random.split(key, 6)
        p: Dict = {
            "embed": common.embed_init(k_embed, (cfg.padded_vocab_size, cfg.d_model),
                                       pdt),
            "final_norm": common.norm_params(cfg.norm, cfg.d_model, pdt),
        }
        if not cfg.tie_embeddings:
            p["head"] = common.dense_init(k_head, (cfg.d_model, cfg.padded_vocab_size),
                                          dtype=pdt)
        if n_dense:
            p["dense_blocks"] = _stack_init(
                lambda k: blocks.init_block(k, cfg, pdt, use_moe=False),
                k_dense, n_dense)
        if n_moe:
            p["moe_blocks"] = _stack_init(
                lambda k: blocks.init_block(k, cfg, pdt, use_moe=True),
                k_moe, n_moe)
        if cfg.mtp:
            km1, km2 = jax.random.split(k_mtp)
            p["mtp"] = {
                "proj": common.dense_init(km1, (2 * cfg.d_model, cfg.d_model),
                                          dtype=pdt),
                "block": blocks.init_block(km2, cfg, pdt, use_moe=False),
            }
        return p

    def _embed(params, batch) -> jnp.ndarray:
        x = jnp.take(params["embed"], batch["tokens"], axis=0).astype(cdt)
        if cfg.frontend == "vision_patches":
            patches = batch["patch_embeds"].astype(cdt)  # (B, P, d) stub
            x = jnp.concatenate([patches, x], axis=1)
        return x

    def _stack_scan(params, params_key: str, use_moe: bool, x, positions):
        def body(carry, layer_p):
            h, aux = carry
            h, a = blocks.block_forward(
                layer_p, cfg, h, positions, use_moe,
                approx_attn=cfg.approx_attention, approx_ffn=cfg.approx_ffn)
            return (h, aux + a), None

        body_fn = jax.checkpoint(body) if cfg.remat else body
        (x, aux), _ = common.scan_layers(cfg.unroll_layers, body_fn,
                                         (x, jnp.float32(0)),
                                         params[params_key])
        return x, aux

    def hidden(params, batch):
        x = _embed(params, batch)
        positions = jnp.arange(x.shape[1])
        aux = jnp.float32(0)
        if n_dense:
            x, a = _stack_scan(params, "dense_blocks", False, x, positions)
            aux = aux + a
        if n_moe:
            x, a = _stack_scan(params, "moe_blocks", True, x, positions)
            aux = aux + a
        x = common.apply_norm(cfg.norm, params["final_norm"], x, cfg.norm_eps)
        return x, aux

    def _head_w(params):
        return (params["embed"].T if cfg.tie_embeddings else params["head"])

    def loss(params, batch):
        x, aux = hidden(params, batch)
        if cfg.frontend == "vision_patches":
            x = x[:, batch["patch_embeds"].shape[1]:]  # text positions only
        total, count = chunked_xent(x, _head_w(params), batch["labels"])
        out = total / jnp.maximum(count, 1.0)
        metrics = {"xent": out, "aux_loss": aux}
        if cfg.mtp:
            # MTP: h'_t = block(W[h_t ; emb(token_{t+1})]) predicts t+2
            emb_next = jnp.take(params["embed"], batch["tokens"],
                                axis=0).astype(cdt)
            cat = jnp.concatenate(
                [x[:, :-1], emb_next[:, 1:]], axis=-1)
            hm = jnp.einsum("bsd,dk->bsk", cat,
                            params["mtp"]["proj"].astype(cdt))
            positions = jnp.arange(hm.shape[1])
            hm, _ = blocks.block_forward(params["mtp"]["block"], cfg, hm,
                                         positions, use_moe=False)
            mtp_labels = batch["labels"][:, 1:]
            t2, c2 = chunked_xent(hm, _head_w(params), mtp_labels)
            mtp_loss = t2 / jnp.maximum(c2, 1.0)
            metrics["mtp_loss"] = mtp_loss
            out = out + cfg.mtp_loss_coef * mtp_loss
        return out + aux, metrics

    def init_cache(batch_size: int, max_len: int):
        cache: Dict = {}
        if n_dense:
            cache["dense"] = jax.vmap(
                lambda _: blocks.init_block_cache(cfg, batch_size, max_len,
                                                  cdt))(jnp.arange(n_dense))
        if n_moe:
            cache["moe"] = jax.vmap(
                lambda _: blocks.init_block_cache(cfg, batch_size, max_len,
                                                  cdt))(jnp.arange(n_moe))
        if _taf_decode_enabled():
            cache["taf"] = _taf_init_cache(batch_size, cfg.n_layers)
        if n_moe and cfg.moe.dropless:
            cache[MOE_ROWS] = jnp.zeros((n_moe, cfg.moe.held), jnp.int32)
        return cache

    def _prefill_stack(params_key, cache_key, use_moe, x, cache, params):
        def body(h, inp):
            layer_p, layer_c = inp
            h, new_c = blocks.block_prefill(
                layer_p, cfg, h, layer_c, use_moe,
                approx_attn=cfg.approx_attention, approx_ffn=cfg.approx_ffn)
            return h, new_c

        x, new_cache = common.scan_layers(
            cfg.unroll_layers, body, x,
            (params[params_key], cache[cache_key]))
        return x, new_cache

    def prefill(params, batch):
        x = _embed(params, batch)
        cache = init_cache(x.shape[0], batch["max_len"])
        if n_dense:
            x, cache["dense"] = _prefill_stack("dense_blocks", "dense", False,
                                               x, cache, params)
        if n_moe:
            x, cache["moe"] = _prefill_stack("moe_blocks", "moe", True,
                                             x, cache, params)
        x = common.apply_norm(cfg.norm, params["final_norm"], x, cfg.norm_eps)
        logits = jnp.einsum("bd,dv->bv", x[:, -1],
                            _head_w(params).astype(cdt))
        return logits.astype(jnp.float32), cache

    # ----- decode-time TAF (the paper's technique as a serving feature) ----
    def _taf_decode_enabled() -> bool:
        return (cfg.approx_decode.technique == Technique.TAF
                and not cfg.use_mla and cfg.moe is None)

    def _taf_init_cache(batch_size: int, n_layers: int):
        t = cfg.approx_decode.taf
        hd = cfg.resolved_head_dim
        return {
            # The RSD threshold rides in the cache pytree (one scalar per
            # layer) rather than closing over the config float: it is a
            # TRACED input to the jitted decode step, so a controller (the
            # QoS plane, repro.qos) can move the knob between ticks without
            # recompiling -- the same static-vs-traced split the Pallas
            # kernels use for their quality knobs.
            "threshold": jnp.full((n_layers,), t.rsd_threshold, jnp.float32),
            "window": jnp.zeros((n_layers, t.history_size), jnp.float32),
            "filled": jnp.zeros((n_layers,), jnp.int32),
            "remaining": jnp.zeros((n_layers,), jnp.int32),
            "memo_delta": jnp.zeros((n_layers, batch_size, cfg.d_model),
                                    jnp.float32),
            "memo_k": jnp.zeros((n_layers, batch_size, cfg.n_kv_heads, 1, hd),
                                cdt),
            "memo_v": jnp.zeros((n_layers, batch_size, cfg.n_kv_heads, 1, hd),
                                cdt),
        }

    def _decode_layer_taf(layer_p, layer_c, taf_c, x, pos):
        """Block-level TAF around one layer's decode step: skip the whole
        layer (reuse the memoized delta and K/V rows) while RSD-stable.
        Returns (x, rows, taf state); the layer cache is read only."""
        t = cfg.approx_decode.taf

        def approx_branch(op):
            x, taf_c = op
            new_x = x + taf_c["memo_delta"][:, None, :].astype(x.dtype)
            new_taf = dict(taf_c)
            new_taf["remaining"] = jnp.maximum(taf_c["remaining"] - 1, 0)
            rows = {"k": taf_c["memo_k"], "v": taf_c["memo_v"]}
            return new_x, rows, new_taf

        def accurate_branch(op):
            x, taf_c = op
            new_x, rows, _ = blocks.block_decode(
                layer_p, cfg, x, layer_c, pos, use_moe=False,
                approx_attn=cfg.approx_attention, approx_ffn=cfg.approx_ffn)
            delta = (new_x - x)[:, 0, :].astype(jnp.float32)
            s = jnp.mean(delta)
            win = jnp.roll(taf_c["window"], -1).at[-1].set(s)
            filled = jnp.minimum(taf_c["filled"] + 1, t.history_size)
            mu = jnp.mean(win)
            sd = jnp.std(win)
            stable = (sd / jnp.maximum(jnp.abs(mu), 1e-12) <
                      taf_c["threshold"]) & (filled >= t.history_size)
            new_taf = {
                "threshold": taf_c["threshold"],
                "window": win, "filled": filled,
                "remaining": jnp.where(stable, t.prediction_size, 0)
                .astype(jnp.int32),
                "memo_delta": delta, "memo_k": rows["k"],
                "memo_v": rows["v"],
            }
            return new_x, rows, new_taf

        return jax.lax.cond(taf_c["remaining"] > 0, approx_branch,
                            accurate_branch, (x, taf_c))

    def _decode_stack(params_key, cache_key, use_moe, x, cache, pos, params):
        """The layer scan of one decode step: the stacked cache is read
        only, each layer yields its new rows, and the rows of every layer
        are written once, after the scan, at `pos`. Returns (x, cache,
        taf state or None, per-layer row counters or None)."""
        if _taf_decode_enabled():
            def body(h, inp):
                layer_p, layer_c, taf_c = inp
                h, rows, new_taf = _decode_layer_taf(layer_p, layer_c,
                                                     taf_c, h, pos)
                return h, (rows, new_taf)

            x, (rows, new_taf) = common.scan_layers(
                cfg.unroll_layers, body, x,
                (params[params_key], cache[cache_key], cache["taf"]))
            counts = None
        else:
            def body(h, inp):
                layer_p, layer_c = inp
                h, rows, counts = blocks.block_decode(
                    layer_p, cfg, h, layer_c, pos, use_moe,
                    approx_attn=cfg.approx_attention,
                    approx_ffn=cfg.approx_ffn)
                return h, (rows, counts)

            x, (rows, counts) = common.scan_layers(
                cfg.unroll_layers, body, x,
                (params[params_key], cache[cache_key]))
            new_taf = None
        return (x, common.write_rows(cache[cache_key], rows, pos), new_taf,
                counts)

    def decode_step(params, cache, tokens, pos):
        """tokens: (B,) -> (logits (B, V), new cache)."""
        x = jnp.take(params["embed"], tokens[:, None], axis=0).astype(cdt)
        new_cache = dict(cache)
        if n_dense:
            x, nc, ntaf, _ = _decode_stack("dense_blocks", "dense", False,
                                           x, cache, pos, params)
            new_cache["dense"] = nc
            if ntaf is not None:
                new_cache["taf"] = ntaf
        if n_moe:
            x, nc, _, counts = _decode_stack("moe_blocks", "moe", True,
                                             x, cache, pos, params)
            new_cache["moe"] = nc
            if counts is not None:
                new_cache[MOE_ROWS] = cache[MOE_ROWS] + counts
        x = common.apply_norm(cfg.norm, params["final_norm"], x, cfg.norm_eps)
        logits = jnp.einsum("bd,dv->bv", x[:, 0], _head_w(params).astype(cdt))
        return logits.astype(jnp.float32), new_cache

    return Model(cfg=cfg, init=init, hidden=lambda p, b: hidden(p, b)[0],
                 loss=loss, init_cache=init_cache, prefill=prefill,
                 decode_step=decode_step)


# ============================================================================
# hybrid (zamba2)
# ============================================================================

def _build_hybrid(cfg: ModelConfig) -> Model:
    pdt = _dtype(cfg.param_dtype)
    cdt = _dtype(cfg.compute_dtype)
    n_groups, mpg, tail = blocks.hybrid_layout(cfg)

    def init(key):
        k1, k2, k3 = jax.random.split(key, 3)
        return {
            "embed": common.embed_init(k1, (cfg.padded_vocab_size, cfg.d_model), pdt),
            "layers": blocks.init_hybrid(k2, cfg, pdt),
            "final_norm": common.norm_params(cfg.norm, cfg.d_model, pdt),
            "head": common.dense_init(k3, (cfg.d_model, cfg.padded_vocab_size),
                                      dtype=pdt),
        }

    def hidden(params, batch):
        x = jnp.take(params["embed"], batch["tokens"], axis=0).astype(cdt)
        positions = jnp.arange(x.shape[1])
        shared = params["layers"]["shared_attn"]

        def group_body(h, group_p):
            def mamba_body(hh, mp):
                return blocks.mamba_sublayer(mp, cfg, hh,
                                             approx_ffn=cfg.approx_ffn), None
            mb = jax.checkpoint(mamba_body) if cfg.remat else mamba_body
            h, _ = common.scan_layers(cfg.unroll_layers, mb, h, group_p)
            h, _ = blocks.block_forward(shared, cfg, h, positions,
                                        use_moe=False,
                                        approx_attn=cfg.approx_attention,
                                        approx_ffn=cfg.approx_ffn)
            return h, None

        x, _ = common.scan_layers(cfg.unroll_layers, group_body, x,
                                  params["layers"]["main"])
        if tail:
            def mamba_body(hh, mp):
                return blocks.mamba_sublayer(mp, cfg, hh,
                                             approx_ffn=cfg.approx_ffn), None
            x, _ = common.scan_layers(cfg.unroll_layers, mamba_body, x,
                                      params["layers"]["tail"])
        return common.apply_norm(cfg.norm, params["final_norm"], x,
                                 cfg.norm_eps)

    def loss(params, batch):
        x = hidden(params, batch)
        total, count = chunked_xent(x, params["head"], batch["labels"])
        out = total / jnp.maximum(count, 1.0)
        return out, {"xent": out}

    def init_cache(batch_size: int, max_len: int):
        def one_mamba(_):
            return mamba2.init_cache(cfg, batch_size, cdt)
        return {
            "mamba_main": jax.vmap(
                lambda i: jax.vmap(one_mamba)(jnp.arange(mpg)))(
                    jnp.arange(n_groups)),
            "mamba_tail": (jax.vmap(one_mamba)(jnp.arange(tail))
                           if tail else None),
            # one KV cache per shared-attn APPLICATION (weights shared,
            # caches distinct)
            "attn": jax.vmap(
                lambda _: blocks.init_block_cache(cfg, batch_size, max_len,
                                                  cdt))(jnp.arange(n_groups)),
        }

    def prefill(params, batch):
        x = jnp.take(params["embed"], batch["tokens"], axis=0).astype(cdt)
        cache = init_cache(x.shape[0], batch["max_len"])
        shared = params["layers"]["shared_attn"]

        def group_body(h, inp):
            group_p, attn_c = inp

            def mamba_body(hh, mp):
                return blocks.mamba_sublayer_prefill(mp, cfg, hh)
            h, mamba_states = common.scan_layers(cfg.unroll_layers,
                                                 mamba_body, h, group_p)
            h, new_attn_c = blocks.block_prefill(shared, cfg, h, attn_c,
                                                 use_moe=False)
            return h, (mamba_states, new_attn_c)

        x, (new_mamba, new_attn) = common.scan_layers(
            cfg.unroll_layers, group_body, x,
            (params["layers"]["main"], cache["attn"]))
        cache["attn"] = new_attn
        cache["mamba_main"] = jax.tree.map(
            lambda a, b: a.astype(b.dtype), new_mamba, cache["mamba_main"])
        if tail:
            def mamba_body(hh, mp):
                return blocks.mamba_sublayer_prefill(mp, cfg, hh)
            x, new_tail = common.scan_layers(cfg.unroll_layers, mamba_body,
                                             x, params["layers"]["tail"])
            cache["mamba_tail"] = jax.tree.map(
                lambda a, b: a.astype(b.dtype), new_tail, cache["mamba_tail"])
        x = common.apply_norm(cfg.norm, params["final_norm"], x, cfg.norm_eps)
        logits = jnp.einsum("bd,dv->bv", x[:, -1], params["head"].astype(cdt))
        return logits.astype(jnp.float32), cache

    def decode_step(params, cache, tokens, pos):
        x = jnp.take(params["embed"], tokens[:, None], axis=0).astype(cdt)
        shared = params["layers"]["shared_attn"]

        def group_body(h, inp):
            group_p, mamba_c, attn_c = inp

            def mamba_body(hh, inp2):
                mp, mc = inp2
                hh, new_mc = blocks.mamba_sublayer_decode(mp, cfg, hh, mc)
                return hh, new_mc
            h, new_mamba_c = common.scan_layers(cfg.unroll_layers,
                                                mamba_body, h,
                                                (group_p, mamba_c))
            h, attn_rows, _ = blocks.block_decode(
                shared, cfg, h, attn_c, pos, use_moe=False,
                approx_attn=cfg.approx_attention)
            return h, (new_mamba_c, attn_rows)

        x, (new_mamba, attn_rows) = common.scan_layers(
            cfg.unroll_layers, group_body, x,
            (params["layers"]["main"], cache["mamba_main"], cache["attn"]))
        new_cache = dict(cache)
        new_cache["mamba_main"] = new_mamba
        new_cache["attn"] = common.write_rows(cache["attn"], attn_rows, pos)
        if tail:
            def mamba_body(hh, inp2):
                mp, mc = inp2
                hh, new_mc = blocks.mamba_sublayer_decode(mp, cfg, hh, mc)
                return hh, new_mc
            x, new_tail = common.scan_layers(cfg.unroll_layers, mamba_body,
                                             x, (params["layers"]["tail"],
                                                 cache["mamba_tail"]))
            new_cache["mamba_tail"] = new_tail
        x = common.apply_norm(cfg.norm, params["final_norm"], x, cfg.norm_eps)
        logits = jnp.einsum("bd,dv->bv", x[:, 0], params["head"].astype(cdt))
        return logits.astype(jnp.float32), new_cache

    return Model(cfg=cfg, init=init, hidden=hidden, loss=loss,
                 init_cache=init_cache, prefill=prefill,
                 decode_step=decode_step)


# ============================================================================
# ssm (rwkv6)
# ============================================================================

def _build_rwkv(cfg: ModelConfig) -> Model:
    pdt = _dtype(cfg.param_dtype)
    cdt = _dtype(cfg.compute_dtype)

    def init(key):
        k1, k2, k3 = jax.random.split(key, 3)
        return {
            "embed": common.embed_init(k1, (cfg.padded_vocab_size, cfg.d_model), pdt),
            "ln_in": common.norm_params("ln", cfg.d_model, pdt),
            "layers": _stack_init(
                lambda k: rwkv6.init_layer(k, cfg, pdt), k2, cfg.n_layers),
            "final_norm": common.norm_params("ln", cfg.d_model, pdt),
            "head": common.dense_init(k3, (cfg.d_model, cfg.padded_vocab_size),
                                      dtype=pdt),
        }

    def init_cache(batch_size: int, max_len: int = 0):
        return jax.vmap(lambda _: rwkv6.init_layer_cache(cfg, batch_size, cdt)
                        )(jnp.arange(cfg.n_layers))

    def _run(params, x, cache):
        def body(h, inp):
            layer_p, layer_c = inp
            h, new_c = rwkv6.layer_forward(layer_p, cfg, h, layer_c,
                                           approx=cfg.approx_ffn)
            return h, new_c

        body_fn = jax.checkpoint(body) if cfg.remat else body
        x, new_cache = common.scan_layers(cfg.unroll_layers, body_fn, x,
                                          (params["layers"], cache))
        return x, new_cache

    def hidden(params, batch):
        x = jnp.take(params["embed"], batch["tokens"], axis=0).astype(cdt)
        x = common.layernorm(params["ln_in"], x, cfg.norm_eps)
        cache = init_cache(x.shape[0])
        x, _ = _run(params, x, cache)
        return common.layernorm(params["final_norm"], x, cfg.norm_eps)

    def loss(params, batch):
        x = hidden(params, batch)
        total, count = chunked_xent(x, params["head"], batch["labels"])
        out = total / jnp.maximum(count, 1.0)
        return out, {"xent": out}

    def prefill(params, batch):
        x = jnp.take(params["embed"], batch["tokens"], axis=0).astype(cdt)
        x = common.layernorm(params["ln_in"], x, cfg.norm_eps)
        cache = init_cache(x.shape[0])
        x, cache = _run(params, x, cache)
        x = common.layernorm(params["final_norm"], x, cfg.norm_eps)
        logits = jnp.einsum("bd,dv->bv", x[:, -1], params["head"].astype(cdt))
        return logits.astype(jnp.float32), cache

    def decode_step(params, cache, tokens, pos):
        del pos  # state-space: position is implicit in the state
        x = jnp.take(params["embed"], tokens[:, None], axis=0).astype(cdt)
        x = common.layernorm(params["ln_in"], x, cfg.norm_eps)
        x, new_cache = _run(params, x, cache)
        x = common.layernorm(params["final_norm"], x, cfg.norm_eps)
        logits = jnp.einsum("bd,dv->bv", x[:, 0], params["head"].astype(cdt))
        return logits.astype(jnp.float32), new_cache

    return Model(cfg=cfg, init=init, hidden=hidden, loss=loss,
                 init_cache=init_cache, prefill=prefill,
                 decode_step=decode_step)


# ============================================================================
# factory
# ============================================================================

def build(cfg: ModelConfig) -> Model:
    if cfg.family in ("dense", "vlm", "moe"):
        return _build_transformer(cfg)
    if cfg.family == "hybrid":
        return _build_hybrid(cfg)
    if cfg.family == "ssm":
        return _build_rwkv(cfg)
    if cfg.family == "audio":
        from . import whisper
        return whisper.build(cfg)
    raise ValueError(f"unknown family {cfg.family}")
