"""Mixture-of-Experts layer, two routings:

  * GShard-style top-k routing with capacity and grouped dispatch einsums
    (SPMD-friendly: the expert dimension shards over the `model` mesh axis
    => XLA inserts the all-to-all pattern) -- OLMoE, DeepSeek-V3 as listed;
  * dropless routing over the share of the experts this device holds
    (`MoEConfig.dropless`, `dropless_forward`) with DeepSeek-V3's sigmoid
    router and correction bias -- Moonlight;

plus optional shared (always-on) experts.

Beyond-paper AC composition: *expert perforation* -- herded dropping of every
M-th routed expert (the paper's loop-perforation insight applied to the
expert loop). Because the drop set is herded (static and shared), the
dropped experts' weights are never touched: structural savings.

The dispatch is grouped (`router_group_size` tokens per group) so the
one-hot dispatch tensor stays (G, S_g, E, C) with S_g small -- the VMEM/HBM
capacity argument of paper Figure 3 applied to routing state.
"""
from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig, MoEConfig
from repro.core.perforation import kept_indices
from repro.core.types import ApproxSpec, Technique
from . import common, mlp


def init_params(key, cfg: ModelConfig, dtype) -> Dict:
    m = cfg.moe
    d = cfg.d_model
    ks = jax.random.split(key, 5)
    p = {
        "router": common.dense_init(ks[0], (d, m.n_experts), dtype=jnp.float32),
        # the held experts stacked on a leading E axis (shards over `model`)
        "w_gate": common.dense_init(ks[1], (m.held, d, m.d_ff_expert),
                                    scale=1.0 / (d ** 0.5), dtype=dtype),
        "w_up": common.dense_init(ks[2], (m.held, d, m.d_ff_expert),
                                  scale=1.0 / (d ** 0.5), dtype=dtype),
        "w_down": common.dense_init(ks[3], (m.held, m.d_ff_expert, d),
                                    scale=1.0 / (m.d_ff_expert ** 0.5),
                                    dtype=dtype),
    }
    if m.dropless:
        p["router_bias"] = jnp.zeros((m.n_experts,), jnp.float32)
    if m.n_shared_experts:
        p["shared"] = mlp.init_params(
            ks[4], d, m.d_ff_expert * m.n_shared_experts, "gated_silu", dtype)
    return p


def _capacity(m: MoEConfig, group: int) -> int:
    c = int(group * m.experts_per_token * m.capacity_factor / m.n_experts)
    return max(c, m.experts_per_token)


def forward(p: Dict, cfg: ModelConfig, x: jnp.ndarray,
            approx: Optional[ApproxSpec] = None
            ) -> Tuple[jnp.ndarray, jnp.ndarray, Optional[Dict]]:
    """x: (B, S, d) -> (out, aux_loss, routed). `cfg.moe.dropless` takes
    the dropless held-share path (`dropless_forward`, which counts the rows
    routed to each held expert); otherwise GShard capacity routing, whose
    overflow falls through to the shared expert / residual, and `routed`
    is None."""
    if cfg.moe.dropless:
        return dropless_forward(p, cfg, x, approx)
    out, aux = _gshard_forward(p, cfg, x, approx)
    return out, aux, None


def _gshard_forward(p: Dict, cfg: ModelConfig, x: jnp.ndarray,
                    approx: Optional[ApproxSpec]):
    m = cfg.moe
    b, s, d = x.shape
    dt = x.dtype
    n_e = m.n_experts

    # --- expert perforation (beyond-paper AC; herded over the expert list)
    kept_experts = None
    if approx is not None and approx.technique == Technique.PERFORATION:
        kept = kept_indices(n_e, approx.perforation)
        if len(kept) < n_e:
            kept_experts = jnp.asarray(kept, jnp.int32)

    group = min(m.router_group_size, b * s)
    n_tokens = b * s
    assert n_tokens % group == 0, (n_tokens, group)
    g = n_tokens // group
    xg = x.reshape(g, group, d)

    router_w = common.shard_hint(p["router"].astype(jnp.float32),
                                 None, None)  # tiny: replicate at use
    if kept_experts is not None:
        router_w = jnp.take(router_w, kept_experts, axis=1)
        w_gate = jnp.take(p["w_gate"], kept_experts, axis=0)
        w_up = jnp.take(p["w_up"], kept_experts, axis=0)
        w_down = jnp.take(p["w_down"], kept_experts, axis=0)
        n_e = len(kept)
    else:
        w_gate, w_up, w_down = p["w_gate"], p["w_up"], p["w_down"]
    # ZeRO-3 use-site re-gather (section Perf cell B): expert weights compute in
    # EP-only layout; FSDP keeps storage sharded over the data axes
    w_gate = common.shard_hint(w_gate, "model", None, None)
    w_up = common.shard_hint(w_up, "model", None, None)
    w_down = common.shard_hint(w_down, "model", None, None)

    logits = jnp.einsum("gtd,de->gte", xg.astype(jnp.float32), router_w)
    # router stays token-local (section Perf cell B4): no E-sharded probs =>
    # no all-gather around top_k
    logits = common.shard_hint(logits, common.data_axes_hint(), None, None)
    probs = jax.nn.softmax(logits, axis=-1)                  # (g, t, E)
    k = min(m.experts_per_token, n_e)
    top_w, top_i = jax.lax.top_k(probs, k)                   # (g, t, k)
    top_w = top_w / jnp.maximum(jnp.sum(top_w, -1, keepdims=True), 1e-9)

    # aux load-balance loss (Switch-style): E * sum_e f_e * P_e
    me = jnp.mean(probs, axis=(0, 1))                        # (E,)
    onehot_top = jax.nn.one_hot(top_i, n_e, dtype=jnp.float32)  # (g,t,k,E)
    ce = jnp.mean(jnp.sum(onehot_top, axis=2), axis=(0, 1))  # (E,)
    aux = n_e * jnp.sum(me * ce) * m.aux_loss_coef

    # --- capacity assignment: position of each (token, slot) in its expert
    cap = _capacity(m, group)
    flat_assign = onehot_top                                  # (g,t,k,E)
    # rank within expert: cumsum over (t, k) flattened
    a2 = flat_assign.reshape(g, group * k, n_e)
    ranks = jnp.cumsum(a2, axis=1) - a2                       # (g, t*k, E)
    pos = jnp.sum(ranks * a2, axis=-1).reshape(g, group, k)   # (g, t, k)
    keep = pos < cap
    w_kept = top_w * keep.astype(jnp.float32)

    # dispatch tensor (g, t, E, C)
    pos_oh = jax.nn.one_hot(jnp.where(keep, pos, cap).astype(jnp.int32),
                            cap + 1, dtype=jnp.float32)[..., :cap]
    disp = jnp.einsum("gtke,gtkc->gtec", onehot_top,
                      pos_oh * keep[..., None].astype(jnp.float32))
    comb = jnp.einsum("gtke,gtkc,gtk->gtec", onehot_top, pos_oh, w_kept)

    # expert compute: (g, E, C, d) -> ffn -> back. Layout pins (section Perf
    # cell B2): token groups over the data axes, experts over model; the
    # g<->E reshard is the all-to-all, everything else stays local.
    da = common.data_axes_hint()
    xg = common.shard_hint(xg, da, None, None)
    disp = common.shard_hint(disp, da, None, "model", None)
    xe = jnp.einsum("gtec,gtd->gecd", disp.astype(dt), xg)
    xe = common.shard_hint(xe, da, "model", None, None)
    h = jax.nn.silu(jnp.einsum("gecd,edf->gecf", xe, w_gate.astype(dt))) * \
        jnp.einsum("gecd,edf->gecf", xe, w_up.astype(dt))
    h = common.shard_hint(h, da, "model", None, None)
    ye = jnp.einsum("gecf,efd->gecd", h, w_down.astype(dt))
    ye = common.shard_hint(ye, da, "model", None, None)
    out = jnp.einsum("gtec,gecd->gtd", comb.astype(dt), ye)
    out = common.shard_hint(out, da, None, None)

    out = out.reshape(b, s, d)
    if m.n_shared_experts:
        out = out + mlp.forward(p["shared"], cfg, x, "gated_silu")
    return out, aux.astype(jnp.float32)


# ----------------------------------------------------------------------------
# dropless routing over a held share of the experts (expert parallelism)
# ----------------------------------------------------------------------------

# Below this many tokens a chunk computes every held expert on every token
# (a masked product): the grouped product's TPU kernel computes whole tiles
# of 256 rows per expert group it visits (tm in the compiled ragged-dot
# metadata on the v5e), so at decode sizes the grouped form computes no
# fewer rows and adds a sort, a gather and a scatter.
MASKED_MAX_TOKENS = 256


def route(p: Dict, m: MoEConfig, x2: jnp.ndarray,
          dropped: Optional[np.ndarray] = None):
    """DeepSeek-V3's `noaux_tc` top-k routing of tokens x2 (N, d) over all
    `m.n_experts`, in float32 (the router's product too, as published):
    sigmoid scores, the correction bias `router_bias` added for the choice
    only, the chosen scores normalised over the k and scaled by
    `routed_scaling_factor`. `dropped`: global expert ids that may not be
    chosen (perforated). Returns (weights (N, k), expert ids (N, k))."""
    logits = jnp.matmul(x2.astype(jnp.float32),
                        p["router"].astype(jnp.float32),
                        precision=jax.lax.Precision.HIGHEST)
    scores = jax.nn.sigmoid(logits)
    choice = scores + p["router_bias"]
    if dropped is not None and len(dropped):
        choice = choice.at[:, dropped].set(-jnp.inf)
    _, idx = jax.lax.top_k(choice, m.experts_per_token)
    w = jnp.take_along_axis(scores, idx, axis=-1)
    w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
    return w * m.routed_scaling_factor, idx


def _experts_masked(x2, wmat, wg, wu, wd):
    """Every held expert on every token; wmat (N, E_h) float32 holds each
    token's routing weight per held expert (0 where not routed)."""
    dt = x2.dtype
    h = jax.nn.silu(jnp.einsum("nd,edf->enf", x2, wg.astype(dt))) * \
        jnp.einsum("nd,edf->enf", x2, wu.astype(dt))
    y = jnp.einsum("enf,efd->end", h, wd.astype(dt))
    return jnp.einsum("ne,end->nd", wmat, y.astype(jnp.float32))


def _experts_grouped(x2, w, local, wg, wu, wd):
    """Only the routed rows: (token, slot) pairs sorted by held expert,
    one grouped product per matrix (`jax.lax.ragged_dot`), weighted and
    added back to their tokens. `local` (N, k) is each pair's held-expert
    index, E_h for pairs routed elsewhere (sorted last, outside every
    group)."""
    n, k = local.shape
    n_e = wg.shape[0]
    dt = x2.dtype
    flat = local.reshape(-1)
    order = jnp.argsort(flat, stable=True)
    tok = order // k
    sizes = jnp.zeros((n_e + 1,), jnp.int32).at[flat].add(1)[:n_e]
    xs = x2[tok]
    h = jax.nn.silu(jax.lax.ragged_dot(xs, wg.astype(dt), sizes)) * \
        jax.lax.ragged_dot(xs, wu.astype(dt), sizes)
    y = jax.lax.ragged_dot(h, wd.astype(dt), sizes).astype(jnp.float32)
    held = (flat[order] < n_e)[:, None]
    y = jnp.where(held, y * w.reshape(-1)[order][:, None], 0.0)
    return jnp.zeros((n, x2.shape[-1]), jnp.float32).at[tok].add(y)


def _kept(m: MoEConfig, approx: Optional[ApproxSpec]) -> np.ndarray:
    """The held experts (0..n_held-1) expert perforation leaves in use."""
    if approx is not None and approx.technique == Technique.PERFORATION:
        return np.asarray(kept_indices(m.held, approx.perforation))
    return np.arange(m.held)


def rows_computed(cfg: ModelConfig, n_tokens: int,
                  approx: Optional[ApproxSpec] = None) -> Optional[int]:
    """Expert rows one `dropless_forward` over `n_tokens` tokens computes:
    every kept held expert on every token where its chunks take the masked
    product; None where they take the grouped product, which computes the
    routed rows alone."""
    if min(cfg.moe.router_group_size, n_tokens) > MASKED_MAX_TOKENS:
        return None
    return n_tokens * len(_kept(cfg.moe, approx))


def dropless_forward(p: Dict, cfg: ModelConfig, x: jnp.ndarray,
                     approx: Optional[ApproxSpec] = None):
    """x: (B, S, d) -> (out, aux_loss, routed). The router scores all
    `n_experts`; this device holds experts [expert_offset, expert_offset +
    n_held) and computes every (token, expert) pair routed to one of them,
    whatever the skew: nothing is dropped. Pairs routed elsewhere add
    nothing here (their experts live on other devices); the shared experts
    are added once. Expert perforation drops a herded subset of the held
    experts from the router's choice and from the weights read.

    Chunks of `router_group_size` tokens run one after another; a chunk of
    at most `MASKED_MAX_TOKENS` tokens takes the masked product, a larger
    one the grouped product (`rows_computed` counts the rows each form
    computes). `routed` (E_h,) int32: the rows routed to each held expert.
    aux is 0: the correction bias balances the load (aux-loss-free)."""
    m = cfg.moe
    b, s, d = x.shape
    n_h = m.held
    kept = _kept(m, approx)
    dropped = np.setdiff1d(np.arange(n_h), kept) + m.expert_offset
    # held index -> position among the kept experts (dropped: never chosen)
    slot = np.full(n_h + 1, len(kept), np.int32)
    slot[kept] = np.arange(len(kept))
    slot = jnp.asarray(slot)
    wg, wu, wd = p["w_gate"], p["w_up"], p["w_down"]
    if len(kept) < n_h:
        wg, wu, wd = (jnp.take(w, jnp.asarray(kept), axis=0)
                      for w in (wg, wu, wd))
    n_e = len(kept)

    n = b * s
    group = min(m.router_group_size, n)
    assert n % group == 0, (n, group)
    use_grouped = group > MASKED_MAX_TOKENS

    def chunk(x2):
        with jax.named_scope("moe.route"):
            w, idx = route(p, m, x2, dropped)
            local = idx - m.expert_offset
            here = (local >= 0) & (local < n_h)
            local = jnp.where(here, local, n_h)
            # a one-hot sum, not a scatter: a scatter-add into this tiny
            # array took 0.8 ms of a 21 ms decode step on the v5e
            routed = jnp.sum(jax.nn.one_hot(local, n_h, dtype=jnp.int32),
                             axis=(0, 1))
            local = slot[local]                       # kept index, or n_e
            w = jnp.where(here, w, 0.0)
        with jax.named_scope("moe.experts"):
            if use_grouped:
                out = _experts_grouped(x2, w, local, wg, wu, wd)
            else:
                wmat = jnp.einsum("nk,nke->ne", w,
                                  jax.nn.one_hot(local, n_e))
                out = _experts_masked(x2, wmat, wg, wu, wd)
        return out, routed

    out, routed = jax.lax.map(chunk, x.reshape(n // group, group, d))
    out = out.reshape(b, s, d).astype(x.dtype)
    if m.n_shared_experts:
        with jax.named_scope("moe.shared"):
            out = out + mlp.forward(p["shared"], cfg, x, "gated_silu")
    return out, jnp.float32(0.0), jnp.sum(routed, axis=0)
