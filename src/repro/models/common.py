"""Shared model components: norms, RoPE, init, chunked attention math.

Pure-functional: params are nested dicts of jnp arrays; every module is a
pair of functions (init_params, apply). No flax -- pytrees all the way down,
which keeps pjit/shard_map sharding rules trivial to express.
"""
from __future__ import annotations

import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp


def dense_init(key, shape, scale: Optional[float] = None, dtype=jnp.float32):
    """Truncated-normal fan-in init (maxtext-style default)."""
    fan_in = shape[0] if len(shape) >= 1 else 1
    scale = scale if scale is not None else 1.0 / math.sqrt(max(fan_in, 1))
    return (jax.random.truncated_normal(key, -2.0, 2.0, shape, jnp.float32)
            * scale).astype(dtype)


def embed_init(key, shape, dtype=jnp.float32):
    return (jax.random.normal(key, shape, jnp.float32) * 0.02).astype(dtype)


# ----------------------------------------------------------------------------
# norms
# ----------------------------------------------------------------------------

def rmsnorm_params(d: int, dtype=jnp.float32):
    return {"scale": jnp.ones((d,), dtype)}


def rmsnorm(p, x, eps: float = 1e-5):
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    out = xf * jax.lax.rsqrt(var + eps) * p["scale"].astype(jnp.float32)
    return out.astype(x.dtype)


def layernorm_params(d: int, dtype=jnp.float32):
    return {"scale": jnp.ones((d,), dtype), "bias": jnp.zeros((d,), dtype)}


def layernorm(p, x, eps: float = 1e-5):
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.mean((xf - mu) ** 2, axis=-1, keepdims=True)
    out = (xf - mu) * jax.lax.rsqrt(var + eps)
    out = out * p["scale"].astype(jnp.float32) + p["bias"].astype(jnp.float32)
    return out.astype(x.dtype)


def norm_params(kind: str, d: int, dtype=jnp.float32):
    return rmsnorm_params(d, dtype) if kind == "rms" else layernorm_params(d, dtype)


def apply_norm(kind: str, p, x, eps: float = 1e-5):
    return rmsnorm(p, x, eps) if kind == "rms" else layernorm(p, x, eps)


# ----------------------------------------------------------------------------
# rotary position embeddings
# ----------------------------------------------------------------------------

def rope_freqs(head_dim: int, theta: float = 10000.0) -> jnp.ndarray:
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32)
                            / head_dim))


def apply_rope(x: jnp.ndarray, positions: jnp.ndarray,
               theta: float = 10000.0) -> jnp.ndarray:
    """x: (..., S, D) with D even; positions: (S,) or broadcastable."""
    d = x.shape[-1]
    freqs = rope_freqs(d, theta)                              # (D/2,)
    angles = positions[..., :, None].astype(jnp.float32) * freqs  # (S, D/2)
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    x1, x2 = x[..., ::2], x[..., 1::2]
    xr1 = x1 * cos - x2 * sin
    xr2 = x2 * cos + x1 * sin
    out = jnp.stack([xr1, xr2], axis=-1).reshape(x.shape)
    return out.astype(x.dtype)


def sinusoidal_positions(seq_len: int, d: int) -> jnp.ndarray:
    pos = jnp.arange(seq_len, dtype=jnp.float32)[:, None]
    div = jnp.exp(jnp.arange(0, d, 2, dtype=jnp.float32) * (-math.log(10000.0) / d))
    pe = jnp.zeros((seq_len, d), jnp.float32)
    pe = pe.at[:, 0::2].set(jnp.sin(pos * div))
    pe = pe.at[:, 1::2].set(jnp.cos(pos * div))
    return pe


def shard_hint(x: jnp.ndarray, *spec) -> jnp.ndarray:
    """with_sharding_constraint when an ambient mesh exists, else identity.

    Spec entries may name axes ('data', 'model', ('pod','data')); axes not
    present in the ambient mesh are dropped, and a dim whose size does not
    divide the axis size falls back to unconstrained. Lets model code carry
    production sharding hints while remaining runnable on a single device.
    """
    from jax._src import mesh as mesh_lib
    env = mesh_lib.thread_resources.env.physical_mesh
    if env.empty:
        return x
    names = set(env.axis_names)

    def axis_size(a):
        if isinstance(a, tuple):
            n = 1
            for el in a:
                n *= env.shape[el]
            return n
        return env.shape[a]

    out = []
    for dim, s in enumerate(spec):
        if s is None:
            out.append(None)
            continue
        if isinstance(s, tuple):
            s = tuple(a for a in s if a in names)
            s = s if s else None
        elif s not in names:
            s = None
        if s is not None and x.shape[dim] % axis_size(s) != 0:
            s = None
        out.append(s)
    from jax.sharding import PartitionSpec
    return jax.lax.with_sharding_constraint(x, PartitionSpec(*out))


def data_axes_hint():
    """('pod','data') subset present in the ambient mesh (or 'data')."""
    return ("pod", "data")


def scan_layers(unroll: bool, body, carry, xs):
    """lax.scan over stacked layer params, or a python unroll when `unroll`.

    Unrolling exists for the roofline marginal-cost artifacts: XLA's cost
    analysis counts a while-loop body ONCE regardless of trip count, so
    per-layer costs must come from unrolled small-L lowerings.
    """
    if not unroll:
        return jax.lax.scan(body, carry, xs)
    n = jax.tree.leaves(xs)[0].shape[0]
    ys = []
    for i in range(n):
        x_i = jax.tree.map(lambda a: a[i], xs)
        carry, y = body(carry, x_i)
        ys.append(y)
    if ys and ys[0] is not None:
        stacked = jax.tree.map(lambda *a: jnp.stack(a), *ys)
    else:
        stacked = None
    return carry, stacked


def write_rows(cache, rows, pos):
    """Write each leaf of `rows` into the same leaf of `cache` at sequence
    position `pos`: one `dynamic_update_slice` per leaf, so a cache the
    caller donates is updated in place. A row leaf has the cache leaf's
    shape with a sequence extent of 1; that extent is the only axis on which
    the two differ, whatever the module's cache layout."""
    def one(c, r):
        starts = [0] * c.ndim
        for ax, (cs, rs) in enumerate(zip(c.shape, r.shape)):
            if cs != rs:
                starts[ax] = pos
                break
        return jax.lax.dynamic_update_slice(c, r.astype(c.dtype), starts)

    return jax.tree.map(one, cache, rows)


# ----------------------------------------------------------------------------
# attention math: memory-efficient chunked softmax attention (pure jnp)
# ----------------------------------------------------------------------------

def chunked_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, *,
                      causal: bool = True, q_chunk: int = 512,
                      kv_chunk: int = 512,
                      scale: Optional[float] = None,
                      kv_positions: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    """Flash-style online-softmax attention in pure jnp (scan over chunks).

    q: (B, Hq, Sq, D); k, v: (B, Hkv, Skv, D), Hq % Hkv == 0. Queries sit at
    the END of the KV timeline. Memory is O(q_chunk * kv_chunk) per (B, H)
    instead of O(Sq * Skv) -- this is the differentiable jnp twin of
    kernels/perforated_attention.py (use that on TPU), and what the 32k/500k
    shape cells lower.

    kv_positions: original timeline positions of each KV row (used by herded
    KV-block perforation, where the KV sequence is a gathered subset); the
    causal mask compares against these instead of contiguous indices.
    """
    b, hq, sq, d = q.shape
    _, hkv, skv, _ = k.shape
    dv = v.shape[-1]   # v head dim may differ from qk head dim (MLA)
    assert hq % hkv == 0
    rep = hq // hkv
    scale = scale if scale is not None else 1.0 / math.sqrt(d)
    if kv_positions is None:
        kv_positions_arr = jnp.arange(skv)
        offset = skv - sq
    else:
        # kept-index set is STATIC (host numpy) -- herded perforation
        import numpy as _np
        kv_np = _np.asarray(kv_positions)
        kv_positions_arr = jnp.asarray(kv_np)
        offset = int(kv_np.max()) + 1 - sq

    q_chunk = min(q_chunk, sq)
    kv_chunk = min(kv_chunk, skv)
    nq = -(-sq // q_chunk)
    nkv = -(-skv // kv_chunk)
    # pad to whole chunks
    sq_p, skv_p = nq * q_chunk, nkv * kv_chunk
    qp = jnp.pad(q, ((0, 0), (0, 0), (0, sq_p - sq), (0, 0)))
    kp = jnp.pad(k, ((0, 0), (0, 0), (0, skv_p - skv), (0, 0)))
    vp = jnp.pad(v, ((0, 0), (0, 0), (0, skv_p - skv), (0, 0)))
    kvpos_p = jnp.pad(kv_positions_arr, (0, skv_p - skv),
                      constant_values=2 ** 30)  # padding: always masked
    if rep > 1:
        kp = jnp.repeat(kp, rep, axis=1)
        vp = jnp.repeat(vp, rep, axis=1)

    qs = qp.reshape(b, hq, nq, q_chunk, d).transpose(2, 0, 1, 3, 4)
    ks = kp.reshape(b, hq, nkv, kv_chunk, d).transpose(2, 0, 1, 3, 4)
    vs = vp.reshape(b, hq, nkv, kv_chunk, dv).transpose(2, 0, 1, 3, 4)

    def q_block(iq, qc):
        # online softmax over kv chunks
        m0 = jnp.full((b, hq, q_chunk), -1e30, jnp.float32)
        l0 = jnp.zeros((b, hq, q_chunk), jnp.float32)
        a0 = jnp.zeros((b, hq, q_chunk, dv), jnp.float32)

        def kv_block(carry, inp):
            m_prev, l_prev, acc = carry
            ikv, kc, vc = inp
            logits = jnp.einsum("bhqd,bhkd->bhqk", qc, kc,
                                preferred_element_type=jnp.float32) * scale
            qi = iq * q_chunk + jnp.arange(q_chunk) + offset
            ki = jax.lax.dynamic_slice(kvpos_p, (ikv * kv_chunk,),
                                       (kv_chunk,))
            mask = ki[None, :] < 2 ** 30  # mask KV padding
            if causal:
                mask = mask & (ki[None, :] <= qi[:, None])
            logits = jnp.where(mask[None, None], logits, -1e30)
            row_max = jnp.max(logits, axis=-1)
            m_new = jnp.maximum(m_prev, row_max)
            # single masked materialization: exp(-1e30 - m) underflows to 0,
            # so the second where is only needed for fully-masked rows,
            # which the final l>0.5 guard already handles
            p = jnp.exp(logits - m_new[..., None])
            alpha = jnp.exp(m_prev - m_new)
            l_new = l_prev * alpha + jnp.sum(p, axis=-1)
            acc = acc * alpha[..., None] + jnp.einsum(
                "bhqk,bhkd->bhqd", p.astype(vc.dtype), vc,
                preferred_element_type=jnp.float32)
            return (m_new, l_new, acc), None

        (m, l, acc), _ = jax.lax.scan(
            kv_block, (m0, l0, a0),
            (jnp.arange(nkv), ks, vs))
        out = acc / jnp.maximum(l, 1e-30)[..., None]
        out = jnp.where((l > 0.5)[..., None], out, 0.0)
        return out.astype(q.dtype)

    outs = jax.lax.map(lambda args: q_block(*args),
                       (jnp.arange(nq), qs))              # (nq, B, H, qc, Dv)
    out = outs.transpose(1, 2, 0, 3, 4).reshape(b, hq, sq_p, dv)
    return out[:, :, :sq]


def full_attention(q, k, v, *, causal=True, scale=None):
    """Quadratic reference attention (small sequences / tests)."""
    b, hq, sq, d = q.shape
    _, hkv, skv, _ = k.shape
    rep = hq // hkv
    if rep > 1:
        k = jnp.repeat(k, rep, axis=1)
        v = jnp.repeat(v, rep, axis=1)
    scale = scale if scale is not None else 1.0 / math.sqrt(d)
    logits = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32),
                        k.astype(jnp.float32)) * scale
    if causal:
        offset = skv - sq
        qi = jnp.arange(sq)[:, None] + offset
        ki = jnp.arange(skv)[None, :]
        logits = jnp.where((ki <= qi)[None, None], logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", probs,
                      v.astype(jnp.float32)).astype(q.dtype)


def decode_attention(q, k, v, *, valid_len, scale=None, keep_mask=None,
                     k_new=None, v_new=None):
    """Single-token decode attention against a (possibly oversized) cache.

    q: (B, Hq, 1, D); k/v: (B, Hkv, S_cache, D); positions >= valid_len are
    masked; `keep_mask` (S_cache,) additionally masks perforated KV blocks
    (herded: the same mask for every batch/head). Linear in cache length.

    `k_new`/`v_new` (B, Hkv, 1, D), when given, are the token's own key and
    value at position `valid_len`, which the cache need not hold: they join
    the same softmax (its max and sum run over the cache's valid positions
    and the new row), so a decode step can read the cache in place and
    write its row afterwards.

    Distribution-aware form (section Perf iteration A1/A2): GQA is a grouped
    einsum -- the KV cache is NEVER head-repeated -- and the logits are
    constrained to stay sharded along the cache sequence axis, so a
    sequence-sharded cache is consumed locally (flash-decoding style) and
    only the tiny (B, Hkv, G) softmax partials and the (B, Hkv, G, Dv)
    context cross chips, instead of an all-gather of the whole cache.
    """
    b, hq, _, d = q.shape
    _, hkv, skv, _ = k.shape
    dv = v.shape[-1]
    group = hq // hkv
    scale = scale if scale is not None else 1.0 / math.sqrt(d)
    da = data_axes_hint()
    qg = q.reshape(b, hkv, group, d)                         # (B,Hkv,G,D)
    logits = jnp.einsum("bhgd,bhsd->bhgs", qg, k,
                        preferred_element_type=jnp.float32) * scale
    logits = shard_hint(logits, da, None, None, "model")
    mask = jnp.arange(skv)[None, None, None, :] < valid_len
    if keep_mask is not None:
        mask = mask & keep_mask[None, None, None, :]
    logits = jnp.where(mask, logits, -1e30)
    # stable softmax over the (sharded) S axis: partial max/sum reductions
    m = jnp.max(logits, axis=-1, keepdims=True)
    if k_new is not None:
        logit_new = jnp.einsum("bhgd,bhsd->bhgs", qg, k_new,
                               preferred_element_type=jnp.float32) * scale
        keep_new = True if keep_mask is None else keep_mask[valid_len]
        logit_new = jnp.where(keep_new, logit_new, -1e30)
        m = jnp.maximum(m, logit_new)
    p = jnp.exp(logits - m)
    p = jnp.where(mask, p, 0.0)
    l = jnp.sum(p, axis=-1, keepdims=True)
    ctx = jnp.einsum("bhgs,bhsd->bhgd", p.astype(v.dtype), v,
                     preferred_element_type=jnp.float32)
    if k_new is not None:
        p_new = jnp.where(keep_new, jnp.exp(logit_new - m), 0.0)
        l = l + p_new
        ctx = ctx + jnp.einsum("bhgs,bhsd->bhgd", p_new.astype(v_new.dtype),
                               v_new, preferred_element_type=jnp.float32)
    ctx = ctx / jnp.maximum(l, 1e-30)
    return ctx.reshape(b, hq, 1, dv).astype(q.dtype)
