"""Flash attention with herded KV-block perforation (paper section 3.1.5 -> TPU).

Online-softmax flash attention over a (B, H, num_q, n_enum) grid whose KV
dimension enumerates perforated context blocks: the same KV blocks are
dropped for every query tile, batch and head -- herded perforation. `ini`
drops the oldest context, `fini` the newest; `small`/`large` give strided
context sparsity. With `perfo=None` this is a standard causal
flash-attention kernel (our full-attention baseline), and with `ini`
fractions it degenerates into a sliding-window: the sub-quadratic mode used
by long-context configs.

Two perforation modes share one kernel body:

  * **structural** (`fraction=None`): the kept-block list is computed on the
    host from the static `perfo` params and the grid enumerates ONLY the
    kept blocks -- dropped blocks are never visited (the herded payoff).
  * **masked** (`fraction=` a possibly-traced scalar; ini/fini/random
    kinds): the grid enumerates ALL KV blocks and a per-block liveness
    vector -- computed in-trace from the traced fraction -- gates each
    block's work under ``@pl.when``. The compiled program is shaped only by
    the block geometry, so a fraction sweep compiles once and stacked
    fractions ``jax.vmap`` straight through (docs/kernels.md). This is the
    kernel-level analogue of `perforated_loop(fraction=...)`'s masked
    variant: blocks still iterate, their compute is skipped.

Both the kept-block list and the liveness vector arrive via TPU scalar
prefetch so index maps and the causal mask read ``kept_ref[kk]``. GQA is
handled in the index map (kv head = q head // group); no KV repeat is
materialized. Scratch m/l/acc implement the numerically-safe online
softmax; a causal early-out ``@pl.when`` skips KV blocks entirely above the
diagonal (uniform across the tile -> genuinely free, the same argument as
herding).
"""
from __future__ import annotations

import functools
from typing import Optional

import numpy as np
import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.perforation import (FRACTION_KINDS, kept_indices,
                                    traced_execute_mask)
from repro.core.types import PerforationParams
from . import tuning

_NEG = -1e30  # python float: jnp constants would be captured by the kernel


def _attn_kernel(kept_ref, live_ref, q_ref, k_ref, v_ref, o_ref,
                 m_ref, l_ref, acc_ref,
                 *, block_q: int, block_kv: int, offset: int, scale: float,
                 causal: bool, n_enum: int):
    iq = pl.program_id(2)
    kk = pl.program_id(3)
    kid = kept_ref[kk]

    @pl.when(kk == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, _NEG)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # causal early-out: KV block entirely above the diagonal for this q tile
    last_q_global = iq * block_q + offset + block_q - 1
    block_live = jnp.logical_or(
        jnp.asarray(not causal), kid * block_kv <= last_q_global)
    block_live = jnp.logical_and(block_live, live_ref[kk] > 0)

    @pl.when(block_live)
    def _process():
        q = q_ref[0, 0].astype(jnp.float32)                  # (bq, D)
        k = k_ref[0, 0].astype(jnp.float32)                  # (bk, D)
        v = v_ref[0, 0].astype(jnp.float32)                  # (bk, D)
        logits = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale
        if causal:
            qi = jax.lax.broadcasted_iota(jnp.int32, logits.shape, 0) + \
                iq * block_q + offset
            ki = jax.lax.broadcasted_iota(jnp.int32, logits.shape, 1) + \
                kid * block_kv
            mask = ki <= qi
            logits = jnp.where(mask, logits, _NEG)
        else:
            mask = jnp.ones(logits.shape, dtype=bool)
        m_prev = m_ref[:, 0]                                 # (bq,)
        row_max = jnp.max(logits, axis=1)
        m_new = jnp.maximum(m_prev, row_max)
        p = jnp.where(mask, jnp.exp(logits - m_new[:, None]), 0.0)
        alpha = jnp.exp(m_prev - m_new)
        l_new = l_ref[:, 0] * alpha + jnp.sum(p, axis=1)
        acc_ref[...] = acc_ref[...] * alpha[:, None] + \
            jnp.dot(p, v, preferred_element_type=jnp.float32)
        m_ref[:, 0] = m_new
        l_ref[:, 0] = l_new

    @pl.when(kk == n_enum - 1)
    def _finalize():
        l = l_ref[:, 0]
        safe = jnp.maximum(l, 1e-30)
        out = acc_ref[...] / safe[:, None]
        out = jnp.where((l > 0.5)[:, None], out, 0.0)  # fully-masked rows -> 0
        o_ref[0, 0] = out.astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=(
    "block_q", "block_kv", "perfo", "causal", "scale", "interpret",
    "pipeline"))
def perforated_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, *,
                         block_q: int = 128, block_kv: int = 128,
                         perfo: Optional[PerforationParams] = None,
                         fraction=None,
                         causal: bool = True,
                         scale: Optional[float] = None,
                         interpret: bool = False,
                         pipeline: bool = False) -> jnp.ndarray:
    """q: (B, Hq, Sq, D); k, v: (B, Hkv, Skv, D) with Hq % Hkv == 0.

    Returns (B, Hq, Sq, D) in q.dtype. Queries sit at the END of the KV
    timeline (offset = Skv - Sq), covering self-attention, chunked prefill
    and single-token decode.

    `fraction` is the traced-parameter hook: a (possibly traced) scalar
    overriding ``perfo.fraction`` for the fraction-driven kinds
    (ini/fini/random). When set, the kernel runs in MASKED mode -- the grid
    enumerates every KV block and a liveness vector computed in-trace gates
    the dropped ones -- so the same compiled program serves any fraction.

    `pipeline=True` marks the batch/head/query-tile axes "parallel" (the
    online-softmax scratch m/l/acc only carries along the kk axis),
    letting Mosaic multi-buffer the next KV tile's DMA against the current
    tile's compute. Bit-identical outputs either way.
    """
    b, hq, sq, d = q.shape
    _, hkv, skv, dk = k.shape
    if dk != d or v.shape != k.shape or hq % hkv:
        raise ValueError(
            f"perforated_attention operand mismatch: q is "
            f"(B, Hq, Sq, D)={tuple(q.shape)} so k and v must share "
            f"(B, Hkv, Skv, D) with D={d} and Hq % Hkv == 0; got "
            f"k.shape={tuple(k.shape)}, v.shape={tuple(v.shape)}")
    if sq % block_q or skv % block_kv:
        raise ValueError(
            f"perforated_attention block shape (block_q={block_q}, "
            f"block_kv={block_kv}) does not divide the sequence geometry "
            f"(Sq={sq}, Skv={skv}): block_q must divide Sq and block_kv "
            "must divide Skv. kernels.tuning.search_space() enumerates "
            "only divisor-valid shapes for these operands.")
    group = hq // hkv
    nkv = skv // block_kv
    if fraction is not None:
        if perfo is None or perfo.kind not in FRACTION_KINDS:
            raise ValueError(
                "fraction is a traced hook for ini/fini/random perforation; "
                f"got perfo={perfo}")
        # Masked mode: enumerate every KV block; liveness is data.
        kept_arr = jnp.arange(nkv, dtype=jnp.int32)
        live_arr = traced_execute_mask(nkv, perfo, fraction).astype(jnp.int32)
        n_enum = nkv
    else:
        kept = np.arange(nkv) if perfo is None else kept_indices(nkv, perfo)
        if len(kept) == 0:
            raise ValueError("perforation dropped every KV block")
        kept_arr = jnp.asarray(kept, jnp.int32)
        live_arr = jnp.ones((len(kept),), jnp.int32)
        n_enum = len(kept)
    offset = skv - sq
    scale = scale if scale is not None else float(1.0 / np.sqrt(d))

    kernel = functools.partial(
        _attn_kernel, block_q=block_q, block_kv=block_kv, offset=offset,
        scale=scale, causal=causal, n_enum=n_enum)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, hq, sq // block_q, n_enum),
        in_specs=[
            pl.BlockSpec((1, 1, block_q, d),
                         lambda bb, h, iq, kk, kept_ref, live_ref:
                         (bb, h, iq, 0)),
            pl.BlockSpec((1, 1, block_kv, d),
                         lambda bb, h, iq, kk, kept_ref, live_ref:
                         (bb, h // group, kept_ref[kk], 0)),
            pl.BlockSpec((1, 1, block_kv, d),
                         lambda bb, h, iq, kk, kept_ref, live_ref:
                         (bb, h // group, kept_ref[kk], 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, d),
                               lambda bb, h, iq, kk, kept_ref, live_ref:
                               (bb, h, iq, 0)),
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, d), jnp.float32),
        ],
    )
    # b, h, iq tile independent outputs; only kk carries the online-softmax
    # scratch. Interpret mode ignores compiler_params entirely.
    semantics = (("parallel", "parallel", "parallel", "arbitrary")
                 if pipeline else None)
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, hq, sq, d), q.dtype),
        compiler_params=tuning.compiler_params(semantics),
        interpret=interpret,
    )(kept_arr, live_arr, q, k, v)
