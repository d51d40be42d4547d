"""Herded-perforated matmul Pallas kernel (paper section 3.1.5 on TPU).

Drops the SAME K-blocks of the contraction for every output tile. Because the
kept set is shared ("herded"), the grid is simply *shorter*: dropped blocks
are never scheduled, so -- unlike per-element (divergent) perforation, which
on a vector machine saves nothing -- the FLOP savings are structural:
executed_flops = kept/total * full_flops.

Two perforation modes share one kernel body (the same split as
``perforated_attention``):

  * **structural** (`fraction=None`): the kept-block list is computed on the
    host from the static `perfo` params and the grid enumerates ONLY the
    kept blocks -- dropped blocks are never scheduled (the herded payoff).
  * **masked** (`fraction=` a possibly-traced scalar; ini/fini/random
    kinds): the grid enumerates ALL K blocks and a per-block liveness
    vector -- computed in-trace from the traced fraction -- gates each
    block's accumulation under ``@pl.when``. The compiled program is shaped
    only by the block geometry, so a fraction sweep compiles once.

The kept-block list, liveness vector, and rescale factor arrive via TPU
scalar prefetch (``pltpu.PrefetchScalarGridSpec``): the index maps read
``kept_ref[kk]`` so the DMA engine fetches exactly the kept tiles; in
structural mode control flow stays perfectly uniform (liveness is all-ones,
so the ``@pl.when`` guard is compile-time foldable on the hot path).
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


def _perf_matmul_kernel(kept_ref, live_ref, factor_ref, x_ref, w_ref, o_ref,
                        acc_ref, *, n_enum: int):
    del kept_ref  # consumed by the index maps
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(live_ref[k] > 0)
    def _accum():
        acc_ref[...] += jnp.dot(x_ref[...].astype(jnp.float32),
                                w_ref[...].astype(jnp.float32),
                                preferred_element_type=jnp.float32)

    @pl.when(k == n_enum - 1)
    def _fini():
        o_ref[...] = (acc_ref[...] * factor_ref[0]).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=(
    "block_m", "block_n", "block_k", "perfo", "rescale", "out_dtype",
    "interpret", "pipeline"))
def perforated_matmul(x: jnp.ndarray, w: jnp.ndarray, *, block_m: int = 128,
                      block_n: int = 128, block_k: int = 128,
                      perfo: Optional[PerforationParams] = None,
                      fraction=None,
                      rescale: bool = False, out_dtype=jnp.float32,
                      interpret: bool = False,
                      pipeline: bool = False) -> jnp.ndarray:
    """Y ~= X @ W computing only the kept K-blocks (herded perforation).

    `fraction` is the traced-parameter hook: a (possibly traced) scalar
    overriding ``perfo.fraction`` for the fraction-driven kinds
    (ini/fini/random). When set, the kernel runs in MASKED mode -- the grid
    enumerates every K block and a liveness vector computed in-trace gates
    the dropped ones -- so the same compiled program serves any fraction.

    `pipeline=True` marks the two output-tile axes (i, j) "parallel" (the
    accumulator scratch only carries along the kk axis), letting Mosaic
    multi-buffer the next tile's operand DMA against the current tile's
    compute. Bit-identical outputs either way.
    """
    m, k = x.shape
    k2, n = w.shape
    if k != k2:
        raise ValueError(
            f"perforated_matmul contraction mismatch: x has K={k} columns "
            f"but w has K={k2} rows (x.shape={tuple(x.shape)}, "
            f"w.shape={tuple(w.shape)})")
    if m % block_m or n % block_n or k % block_k:
        raise ValueError(
            f"perforated_matmul block shape (block_m={block_m}, "
            f"block_n={block_n}, block_k={block_k}) does not divide the "
            f"operand geometry (M={m}, N={n}, K={k}): each block must "
            "divide its axis. kernels.tuning.search_space() enumerates "
            "only divisor-valid shapes for these operands.")
    nk = k // block_k
    if fraction is not None:
        if perfo is None or perfo.kind not in FRACTION_KINDS:
            raise ValueError(
                "fraction is a traced hook for ini/fini/random perforation; "
                f"got perfo={perfo}")
        # Masked mode: enumerate every K block; liveness is data.
        kept_arr = jnp.arange(nk, dtype=jnp.int32)
        live_arr = traced_execute_mask(nk, perfo, fraction).astype(jnp.int32)
        n_enum = nk
        n_live = jnp.maximum(jnp.sum(live_arr), 1).astype(jnp.float32)
        factor = (nk / n_live) if rescale else jnp.float32(1.0)
    else:
        kept = np.arange(nk) if perfo is None else kept_indices(nk, perfo)
        if len(kept) == 0:
            raise ValueError("perforation dropped every K block")
        kept_arr = jnp.asarray(kept, jnp.int32)
        live_arr = jnp.ones((len(kept),), jnp.int32)
        n_enum = len(kept)
        factor = (nk / n_enum) if rescale else 1.0
    factor_arr = jnp.asarray(factor, jnp.float32).reshape((1,))

    kernel = functools.partial(_perf_matmul_kernel, n_enum=n_enum)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(m // block_m, n // block_n, n_enum),
        in_specs=[
            pl.BlockSpec((block_m, block_k),
                         lambda i, j, kk, kept_ref, live_ref, factor_ref:
                         (i, kept_ref[kk])),
            pl.BlockSpec((block_k, block_n),
                         lambda i, j, kk, kept_ref, live_ref, factor_ref:
                         (kept_ref[kk], j)),
        ],
        out_specs=pl.BlockSpec((block_m, block_n),
                               lambda i, j, kk, kept_ref, live_ref, factor_ref:
                               (i, j)),
        scratch_shapes=[pltpu.VMEM((block_m, block_n), jnp.float32)],
    )
    # i and j tile independent outputs; only kk carries the accumulator
    # scratch. Interpret mode ignores compiler_params entirely.
    semantics = ("parallel", "parallel", "arbitrary") if pipeline else None
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((m, n), out_dtype),
        compiler_params=tuning.compiler_params(semantics),
        interpret=interpret,
    )(kept_arr, live_arr, factor_arr, x, w)
