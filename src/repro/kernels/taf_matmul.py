"""TAF-memoized matmul Pallas kernel (paper sections 3.1.1, 3.1.3 on TPU).

Y = X @ W over a (num_j, num_i) grid of (block_m, block_n) output tiles.
TPU Pallas grids execute **sequentially** on a core, so for a fixed column
block j the row blocks i = 0..num_i-1 form exactly the paper's grid-stride
temporal sequence (Figure 4d), and VMEM/SMEM scratch is the paper's
"shared memory" AC state (section 3.1.1): its size depends on the block shape,
never on the total number of logical iterations.

State (per column block; reset when i wraps to 0, i.e. kernel-lifetime scope):
  window    VMEM (1, history_size) -- last accurate block-mean outputs, a
                                      ring buffer (mean and deviation do not
                                      depend on the order of its entries)
  counters  SMEM (3,)              -- [filled, remaining, ring cursor]
  memo      VMEM (block_m, block_n) -- last accurate block output

The approximation mask is stored column-block-major, (num_j, 1, num_i): each
column block's row of flags is one block that stays resident while i runs,
and a step sets its own entry with a select against an iota. A (1, 1) block
per step would break Mosaic's (8, 128) tiling rule.

The decision is **block-level** (paper `level(team)`): a scalar predicate
drives ``@pl.when``, so an approximated tile genuinely skips its MXU dot --
the divergence-free fast path that element-level masking cannot give on a
vector machine (DESIGN.md section 2).

The RSD threshold is a **traced** scalar-prefetch operand, not a static jit
argument: the compiled program is shaped only by the structural parameters
(block shape, history/prediction sizes), so a threshold sweep reuses one
executable per structural group and a batched runner can ``jax.vmap``
stacked thresholds straight through the kernel (docs/kernels.md).
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import tuning


def _taf_matmul_kernel(thresh_ref, x_ref, w_ref, o_ref, mask_ref,
                       window_ref, counters_ref, memo_ref, *,
                       history_size: int, prediction_size: int):
    i = pl.program_id(1)  # row block (fast axis) -- the temporal sequence
    rsd_threshold = thresh_ref[0]

    @pl.when(i == 0)
    def _reset():  # kernel-lifetime state scope, fresh per column block
        counters_ref[0] = 0  # filled
        counters_ref[1] = 0  # remaining
        counters_ref[2] = 0  # ring cursor into the window
        window_ref[...] = jnp.zeros_like(window_ref)
        mask_ref[...] = jnp.zeros_like(mask_ref)

    remaining = counters_ref[1]
    approximate = remaining > 0
    step = jax.lax.broadcasted_iota(jnp.int32, mask_ref.shape, 2)
    mask_ref[...] = jnp.where(step == i, approximate.astype(jnp.int32),
                              mask_ref[...])

    @pl.when(approximate)
    def _approx_path():
        # Return the last accurately-computed output; no MXU work at all.
        o_ref[...] = memo_ref[...].astype(o_ref.dtype)
        counters_ref[1] = remaining - 1

    @pl.when(jnp.logical_not(approximate))
    def _accurate_path():
        y = jnp.dot(x_ref[...].astype(jnp.float32),
                    w_ref[...].astype(jnp.float32),
                    preferred_element_type=jnp.float32)
        o_ref[...] = y.astype(o_ref.dtype)
        memo_ref[...] = y
        # Overwrite the oldest window entry (hSize is tiny: 1..5). A select
        # against an iota, not a scatter: Mosaic lowers no scatter.
        s = jnp.mean(y)
        cursor = counters_ref[2]
        slot = jax.lax.broadcasted_iota(jnp.int32, window_ref.shape, 1)
        win = jnp.where(slot == cursor, s, window_ref[...])
        window_ref[...] = win
        counters_ref[2] = jax.lax.rem(cursor + 1, history_size)
        filled = jnp.minimum(counters_ref[0] + 1, history_size)
        counters_ref[0] = filled
        mu = jnp.mean(win)
        sigma = jnp.sqrt(jnp.maximum(jnp.mean(win * win) - mu * mu, 0.0))
        stable = (sigma / jnp.maximum(jnp.abs(mu), 1e-12) < rsd_threshold)
        stable = jnp.logical_and(stable, filled >= history_size)
        counters_ref[1] = jnp.where(stable, prediction_size, 0)


@functools.partial(jax.jit, static_argnames=(
    "block_m", "block_n", "history_size", "prediction_size",
    "out_dtype", "interpret", "pipeline"))
def taf_matmul(x: jnp.ndarray, w: jnp.ndarray, *, block_m: int = 128,
               block_n: int = 128, history_size: int = 3,
               prediction_size: int = 8, rsd_threshold=0.5,
               out_dtype=jnp.float32, interpret: bool = False,
               pipeline: bool = False) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Returns (y (M, N), approx_mask (num_i, num_j) int32).

    `rsd_threshold` may be a Python float or a traced scalar: it rides in
    scalar memory and never shapes the compiled program.

    `pipeline=True` marks the column-block axis j "parallel" (it carries no
    scratch state: window/counters/memo reset at i == 0 per column block),
    letting Mosaic multi-buffer the next tile's operand DMA against the
    current tile's compute. The temporal axis i stays "arbitrary" -- its
    scratch carry IS the TAF mechanism. Bit-identical outputs either way.
    """
    m, k = x.shape
    k2, n = w.shape
    if k != k2:
        raise ValueError(
            f"taf_matmul contraction mismatch: x has K={k} columns but w "
            f"has K={k2} rows (x.shape={tuple(x.shape)}, "
            f"w.shape={tuple(w.shape)})")
    if m % block_m or n % block_n:
        raise ValueError(
            f"taf_matmul block shape ({block_m}, {block_n}) does not divide "
            f"the output geometry ({m}, {n}): block_m must divide M={m} and "
            f"block_n must divide N={n}. kernels.tuning.search_space() "
            "enumerates only divisor-valid shapes for these operands.")
    num_i, num_j = m // block_m, n // block_n

    thresh = jnp.asarray(rsd_threshold, jnp.float32).reshape((1,))
    grid = (num_j, num_i)  # j slow, i fast: temporal sequence over row blocks
    kernel = functools.partial(
        _taf_matmul_kernel, history_size=history_size,
        prediction_size=prediction_size)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_m, k), lambda j, i, thresh_ref: (i, 0)),
            pl.BlockSpec((k, block_n), lambda j, i, thresh_ref: (0, j)),
        ],
        out_specs=[
            pl.BlockSpec((block_m, block_n), lambda j, i, thresh_ref: (i, j)),
            pl.BlockSpec((1, 1, num_i), lambda j, i, thresh_ref: (j, 0, 0)),
        ],
        scratch_shapes=[
            pltpu.VMEM((1, history_size), jnp.float32),
            pltpu.SMEM((3,), jnp.int32),
            pltpu.VMEM((block_m, block_n), jnp.float32),
        ],
    )
    # j carries no state across grid steps (scratch resets at i == 0 per
    # column block); i is the paper's temporal sequence and must stay
    # sequential. Interpret mode ignores compiler_params entirely.
    semantics = ("parallel", "arbitrary") if pipeline else None
    y, mask = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((m, n), out_dtype),
            jax.ShapeDtypeStruct((num_j, 1, num_i), jnp.int32),
        ],
        compiler_params=tuning.compiler_params(semantics),
        interpret=interpret,
    )(thresh, x, w)
    return y, mask[:, 0, :].T.astype(bool)
