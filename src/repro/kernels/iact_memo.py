"""iACT input-memoized row function Pallas kernel (paper sections 3.1.4, 3.3).

The approximated region is an FFN tile y = gelu(x @ w1) @ w2 applied to rows
of x -- the archetypal "expensive device function" of paper Figure 5. Rows
are processed in blocks of `block_rows` by a sequential TPU grid; the memo
table is VMEM scratch (the paper's shared-memory table, sized by the block,
not by N -- the Figure 3 capacity argument: table bytes =
table_size*(d_in+d_out)*4, independent of N).

Faithful mechanics:
  * read phase: all rows probe the table (vectorized distance computation);
  * block-level majority-rules vote (ballot/popcount == masked sum);
  * approximate path: one-hot x table -> nearest cached outputs, the FFN
    matmuls are genuinely skipped via ``@pl.when``;
  * accurate path + write phase: a SINGLE writer -- the row with the largest
    distance from any table value -- inserts at the round-robin cursor.

The distance threshold is a **traced** scalar-prefetch operand: only the
structural parameters (block_rows, table_size, layer widths) shape the
compiled program, so a threshold sweep compiles once per structural group
and stacked thresholds ``jax.vmap`` straight through (docs/kernels.md).

Unlike the other hot kernels this one has NO ``pipeline=`` variant: its
grid is a single sequential axis and the memo table (keys/vals/meta
scratch) carries across *every* block -- there is no state-free axis to
mark "parallel", so DMA/compute overlap cannot be exposed through
``dimension_semantics`` here (docs/kernels.md "Block-shape autotuning &
DMA pipelining").
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import tuning

_BIG = 3.4e38  # python float: jnp constants would be captured by the kernel


def _iact_kernel(thresh_ref, x_ref, w1_ref, w2_ref, o_ref, mask_ref,
                 keys_ref, vals_ref, meta_ref, *, table_size: int):
    b = pl.program_id(0)
    threshold = thresh_ref[0]

    @pl.when(b == 0)
    def _reset():
        meta_ref[0] = 0  # round-robin cursor
        meta_ref[1] = 0  # number of valid entries
        keys_ref[...] = jnp.zeros_like(keys_ref)
        vals_ref[...] = jnp.zeros_like(vals_ref)
        mask_ref[...] = jnp.zeros_like(mask_ref)

    x = x_ref[...].astype(jnp.float32)                       # (R, d_in)
    keys = keys_ref[...]                                     # (T, d_in)
    n_valid = meta_ref[1]
    # read phase: squared euclidean distances (monotone in the paper's norm)
    diff = x[:, None, :] - keys[None, :, :]                  # (R, T, d_in)
    d2 = jnp.sum(diff * diff, axis=-1)                       # (R, T)
    slot_valid = jax.lax.broadcasted_iota(jnp.int32, d2.shape, 1) < n_valid
    d2 = jnp.where(slot_valid, d2, _BIG)
    min_d2 = jnp.min(d2, axis=1)                             # (R,)
    best = jnp.argmin(d2, axis=1)                            # (R,)
    hit = jnp.logical_and(min_d2 < threshold * threshold, n_valid > 0)
    n_rows = x.shape[0]
    approximate = jnp.sum(hit.astype(jnp.int32)) * 2 > n_rows  # majority
    step = jax.lax.broadcasted_iota(jnp.int32, mask_ref.shape, 1)
    mask_ref[...] = jnp.where(step == b, approximate.astype(jnp.int32),
                              mask_ref[...])

    @pl.when(approximate)
    def _approx_path():
        # nearest cached outputs via one-hot matmul (TPU-friendly gather)
        onehot = (best[:, None] ==
                  jax.lax.broadcasted_iota(jnp.int32, (n_rows, table_size), 1))
        out = jnp.dot(onehot.astype(jnp.float32), vals_ref[...],
                      preferred_element_type=jnp.float32)
        o_ref[...] = out.astype(o_ref.dtype)

    @pl.when(jnp.logical_not(approximate))
    def _accurate_path():
        h = jnp.dot(x, w1_ref[...].astype(jnp.float32),
                    preferred_element_type=jnp.float32)
        h = jax.nn.gelu(h)
        y = jnp.dot(h, w2_ref[...].astype(jnp.float32),
                    preferred_element_type=jnp.float32)
        o_ref[...] = y.astype(o_ref.dtype)
        # write phase -- single writer: farthest row from any cached value
        score = jnp.where(min_d2 >= _BIG, _BIG, min_d2)
        writer = jnp.argmax(score)
        wsel = (jax.lax.broadcasted_iota(jnp.int32, (n_rows, 1), 0) == writer)
        wx = jnp.sum(jnp.where(wsel, x, 0.0), axis=0)        # (d_in,)
        wy = jnp.sum(jnp.where(wsel, y, 0.0), axis=0)        # (d_out,)
        cursor = meta_ref[0]
        keys_ref[pl.dslice(cursor, 1), :] = wx[None, :]
        vals_ref[pl.dslice(cursor, 1), :] = wy[None, :]
        meta_ref[0] = jax.lax.rem(cursor + 1, table_size)
        meta_ref[1] = jnp.minimum(n_valid + 1, table_size)


@functools.partial(jax.jit, static_argnames=(
    "block_rows", "table_size", "out_dtype", "interpret"))
def iact_rowfn(x: jnp.ndarray, w1: jnp.ndarray, w2: jnp.ndarray, *,
               block_rows: int = 128, table_size: int = 4,
               threshold=0.5, out_dtype=jnp.float32,
               interpret: bool = False) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Returns (y (N, d_out), block_approx_mask (num_blocks,) bool).

    `threshold` may be a Python float or a traced scalar: it rides in scalar
    memory and never shapes the compiled program.
    """
    n, d_in = x.shape
    d_h = w1.shape[1]
    d_out = w2.shape[1]
    if w1.shape[0] != d_in or w2.shape[0] != d_h:
        raise ValueError(
            f"iact_rowfn layer width mismatch: x is (N={n}, d_in={d_in}) so "
            f"w1 must be (d_in, d_h) and w2 (d_h, d_out); got "
            f"w1.shape={tuple(w1.shape)}, w2.shape={tuple(w2.shape)}")
    if n % block_rows:
        raise ValueError(
            f"iact_rowfn block_rows={block_rows} does not divide the row "
            f"count N={n}: the sequential grid needs whole row blocks. "
            "kernels.tuning.search_space() enumerates only divisor-valid "
            "shapes for these operands.")
    num_b = n // block_rows
    need = tuning.vmem_bytes(
        "iact_rowfn", (x.shape, w1.shape, w2.shape),
        {"block_rows": block_rows}, itemsize=jnp.dtype(w1.dtype).itemsize,
        table_size=table_size)
    if need > tuning.VMEM_BUDGET_BYTES:
        raise ValueError(
            f"iact_rowfn needs ~{need / 2 ** 20:.1f} MiB of VMEM for "
            f"d_in={d_in}, d_h={d_h}, d_out={d_out}, block_rows="
            f"{block_rows} in {jnp.dtype(w1.dtype).name}, over the "
            f"{tuning.VMEM_BUDGET_BYTES // 2 ** 20} MiB limit "
            "(kernels.tuning.VMEM_BUDGET_BYTES): both weights are held "
            "whole in VMEM, so narrow d_h or use bf16 weights.")

    thresh = jnp.asarray(threshold, jnp.float32).reshape((1,))
    kernel = functools.partial(_iact_kernel, table_size=table_size)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(num_b,),
        in_specs=[
            pl.BlockSpec((block_rows, d_in), lambda b, thresh_ref: (b, 0)),
            pl.BlockSpec((d_in, d_h), lambda b, thresh_ref: (0, 0)),
            pl.BlockSpec((d_h, d_out), lambda b, thresh_ref: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((block_rows, d_out), lambda b, thresh_ref: (b, 0)),
            # every block's flag in one resident block, each step setting
            # its own entry: a (1,) block breaks Mosaic's tiling rule
            pl.BlockSpec((1, num_b), lambda b, thresh_ref: (0, 0)),
        ],
        scratch_shapes=[
            pltpu.VMEM((table_size, d_in), jnp.float32),
            pltpu.VMEM((table_size, d_out), jnp.float32),
            pltpu.SMEM((2,), jnp.int32),
        ],
    )
    y, mask = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((n, d_out), out_dtype),
            jax.ShapeDtypeStruct((1, num_b), jnp.int32),
        ],
        compiler_params=tuning.compiler_params(None),
        interpret=interpret,
    )(thresh, x, w1, w2)
    return y, mask[0].astype(bool)
