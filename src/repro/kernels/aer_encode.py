"""Pallas TPU kernel: AER event encoder (TX path of the transceiver).

Selects |x| >= tau entries of each block and compacts them into fixed-width
event slots.  TPU adaptation notes (vs. a GPU stream-compaction kernel):

* Mosaic has no ``cumsum`` and no scatter.  The running count of
  selected entries is a matmul of the 0/1 mask with an upper-triangular
  ones matrix on the MXU (bf16 operands, f32 accumulation: exact for
  counts up to 2**24).  Slot ``e`` of a row then receives the entry
  whose count is ``e + 1`` as a one-hot masked reduction over the
  (budget, block) plane — a sum of exactly one term, so values and
  indices are bit-exact whatever the matmul precision.
* The per-block budget keeps shapes static (SPMD-friendly); overflow beyond
  the budget is deliberately left in place for the caller's error-feedback
  residual — the AER analogue of FIFO back-pressure.
* Row blocks are multiples of 8 rows (the sublane tile) or the whole
  array; per-row scalars (``tau``, ``count``, ``wanted``) are (rows, 1)
  columns.  VMEM per grid step (rows_per_block=8, block=1024,
  budget=128): the triangular matrix 2 MiB + one (budget, block) plane
  512 KiB per row.

Validated against ``ref.aer_encode`` in interpret mode on the CPU and
compiled for a TPU v5e by ``tests/test_tpu_compile.py``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .dispatch import resolve_interpret


def _encode_kernel(x_ref, tau_ref, idx_ref, val_ref, count_ref, wanted_ref,
                   *, budget: int):
    x = x_ref[...]                      # (rows, block)
    tau = tau_ref[...]                  # (rows, 1)
    rows, block = x.shape

    # zeros never ship (AER: no activity, no event) — see ref.aer_encode
    mask = (jnp.abs(x) >= tau) & (x != 0)
    tri = (jax.lax.broadcasted_iota(jnp.int32, (block, block), 0)
           <= jax.lax.broadcasted_iota(jnp.int32, (block, block), 1))
    csum = jax.lax.dot_general(
        mask.astype(jnp.bfloat16), tri.astype(jnp.bfloat16),
        (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32).astype(jnp.int32)
    # slot of each selected entry (-1: not selected or over budget)
    dest = jnp.where(mask & (csum <= budget), csum - 1, -1)

    iota_e = jax.lax.broadcasted_iota(jnp.int32, (budget, block), 0)
    iota_b = jax.lax.broadcasted_iota(jnp.int32, (budget, block), 1)
    xf = x.astype(jnp.float32)
    idx_rows, val_rows = [], []
    for r in range(rows):
        hit = dest[r:r + 1, :] == iota_e            # (budget, block)
        idx_rows.append(jnp.sum(jnp.where(hit, iota_b + 1, 0), axis=1) - 1)
        val_rows.append(jnp.sum(jnp.where(hit, xf[r:r + 1, :], 0.0),
                                axis=1))
    idx_ref[...] = jnp.stack(idx_rows)
    val_ref[...] = jnp.stack(val_rows).astype(val_ref.dtype)
    wanted = csum[:, block - 1:]
    wanted_ref[...] = wanted
    count_ref[...] = jnp.minimum(wanted, budget)


def aer_encode_pallas(x: jnp.ndarray, tau: jnp.ndarray, budget: int,
                      *, rows_per_block: int = 8,
                      interpret: bool | str | None = None):
    """x: (num_blocks, block) float; tau: (num_blocks,) float.

    Returns (idx i32, val x.dtype, count i32, wanted i32) with event slots
    (num_blocks, budget).  ``rows_per_block`` must divide num_blocks; a
    compiled TPU kernel also needs it to be a multiple of 8 or all rows.
    """
    nb, block = x.shape
    assert nb % rows_per_block == 0, (nb, rows_per_block)
    grid = (nb // rows_per_block,)

    kernel = functools.partial(_encode_kernel, budget=budget)
    slots = pl.BlockSpec((rows_per_block, budget), lambda i: (i, 0))
    col = pl.BlockSpec((rows_per_block, 1), lambda i: (i, 0))
    idx, val, count, wanted = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[pl.BlockSpec((rows_per_block, block), lambda i: (i, 0)),
                  col],
        out_specs=[slots, slots, col, col],
        out_shape=[
            jax.ShapeDtypeStruct((nb, budget), jnp.int32),
            jax.ShapeDtypeStruct((nb, budget), x.dtype),
            jax.ShapeDtypeStruct((nb, 1), jnp.int32),
            jax.ShapeDtypeStruct((nb, 1), jnp.int32),
        ],
        interpret=resolve_interpret(interpret),
    )(x, jnp.asarray(tau, x.dtype).reshape(nb, 1))
    return idx, val, count[:, 0], wanted[:, 0]
