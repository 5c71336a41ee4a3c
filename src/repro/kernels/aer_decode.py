"""Pallas TPU kernel: AER event decoder (RX path of the transceiver).

Accumulates fixed-width event slots back into a dense block:
``dense[r, b] = sum_e [idx[r, e] == b] * val[r, e]``, one row at a time
as a masked reduction over the (block, budget) plane (Mosaic has no
scatter); duplicate addresses therefore sum naturally (the AER
semantics — two spikes at one address are two contributions), and a
single event lands bit-exactly.

VMEM per grid step (rows_per_block=8, budget=128, block=1024): one
(block, budget) plane 512 KiB per row.  idx == -1 marks a void slot
(matches no address).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .dispatch import resolve_interpret


def _decode_kernel(idx_ref, val_ref, out_ref):
    idx = idx_ref[...]                  # (rows, budget) i32
    val = val_ref[...].astype(jnp.float32)
    rows, budget = idx.shape
    block = out_ref.shape[-1]

    iota_b = jax.lax.broadcasted_iota(jnp.int32, (block, budget), 0)
    dense = [jnp.sum(jnp.where(idx[r:r + 1, :] == iota_b,
                               val[r:r + 1, :], 0.0), axis=1)
             for r in range(rows)]
    out_ref[...] = jnp.stack(dense).astype(out_ref.dtype)


def aer_decode_pallas(idx: jnp.ndarray, val: jnp.ndarray, block: int,
                      *, rows_per_block: int = 8,
                      interpret: bool | str | None = None):
    """idx/val: (num_blocks, budget); returns dense (num_blocks, block)."""
    nb, budget = idx.shape
    assert nb % rows_per_block == 0, (nb, rows_per_block)
    grid = (nb // rows_per_block,)

    return pl.pallas_call(
        _decode_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((rows_per_block, budget), lambda i: (i, 0)),
            pl.BlockSpec((rows_per_block, budget), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((rows_per_block, block), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((nb, block), val.dtype),
        interpret=resolve_interpret(interpret),
    )(idx, val)
