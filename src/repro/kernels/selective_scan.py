"""Pallas TPU kernel: fused Mamba selective scan.

The S6 recurrence  h_t = exp(dt_t·A)·h_t−1 + (dt_t·x_t)·B_t ;  y_t = h_t·C_t
is memory-bound when staged through HBM (the chunked-jnp path materializes
(B, chunk, d_inner, N) discretization tensors per chunk).  This kernel keeps
the ENTIRE state trajectory in VMEM: one grid step owns a (d_block × N)
state tile and walks the full sequence with a ``fori_loop``, reading one
(d_block,) x/dt lane-row and one (N,) B/C row per step, writing one y row.
HBM traffic collapses to the operands + outputs (no intermediate tensors).

Grid: (batch, d_inner / d_block).  VMEM per step (defaults d_block=512,
N=16, S≤4096): x/dt tiles 2·S·d_block·4B ≈ 16 MiB at S=4096/d_block=512 —
choose d_block so the tile fits (the wrapper auto-shrinks); state tile
512×16×4 = 32 KiB.  d_inner is TP-sharded over the model axis, so per-core
sequences see d_inner/16 lanes — d_block=512 covers falcon-mamba exactly.

Validated in interpret mode vs ``ref.selective_scan_ref`` and the
production chunked-associative-scan path (tests/test_kernels_scan.py).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .dispatch import resolve_interpret


def _scan_kernel(x_ref, dt_ref, b_ref, c_ref, a_ref, y_ref, h_ref):
    S = x_ref.shape[1]
    d_blk = x_ref.shape[2]
    N = a_ref.shape[1]
    A = a_ref[...]                       # (d_blk, N)

    def body(t, h):
        dtv = dt_ref[0, t, :]            # (d_blk,)
        xv = x_ref[0, t, :]
        bv = b_ref[0, t, :]              # (N,)
        cv = c_ref[0, t, :]
        abar = jnp.exp(dtv[:, None] * A)
        bx = (dtv * xv)[:, None] * bv[None, :]
        h = abar * h + bx                # (d_blk, N)
        y_ref[0, t, :] = (h * cv[None, :]).sum(axis=-1)
        return h

    h = jax.lax.fori_loop(0, S, body,
                          jnp.zeros((d_blk, N), jnp.float32))
    h_ref[0] = h


def selective_scan_pallas(x, dt, b_ssm, c_ssm, a, *, d_block: int = 512,
                          interpret: bool | str | None = None):
    """x, dt: (B, S, d_in) f32; b_ssm/c_ssm: (B, S, N); a: (d_in, N).

    Returns (y: (B, S, d_in) f32, h_final: (B, d_in, N) f32).
    """
    B, S, d_in = x.shape
    N = a.shape[1]
    while d_in % d_block:
        d_block //= 2
    grid = (B, d_in // d_block)

    return pl.pallas_call(
        _scan_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, S, d_block), lambda i, j: (i, 0, j)),
            pl.BlockSpec((1, S, d_block), lambda i, j: (i, 0, j)),
            pl.BlockSpec((1, S, N), lambda i, j: (i, 0, 0)),
            pl.BlockSpec((1, S, N), lambda i, j: (i, 0, 0)),
            pl.BlockSpec((d_block, N), lambda i, j: (j, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, S, d_block), lambda i, j: (i, 0, j)),
            pl.BlockSpec((1, d_block, N), lambda i, j: (i, j, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, S, d_in), jnp.float32),
            jax.ShapeDtypeStruct((B, d_in, N), jnp.float32),
        ],
        interpret=resolve_interpret(interpret),
    )(x, dt, b_ssm, c_ssm, a)
