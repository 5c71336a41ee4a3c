"""Public jit'd wrappers around the Pallas kernels.

Handles flattening/padding arbitrary tensors into (num_blocks, block) tiles,
threshold selection, event packing (26-bit-style wire words), and the
error-feedback compose used by the sparse collectives.

``interpret=None`` auto-selects via ``dispatch.resolve_interpret``:
compiled on a TPU, interpret mode on the CPU.  ``PALLAS_INTERPRET=1``
forces interpret mode everywhere — note it is read when a wrapper first
traces, so set it before the first call.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp

from ..core import events as ev
from . import ref
from .aer_decode import aer_decode_pallas
from .aer_encode import aer_encode_pallas
from .dispatch import resolve_interpret as _auto_interpret
from .fabric_queue import (fabric_queue_multistep_pallas,
                           fabric_queue_step_pallas,
                           fabric_queue_update_pallas)
from .lif_step import lif_step_pallas

DEFAULT_BLOCK = 1024
DEFAULT_BUDGET = 128


class EventBlocks(NamedTuple):
    """A compressed tensor: fixed-width AER event slots per block."""
    idx: jnp.ndarray     # (num_blocks, budget) i32, -1 = void
    val: jnp.ndarray     # (num_blocks, budget) float
    count: jnp.ndarray   # (num_blocks,) i32 — events emitted
    wanted: jnp.ndarray  # (num_blocks,) i32 — events over threshold

    @property
    def wire_words(self):
        """Packed uint32 wire words ((idx:16|bf16:16) — events.py format)."""
        return ev.pack_events(jnp.maximum(self.idx, 0), self.val)

    def wire_bytes(self):
        """Actual bytes on the wire under run-length framing: only `count`
        slots per block ship (void slots are never driven onto the bus)."""
        return jnp.sum(self.count) * 4 + self.count.shape[0] * 4


def pad_to_blocks(x: jnp.ndarray, block: int = DEFAULT_BLOCK):
    """Flatten + zero-pad to (num_blocks, block). Returns (tiles, orig_size)."""
    flat = x.reshape(-1)
    n = flat.shape[0]
    nb = max(1, -(-n // block))
    pad = nb * block - n
    flat = jnp.pad(flat, (0, pad))
    return flat.reshape(nb, block), n


def unpad_from_blocks(tiles: jnp.ndarray, orig_size: int, shape):
    return tiles.reshape(-1)[:orig_size].reshape(shape)


def tau_from_fraction(x_tiles: jnp.ndarray, frac: float):
    """Per-block threshold that keeps ~frac of entries (quantile of |x|)."""
    q = jnp.clip(1.0 - frac, 0.0, 1.0)
    return jnp.quantile(jnp.abs(x_tiles.astype(jnp.float32)), q, axis=1).astype(
        x_tiles.dtype)


def _pad_rows(x: jnp.ndarray, rows: int, fill=0):
    """Pad the leading axis up to a multiple of ``rows`` with ``fill``."""
    pad = -x.shape[0] % rows
    if not pad:
        return x
    return jnp.concatenate(
        [x, jnp.full((pad,) + x.shape[1:], fill, x.dtype)])


@functools.partial(jax.jit, static_argnames=("budget", "interpret",
                                             "rows_per_block", "use_ref"))
def aer_compress(x_tiles: jnp.ndarray, tau: jnp.ndarray,
                 budget: int = DEFAULT_BUDGET, *, interpret: bool | None = None,
                 rows_per_block: int = 8, use_ref: bool = False) -> EventBlocks:
    """Encode (num_blocks, block) tiles into event slots.

    The kernel runs ``rows_per_block`` rows per grid step (a multiple of
    8 on a TPU); zero rows pad the tail and ship no events."""
    if use_ref:
        out = ref.aer_encode(x_tiles, tau, budget)
    else:
        nb = x_tiles.shape[0]
        tau = jnp.asarray(tau, x_tiles.dtype).reshape(nb)
        out = aer_encode_pallas(
            _pad_rows(x_tiles, rows_per_block), _pad_rows(tau, rows_per_block),
            budget, rows_per_block=rows_per_block,
            interpret=_auto_interpret(interpret))
        out = tuple(o[:nb] for o in out)
    return EventBlocks(*out)


@functools.partial(jax.jit, static_argnames=("block", "interpret",
                                             "rows_per_block", "use_ref"))
def aer_decompress(events_: EventBlocks, block: int = DEFAULT_BLOCK, *,
                   interpret: bool | None = None, rows_per_block: int = 8,
                   use_ref: bool = False) -> jnp.ndarray:
    if use_ref:
        return ref.aer_decode(events_.idx, events_.val, block)
    nb = events_.idx.shape[0]
    return aer_decode_pallas(_pad_rows(events_.idx, rows_per_block, -1),
                             _pad_rows(events_.val, rows_per_block), block,
                             rows_per_block=rows_per_block,
                             interpret=_auto_interpret(interpret))[:nb]


def compress_with_feedback(x: jnp.ndarray, residual: jnp.ndarray, *,
                           frac: float = 0.05, budget: int = DEFAULT_BUDGET,
                           block: int = DEFAULT_BLOCK,
                           interpret: bool | None = None):
    """Error-feedback AER compression of one tensor.

    y = x + residual; events = encode(y); residual' = y - decode(events).
    Returns (EventBlocks, new_residual, orig_size).
    """
    y = x + residual
    tiles, n = pad_to_blocks(y, block)
    tau = tau_from_fraction(tiles, frac)
    events_ = aer_compress(tiles, tau, budget, interpret=interpret)
    dec = aer_decompress(events_, block, interpret=interpret)
    new_res = unpad_from_blocks(tiles - dec, n, x.shape)
    return events_, new_res, n


@functools.partial(jax.jit, static_argnames=("interpret", "use_ref",
                                             "rows_per_block"))
def fabric_queue_scan(q_time: jnp.ndarray, q_dest: jnp.ndarray,
                      t_q: jnp.ndarray, *,
                      interpret: bool | None = None, use_ref: bool = False,
                      rows_per_block: int = 8):
    """Fused per-queue released-count / min-release / next-arrival /
    argmin-pop / backlog-indicator / head-route over (Q, C) slot arrays
    (the fabric engine's O(C) step).

    Returns ``(pend, r_min, nxt, amin, busy, head_route)``, each (Q,)
    int32.

    vmap-compatible: under a batched fabric run (``Fabric.run_batch``
    with ``engine="pallas"``) the leading ``(B,)`` instance axis lowers
    through ``pallas_call``'s batching rule as an extra grid dimension —
    B independent (Q, C) scans in one kernel launch, bit-exact with the
    solo calls (interpret mode included; asserted by the batch tests).
    """
    if use_ref:
        return ref.fabric_queue_scan(q_time, q_dest, t_q)
    return fabric_queue_step_pallas(
        q_time, q_dest, t_q,
        rows_per_block=rows_per_block,
        interpret=_auto_interpret(interpret))


@functools.partial(jax.jit, static_argnames=("interpret", "use_ref",
                                             "rows_per_block"))
def fabric_queue_update(q_time, q_dest, q_inj, pop_q, pop_slot,
                        app_q, app_slot, app_t, app_dest, app_inj, *,
                        interpret: bool | None = None, use_ref: bool = False,
                        rows_per_block: int = 8):
    """Fused pop-consume + forward-append scatter on the slot arrays.

    Queue ids >= Q skip the lane (no pop / dropped forward); the append
    lanes may outnumber the pop lanes (in-fabric multicast replication:
    L·K candidate copies for L pops).  Returns the updated
    ``(q_time, q_dest, q_inj)``.  vmap-compatible like
    :func:`fabric_queue_scan` — per-instance queue/slot ids need no
    offsetting because each batch member scatters into its own (Q, C)
    slice.
    """
    if use_ref:
        return ref.fabric_queue_update(q_time, q_dest, q_inj, pop_q,
                                       pop_slot, app_q, app_slot, app_t,
                                       app_dest, app_inj)
    return fabric_queue_update_pallas(
        q_time, q_dest, q_inj, pop_q, pop_slot,
        app_q, app_slot, app_t, app_dest, app_inj,
        rows_per_block=rows_per_block,
        interpret=_auto_interpret(interpret))


def fabric_queue_multistep(carry, consts, base, *, step_fn, chunk: int,
                           max_steps: int, interpret: bool | None = None,
                           use_ref: bool = False):
    """Fused multi-step fabric loop: ``chunk`` micro-transactions per
    kernel launch, carry resident across steps (vs. 2 launches + a full
    state round-trip per step on the per-step path).

    Not jitted here — the engine (``core.network._slot_run_multistep``)
    calls it inside its own jitted chunk scan, and ``step_fn`` is a
    per-engine closure (jit static-arg hashing by closure identity
    would defeat the cache).
    """
    if use_ref:
        return ref.fabric_queue_multistep(carry, consts, base,
                                          step_fn=step_fn, chunk=chunk,
                                          max_steps=max_steps)
    return fabric_queue_multistep_pallas(carry, consts, base,
                                         step_fn=step_fn, chunk=chunk,
                                         max_steps=max_steps,
                                         interpret=interpret)


def lif_step(v: jnp.ndarray, i_syn: jnp.ndarray, *, decay: float = 0.9,
             v_th: float = 1.0, v_reset: float = 0.0,
             interpret: bool | None = None, use_ref: bool = False):
    """Fused LIF update on (rows, lanes) state."""
    if use_ref:
        return ref.lif_step(v, i_syn, decay, v_th, v_reset)
    rows = v.shape[0]
    br = 8
    while rows % br:
        br //= 2
    return lif_step_pallas(v, i_syn, decay=decay, v_th=v_th, v_reset=v_reset,
                           block_rows=max(br, 1),
                           interpret=_auto_interpret(interpret))
