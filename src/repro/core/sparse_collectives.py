"""Event-sparse collectives: the Address-Event Representation applied to
gradient synchronization (paper technique, layer 1).

AER's economy: transmit (address, value) only for *active* entries, so wire
traffic scales with activity, not tensor size.  ``aer_allreduce`` is the DP
gradient sync built on that idea:

  1. add the error-feedback residual to the local gradient shard;
  2. threshold-encode each (num_blocks × block) tile into fixed-budget
     event slots (Pallas kernel ``kernels/aer_encode``) — the threshold is
     the per-block |g| quantile for the target fraction;
  3. all-gather the event slots over the DP axis (the only cross-device
     traffic: ``budget/block`` of the dense payload);
  4. decode every peer's events (``kernels/aer_decode``) and sum into the
     dense result;
  5. keep what did not ship as the next step's residual (the FIFO
     back-pressure analogue — nothing is lost, only delayed).

Runs inside ``shard_map`` over the DP axis.  Also provides the dense
baselines and the wire-volume accounting used by benchmarks/tables.
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from ..kernels import ops as K
from . import halfduplex as hd


class AerState(NamedTuple):
    """Per-tensor error-feedback residual (same shape as the gradient)."""
    residual: jnp.ndarray

    @classmethod
    def init(cls, x):
        return cls(residual=jnp.zeros_like(x))


def aer_allreduce(x, state: AerState, axis_name, *, frac=0.02,
                  budget=K.DEFAULT_BUDGET, block=K.DEFAULT_BLOCK,
                  interpret=None):
    """Event-sparse all-*mean* of ``x`` over ``axis_name``.

    Returns (dense mean-reduced tensor — identical on all axis members,
    new AerState, wire_words_sent scalar).
    """
    n = jax.lax.axis_size(axis_name)
    y = x + state.residual
    tiles, size = K.pad_to_blocks(y, block)
    tau = K.tau_from_fraction(tiles, frac)
    ev = K.aer_compress(tiles, tau, budget, interpret=interpret)

    # the wire: fixed-width event slots, all-gathered over the DP axis
    all_idx = jax.lax.all_gather(ev.idx, axis_name)    # (n, nb, budget)
    all_val = jax.lax.all_gather(ev.val, axis_name)

    # decode the peers one at a time into one accumulator: an (nb, block)
    # buffer, not n of them, beside the gradient
    def add_peer(acc, iv):
        dec = K.aer_decompress(K.EventBlocks(*iv, ev.count, ev.wanted),
                               block, interpret=interpret)
        return acc + dec, None

    summed, _ = jax.lax.scan(add_peer, jnp.zeros_like(tiles),
                             (all_idx, all_val))
    summed = summed / n

    own_dec = K.aer_decompress(ev, block, interpret=interpret)
    new_residual = K.unpad_from_blocks(tiles - own_dec, size, x.shape)
    reduced = K.unpad_from_blocks(summed, size, x.shape)
    wire_words = jnp.sum(ev.count)
    return reduced, AerState(residual=new_residual), wire_words


def dense_allreduce(x, axis_name, *, schedule="psum"):
    """Dense mean baselines: psum | ring | bidir_ring."""
    n = jax.lax.axis_size(axis_name)
    if schedule == "psum":
        return jax.lax.psum(x, axis_name) / n
    return hd.ring_allreduce(
        x, axis_name, bidirectional=(schedule == "bidir_ring")) / n


def reduce_gradients(grads, aer_states, axis_name, *, mode="psum",
                     frac=0.02, budget=K.DEFAULT_BUDGET, interpret=None):
    """Tree-wise DP gradient reduction with selectable schedule.

    mode: psum | ring | bidir_ring | aer_topk.
    Returns (grads, new_aer_states, wire_words_total).
    """
    if mode in ("psum", "ring", "bidir_ring"):
        out = jax.tree.map(
            lambda g: dense_allreduce(g, axis_name, schedule=mode), grads)
        return out, aer_states, jnp.int32(0)

    assert mode == "aer_topk", mode
    leaves, treedef = jax.tree.flatten(grads)
    st_leaves = treedef.flatten_up_to(aer_states)
    outs, states, words = [], [], jnp.int32(0)
    for g, st in zip(leaves, st_leaves):
        r, ns, w = aer_allreduce(g, st, axis_name, frac=frac, budget=budget,
                                 interpret=interpret)
        outs.append(r)
        states.append(ns)
        words = words + w
    return (jax.tree.unflatten(treedef, outs),
            jax.tree.unflatten(treedef, states), words)


def init_aer_states(grads_or_params):
    return jax.tree.map(AerState.init, grads_or_params)


# ---------------------------------------------------------------------------
# Wire-volume accounting (benchmarks; the paper's "I/O saved" in bytes)
# ---------------------------------------------------------------------------

def dense_allreduce_bytes(n_params: int, n_devices: int, bytes_per=4,
                          bidirectional=False) -> float:
    return hd.wire_bytes_per_direction(n_params * bytes_per, n_devices,
                                       bidirectional)


def aer_allreduce_bytes(n_params: int, n_devices: int, frac: float,
                        budget: int = K.DEFAULT_BUDGET,
                        block: int = K.DEFAULT_BLOCK) -> float:
    """All-gather of event slots: each device ships nb*budget words once
    around the ring ((n-1)/n of it per link direction)."""
    nb = -(-n_params // block)
    shipped = min(budget, int(frac * block) + 1) * nb * 4
    return (n_devices - 1) / n_devices * shipped * n_devices / n_devices
