"""Pure-jnp oracles for every Pallas kernel in this package.

These define the semantics; kernels must match them bit-for-bit (integer
outputs) / to float tolerance (float outputs) in the per-kernel sweep tests.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


# ---------------------------------------------------------------------------
# aer_encode: block-local thresholded event extraction (the TX path).
#
# Given a (num_blocks, block) dense tensor and a per-block threshold tau,
# select entries with |x| >= tau in index order, keeping at most `budget`
# per block (overflow stays behind for the error-feedback residual), and
# emit fixed-width event slots:
#   idx[r, e]  = block-local index of the e-th selected entry, or -1
#   val[r, e]  = its value, or 0
#   count[r]   = number of entries selected AND emitted (<= budget)
#   wanted[r]  = number of entries over threshold (>= count)
# ---------------------------------------------------------------------------

def aer_encode(x: jnp.ndarray, tau: jnp.ndarray, budget: int):
    nb, blk = x.shape
    tau = jnp.broadcast_to(jnp.asarray(tau, x.dtype).reshape(-1, 1), (nb, 1))
    # AER semantics: no activity, no event — zeros never ship, even when the
    # threshold collapses to 0 (else they'd waste budget slots).
    mask = (jnp.abs(x) >= tau) & (x != 0)
    csum = jnp.cumsum(mask.astype(jnp.int32), axis=1)
    sel = mask & (csum <= budget)
    dest = csum - 1  # target slot for selected entries

    iota_e = jnp.arange(budget, dtype=jnp.int32)
    # one-hot scatter: slot e receives the entry whose dest == e
    onehot = (dest[:, :, None] == iota_e[None, None, :]) & sel[:, :, None]
    onehot_f = onehot.astype(jnp.float32)
    # HIGHEST: a one-hot contraction is exact only at full f32 precision
    # (a TPU's default rounds f32 matmul operands to bf16)
    val = jnp.einsum("rbe,rb->re", onehot_f, x.astype(jnp.float32),
                     precision=jax.lax.Precision.HIGHEST)
    iota_b = jnp.arange(blk, dtype=jnp.float32) + 1.0
    idx = jnp.einsum("rbe,b->re", onehot_f, iota_b,
                     precision=jax.lax.Precision.HIGHEST
                     ).astype(jnp.int32) - 1

    wanted = csum[:, -1]
    count = jnp.minimum(wanted, budget)
    return idx, val.astype(x.dtype), count, wanted


# ---------------------------------------------------------------------------
# aer_decode: event slots -> dense accumulation (the RX path).
# Duplicate addresses accumulate (sum semantics); idx == -1 slots are void.
# ---------------------------------------------------------------------------

def aer_decode(idx: jnp.ndarray, val: jnp.ndarray, block: int):
    nb, budget = idx.shape
    iota_b = jnp.arange(block, dtype=jnp.int32)
    onehot = (idx[:, :, None] == iota_b[None, None, :]) & (idx[:, :, None] >= 0)
    dense = jnp.einsum("reb,re->rb", onehot.astype(jnp.float32),
                       val.astype(jnp.float32),
                       precision=jax.lax.Precision.HIGHEST)
    return dense.astype(val.dtype)


# ---------------------------------------------------------------------------
# lif_step: fused leaky integrate-and-fire neuron update.
#   v'      = v * decay + i_syn
#   spike   = v' >= v_th
#   v_next  = v_reset where spike else v'
# Shapes: (rows, lanes) float32; returns (v_next, spike as input dtype).
# ---------------------------------------------------------------------------

def lif_step(v: jnp.ndarray, i_syn: jnp.ndarray, decay: float, v_th: float,
             v_reset: float):
    v2 = v * jnp.asarray(decay, v.dtype) + i_syn
    spike = (v2 >= jnp.asarray(v_th, v.dtype))
    v_next = jnp.where(spike, jnp.asarray(v_reset, v.dtype), v2)
    return v_next, spike.astype(v.dtype)


# ---------------------------------------------------------------------------
# fabric_queue_scan / fabric_queue_update: the per-micro-transaction queue
# step of core/network.py's slot engines.  q_time is (Q, C) int32 release
# times with BIG_NS (2**30) marking empty/consumed one-shot slots; t_q is
# the (Q,) per-queue clock.  These ARE the reference engine's per-step
# queue semantics — the Pallas kernels in fabric_queue.py must match them
# bit-for-bit (tested in tests/test_fabric_queue_kernel.py).
# ---------------------------------------------------------------------------

from ..core.protocol_sim import BIG_NS as _QBIG  # noqa: E402


def fabric_queue_scan(q_time: jnp.ndarray, q_dest: jnp.ndarray,
                      t_q: jnp.ndarray):
    """Per-queue released-count / min-release / next-arrival / argmin-pop
    / backlog indicator / head route.

    Returns ``(pend, r_min, nxt, amin, busy, head_route)``, each (Q,)
    int32; ``amin`` is the slot a pop must consume (lowest released slot
    of the minimum release time — FIFO among simultaneous arrivals; 0
    for empty rows); ``busy`` is the 0/1 released-work indicator
    (``pend > 0``) the telemetry plane accumulates per
    micro-transaction; ``head_route`` is ``q_dest[q, amin[q]]`` — the
    route id a pop of this queue would dispatch, read here so the
    flow-control gate can inspect each head's downstream targets
    *before* the FSM step without a second O(C) pass (garbage-but-valid
    for empty rows, exactly like the engines' post-step gather).
    """
    released = q_time <= t_q[:, None]
    pend = jnp.sum(released.astype(jnp.int32), axis=1)
    val = jnp.where(released, q_time, _QBIG)
    r_min = jnp.min(val, axis=1)
    nxt = jnp.min(jnp.where(released, _QBIG, q_time), axis=1)
    amin = jnp.argmin(val, axis=1).astype(jnp.int32)
    busy = (pend > 0).astype(jnp.int32)
    head_route = jnp.take_along_axis(q_dest, amin[:, None], axis=1)[:, 0]
    return pend, r_min, nxt, amin, busy, head_route


def fabric_queue_update(q_time, q_dest, q_inj, pop_q, pop_slot,
                        app_q, app_slot, app_t, app_dest, app_inj):
    """Consume popped slots (back to BIG_NS) and append forwarded copies.

    ``pop_q``: (Lp,) queue row per link; ``app_q``: (La,) queue row per
    append lane — La may exceed Lp (L·K lanes when in-fabric multicast
    replicates one pop into up to K child copies).  Any id >= Q skips
    the lane (dropped indices).  Append targets are unique (queue, slot)
    pairs, and pop and append slots are disjoint by construction
    (appends land at ``n_ins``, beyond released slots).
    """
    q_time = q_time.at[pop_q, pop_slot].set(_QBIG, mode="drop")
    q_time = q_time.at[app_q, app_slot].set(app_t, mode="drop")
    q_dest = q_dest.at[app_q, app_slot].set(app_dest, mode="drop")
    q_inj = q_inj.at[app_q, app_slot].set(app_inj, mode="drop")
    return q_time, q_dest, q_inj


def fabric_queue_multistep(carry, consts, base, *, step_fn, chunk: int,
                           max_steps: int):
    """Multi-step oracle: the semantics of one
    ``fabric_queue_multistep_pallas`` launch in pure jnp (no Pallas).

    Steps the packed carry ``min(chunk, max_steps - base)`` times with a
    plain ``lax.fori_loop`` — same dynamic bound as the kernel, so a
    binding ``max_steps`` truncates the final chunk identically.  The
    injected ``step_fn`` should be built over *this module's*
    ``fabric_queue_scan`` / ``fabric_queue_update`` (the engine's
    ``kernels="ref"`` wiring does exactly that), making the oracle
    Pallas-free end to end; the kernel must match it bit-for-bit for
    any step_fn (tested in tests/test_fabric_queue_kernel.py).
    """
    b = jnp.asarray(base).reshape(-1)[0]
    n = jnp.minimum(chunk, max_steps - b)

    def body(i, c):
        return step_fn(c, tuple(consts), b + i)

    return jax.lax.fori_loop(0, n, body, tuple(carry))


# ---------------------------------------------------------------------------
# selective_scan_ref: plain time-step loop oracle for the S6 recurrence
#   h_t = exp(dt_t · A) ⊙ h_{t-1} + (dt_t · x_t) ⊗ B_t ;  y_t = h_t · C_t
# ---------------------------------------------------------------------------

def selective_scan_ref(x, dt, b_ssm, c_ssm, a):
    """x, dt: (B, S, d_in); b_ssm/c_ssm: (B, S, N); a: (d_in, N).
    Returns (y (B,S,d_in), h_final (B,d_in,N)), all f32."""
    x = x.astype(jnp.float32)
    dt = dt.astype(jnp.float32)

    def step(h, inp):
        xt, dtt, bt, ct = inp            # (B,d_in),(B,d_in),(B,N),(B,N)
        abar = jnp.exp(dtt[..., None] * a)
        bx = (dtt * xt)[..., None] * bt[:, None, :]
        h = abar * h + bx
        y = (h * ct[:, None, :]).sum(-1)
        return h, y

    B, S, d_in = x.shape
    h0 = jnp.zeros((B, d_in, a.shape[1]), jnp.float32)
    hf, ys = jax.lax.scan(step, h0,
                          (x.swapaxes(0, 1), dt.swapaxes(0, 1),
                           b_ssm.swapaxes(0, 1), c_ssm.swapaxes(0, 1)))
    return ys.swapaxes(0, 1), hf
