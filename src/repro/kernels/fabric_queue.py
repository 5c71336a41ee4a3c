"""Pallas TPU kernels: the fabric event-queue step (network.py hot path).

The fabric simulator's slot layout keeps, per endpoint queue, ``C``
one-shot slots of int32 release times (``BIG_NS`` = empty/consumed).
Each micro-transaction needs four reductions over every queue —

  pend   how many slots have been released (release <= clock),
  r_min  the earliest released release time (conservative-pop guard),
  nxt    the earliest *future* release (idle-link wake-up target),
  amin   the slot to pop: first index of the released minimum
         (``jnp.argmin`` semantics — lowest slot wins ties, i.e. FIFO
         among simultaneous arrivals),

— followed by a sparse update: consume at most one popped slot per link
(set it back to ``BIG_NS``) and append the step's forwarded copies at
their queues' insertion slots.  In-fabric multicast replication spawns
up to ``K`` child copies per pop, so the append operands are (L·K,)
lanes while the pop operands stay (L,) — the one-hot scatter handles
the two widths independently.  Off-kernel this is several separate
O(Q·C) passes per step; here each becomes ONE pass.

TPU adaptation notes (mirroring ``aer_encode.py``):

* The scan kernel materializes the released mask once per VMEM tile and
  feeds all four reductions from it.  argmin is recast as
  ``min(where(val == row_min, iota, C))`` — the first-minimum-index
  trick — so no argmin lowering is needed and the tie rule matches
  ``jnp.argmin`` exactly.
* The update kernel recasts both scatters as ONE-HOT MATMULS (VMEM has
  no scatter): with ``A[r, l] = [pop_q[l] == r]`` and
  ``S[l, c] = [pop_slot[l] == c]``, the pop mask is ``A @ S`` and the
  append values are ``(A * value) @ S_app`` — (rows × links × C)
  contractions that run on the MXU.  The MXU has no int32 matmul, so
  each int32 value is scattered as its four bytes in bf16 with f32
  accumulation: every output sums at most one nonzero term (targets
  are unique), and a byte is exact in bf16, so release times up to the
  ``BIG_NS`` sentinel (2**30) survive bit for bit.
* Out-of-range ids (the caller's "no pop / no append on this link"
  sentinel ``Q``; dropped forwards) simply match no row — the one-hot
  formulation gives masked scatter for free.

Validated bit-exactly against ``ref.fabric_queue_scan`` /
``ref.fabric_queue_update`` in interpret mode on the CPU, and compiled
for a TPU v5e by ``tests/test_tpu_compile.py``.  Row blocks are 8 rows
(or the whole array) and per-row results are (rows, 1) columns, the
tiling Mosaic accepts.

These kernels back ``engine="pallas"`` of the fabric front-end
(``fabric.EngineSpec`` / the ``simulate_fabric`` wrapper).  They are
deliberately timing-agnostic: the queue step sees only release times and
per-queue clocks, so per-link timing heterogeneity (structure-of-arrays
``LinkTiming``) flows through the engine's dynamic cost vectors without
touching the kernel layout.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..core.protocol_sim import BIG_NS
from .dispatch import resolve_interpret

# plain Python int: a jnp scalar would be a captured constant inside the
# kernel, which pallas_call rejects
_BIG = int(BIG_NS)


def scan_math(q, qd, t):
    """Value-level body of the scan kernel (kernel-safe jnp only).

    Shared by ``_scan_kernel`` (one VMEM tile per grid step) and the
    multi-step kernel's in-loop queue scan, so the tile math — the
    first-minimum-index argmin recast, the one-hot head-route select —
    exists exactly once.  Returns the six (rows,) int32 reductions.
    """
    rows, ncols = q.shape
    released = q <= t[:, None]
    val = jnp.where(released, q, _BIG)
    row_min = jnp.min(val, axis=1)
    pend = jnp.sum(released.astype(jnp.int32), axis=1)
    nxt = jnp.min(jnp.where(released, _BIG, q), axis=1)
    # first-minimum-index == jnp.argmin (all-BIG rows resolve to slot 0)
    iota_c = jax.lax.broadcasted_iota(jnp.int32, (rows, ncols), 1)
    amin = jnp.min(
        jnp.where(val == row_min[:, None], iota_c, ncols), axis=1)
    # 0/1 backlog indicator: the released mask is already in VMEM, so the
    # telemetry plane's per-step counter costs one more reduction of the
    # same tile instead of a second O(Q*C) pass off-kernel
    busy = (pend > 0).astype(jnp.int32)
    # head route = q_dest[row, amin] as a one-hot select (no gather
    # lowering needed): amin matches exactly one column per row, so the
    # masked sum IS the gather.  Feeds the flow-control admission gate.
    route = jnp.sum(jnp.where(iota_c == amin[:, None], qd, 0), axis=1)
    return pend, row_min, nxt, amin, busy, route


def _scan_kernel(q_ref, qd_ref, t_ref, *out_refs):
    outs = scan_math(q_ref[...], qd_ref[...], t_ref[...][:, 0])
    for o_ref, o in zip(out_refs, outs):
        o_ref[...] = o[:, None]


def _row_block(nq: int, rows_per_block: int) -> int:
    """Rows per grid step: ``rows_per_block`` when it tiles ``nq`` in
    multiples of 8 (the TPU sublane tile), else the whole array."""
    if rows_per_block % 8 == 0 and nq % rows_per_block == 0:
        return rows_per_block
    return nq


def fabric_queue_step_pallas(q_time: jnp.ndarray, q_dest: jnp.ndarray,
                             t_q: jnp.ndarray, *,
                             rows_per_block: int = 8,
                             interpret: bool | str | None = None):
    """Fused queue-step reductions.

    Args:
      q_time: (Q, C) int32 release times, ``BIG_NS`` = empty slot.
      q_dest: (Q, C) int32 route ids riding the slots.
      t_q:    (Q,) int32 per-queue clock.

    Returns ``(pend, r_min, nxt, amin, busy, head_route)``, each (Q,)
    int32 (``busy`` = 0/1 released-backlog indicator for the telemetry
    plane; ``head_route`` = the would-pop slot's route id for the
    flow-control gate).
    """
    nq, _ = q_time.shape
    rows = _row_block(nq, rows_per_block)
    grid = (nq // rows,)

    out_shape = [jax.ShapeDtypeStruct((nq, 1), jnp.int32) for _ in range(6)]
    col_spec = pl.BlockSpec((rows, 1), lambda i: (i, 0))
    tile = pl.BlockSpec((rows, q_time.shape[1]), lambda i: (i, 0))
    outs = pl.pallas_call(
        _scan_kernel,
        grid=grid,
        in_specs=[tile, tile, col_spec],
        out_specs=[col_spec] * 6,
        out_shape=out_shape,
        interpret=resolve_interpret(interpret),
    )(q_time, q_dest, t_q[:, None])
    return tuple(o[:, 0] for o in outs)


_DN = (((1,), (0,)), ((), ()))


def _onehot_dot(a, s):
    """``a @ s`` for small non-negative int32 operands (0/1 or a byte,
    exact in bf16) on the MXU, accumulated in f32: exact while every
    output stays below 2**24."""
    return jax.lax.dot_general(
        a.astype(jnp.bfloat16), s.astype(jnp.bfloat16), _DN,
        preferred_element_type=jnp.float32).astype(jnp.int32)


def _onehot_scatter(a, vals, s):
    """``(a * vals) @ s`` for one-hot ``a``/``s`` with unique targets:
    the four bytes of each int32 value go through :func:`_onehot_dot`
    separately and are reassembled, so every int32 is exact."""
    out = jnp.zeros((a.shape[0], s.shape[1]), jnp.int32)
    for shift in (0, 8, 16, 24):
        byte = jax.lax.shift_right_logical(vals, shift) & 0xFF
        out = out | (_onehot_dot(a * byte[None, :], s) << shift)
    return out


def update_math(qt, qd, qi, popq, pops, appq, apps, appt, appd, appi,
                row_base=0):
    """Value-level body of the update kernel (scatter-as-matmul).

    ``row_base`` offsets the tile's row ids when the caller processes a
    (rows, C) slice of a larger array (the gridded per-step kernel); the
    multi-step kernel passes the whole array with ``row_base=0``.
    Shared so the one-hot matmul scatter exists exactly once.  Returns
    the updated ``(q_time, q_dest, q_inj)`` values.
    """
    rows, ncols = qt.shape
    row_ids = row_base + jax.lax.broadcasted_iota(jnp.int32, (rows, 1), 0)

    n_pop = popq.shape[0]                # (Lp,) lanes
    n_app = appq.shape[0]                # (La,) = Lp·K under mcast

    iota_pop = jax.lax.broadcasted_iota(jnp.int32, (n_pop, ncols), 1)
    iota_app = jax.lax.broadcasted_iota(jnp.int32, (n_app, ncols), 1)

    a_pop = (row_ids == popq[None, :]).astype(jnp.int32)     # (rows, Lp)
    s_pop = (pops[:, None] == iota_pop).astype(jnp.int32)    # (Lp, C)
    p_pop = _onehot_dot(a_pop, s_pop)

    a_app = (row_ids == appq[None, :]).astype(jnp.int32)     # (rows, La)
    s_app = (apps[:, None] == iota_app).astype(jnp.int32)    # (La, C)
    p_app = _onehot_dot(a_app, s_app)

    keep = 1 - p_pop - p_app             # pop/append slots are disjoint
    return (qt * keep + _BIG * p_pop + _onehot_scatter(a_app, appt, s_app),
            qd * (1 - p_app) + _onehot_scatter(a_app, appd, s_app),
            qi * (1 - p_app) + _onehot_scatter(a_app, appi, s_app))


def _update_kernel(qt_ref, qd_ref, qi_ref, popq_ref, pops_ref,
                   appq_ref, apps_ref, appt_ref, appd_ref, appi_ref,
                   ot_ref, od_ref, oi_ref, *, rows_per_block: int):
    ot, od, oi = update_math(
        qt_ref[...], qd_ref[...], qi_ref[...], popq_ref[...], pops_ref[...],
        appq_ref[...], apps_ref[...], appt_ref[...], appd_ref[...],
        appi_ref[...], row_base=pl.program_id(0) * rows_per_block)
    ot_ref[...] = ot
    od_ref[...] = od
    oi_ref[...] = oi


def fabric_queue_update_pallas(q_time, q_dest, q_inj,
                               pop_q, pop_slot,
                               app_q, app_slot, app_t, app_dest, app_inj,
                               *, rows_per_block: int = 8,
                               interpret: bool | str | None = None):
    """Fused pop-consume + forward-append over the (Q, C) slot arrays.

    ``pop_q`` / ``app_q`` hold a queue id per lane, or ``Q`` (any id
    >= Q) to skip that lane; popped slots revert to ``BIG_NS``, appended
    slots receive ``(app_t, app_dest, app_inj)``.  The append lanes may
    outnumber the pop lanes (L·K vs L when in-fabric multicast
    replicates one pop into up to K child copies); every (queue, slot)
    append target must be unique, and pop and append slots must be
    disjoint (the engine appends at ``n_ins``, beyond any released
    slot).  Returns the three updated arrays.
    """
    nq, ncols = q_time.shape
    rows = _row_block(nq, rows_per_block)
    grid = (nq // rows,)

    kernel = functools.partial(_update_kernel, rows_per_block=rows)
    tile = pl.BlockSpec((rows, ncols), lambda i: (i, 0))
    whole_pop = pl.BlockSpec((pop_q.shape[0],), lambda i: (0,))
    whole_app = pl.BlockSpec((app_q.shape[0],), lambda i: (0,))
    out_shape = [jax.ShapeDtypeStruct((nq, ncols), jnp.int32)
                 for _ in range(3)]
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[tile, tile, tile,
                  whole_pop, whole_pop,
                  whole_app, whole_app, whole_app, whole_app, whole_app],
        out_specs=[tile, tile, tile],
        out_shape=out_shape,
        interpret=resolve_interpret(interpret),
    )(q_time, q_dest, q_inj, pop_q, pop_slot,
      app_q, app_slot, app_t, app_dest, app_inj)


# ---------------------------------------------------------------------------
# Multi-step fused kernel: the whole micro-transaction loop per launch
# ---------------------------------------------------------------------------

#: Mosaic's default scoped-VMEM limit per kernel.
VMEM_DEFAULT_BYTES = 16 << 20
#: Scoped VMEM the multi-step kernel may ask for: most of the 128 MiB of
#: one TPU v5e core, leaving room for the compiler's own buffers.
VMEM_MAX_BYTES = 100 << 20
#: Scoped VMEM the kernel needs beyond its operands and temporaries.
VMEM_FIXED_BYTES = 1 << 20


def multistep_vmem_bytes(carry, consts, step_temp_bytes: int = 0) -> int:
    """Scoped VMEM the multi-step kernel is budgeted for these operands.

    The carry is counted four times (the input and output blocks and the
    step loop's state before and after a step), the constants once, and
    ``step_temp_bytes`` — the step function's largest temporaries, which
    its caller knows — six times (an iota-compare mask, the selected
    values and the reduction, for up to two index columns at once).
    Compiled for a v5e, Mosaic asked for 4.2x to 7.9x the carry on
    ring-16 (1,024 to 32,768 events), ring-32, ring-64 and 4x4 and 8x8
    meshes; this budget sits 26% to 55% above each of those asks.
    """
    def nbytes(arrs):
        return sum(a.size * a.dtype.itemsize for a in arrs)
    return (4 * nbytes(carry) + nbytes(consts) + 6 * step_temp_bytes
            + VMEM_FIXED_BYTES)


def fabric_queue_multistep_pallas(carry, consts, base, *, step_fn,
                                  chunk: int, max_steps: int,
                                  step_temp_bytes: int = 0,
                                  interpret: bool | str | None = None):
    """Run up to ``chunk`` fabric micro-transactions in ONE kernel launch.

    The per-step path above dispatches two ``pallas_call``s per
    micro-transaction and round-trips the full engine state through XLA
    between them — 2·max_steps kernel launches per simulation, each
    re-loading the (Q, C) slot arrays from HBM.  This kernel instead
    loads the packed carry once, steps it ``chunk`` times with a
    ``lax.fori_loop`` *inside* the kernel body (the carry stays resident
    in VMEM/registers across steps), and writes it back once: HBM
    traffic and launch count drop by the chunk factor.

    The step loop is an in-kernel ``fori_loop`` rather than a grid
    dimension deliberately: scratch carried across sequential grid steps
    is a TPU-only guarantee, and interpret mode *unrolls* grid
    iterations at trace time (chunk copies of the body), while a
    ``fori_loop`` body traces once on every backend.  The outer
    chunk-of-steps structure is the caller's ``lax.scan`` over
    ``base`` values (``core.network._slot_run_multistep``).

    Args:
      carry:  tuple of int32 state arrays (slot arrays + packed lane /
              side / log / counter planes — the caller owns the layout).
      consts: tuple of read-only int32 arrays (links, replication
              tables, timing planes, flow-control scalars).
      base:   (1,) int32 — global index of this chunk's first step; it
              rides in SMEM, so the kernel reads it as a scalar.
      step_fn: ``step_fn(carry, consts, step_i) -> carry`` — one
              micro-transaction of physics, built by the engine so the
              kernel body and the pure-jnp oracle
              (``ref.fabric_queue_multistep``) share it verbatim.  The
              queue scan / scatter math inside it must use
              :func:`scan_math` / :func:`update_math` (kernel-safe,
              scatter-as-matmul) — that is what moves the pop/append
              contractions inside the kernel body.
      chunk / max_steps: static ints.  The loop bound is
              ``min(chunk, max_steps - base)`` — dynamic, so a binding
              ``max_steps`` is honoured exactly (post-bound steps are
              NOT executed; they are not guaranteed to be no-ops).
      step_temp_bytes: bytes of the largest temporaries one
              ``step_fn`` call makes (its one-hot masks), for the VMEM
              budget.

    The whole carry lives in VMEM, so on a compiled backend a budget
    (:func:`multistep_vmem_bytes`) above ``VMEM_MAX_BYTES`` raises
    ``ValueError``; one above Mosaic's default limit raises the kernel's
    limit to the budget.

    Returns the stepped carry tuple (same shapes/dtypes).
    """
    carry = tuple(carry)
    consts = tuple(consts)
    n_car = len(carry)
    n_con = len(consts)
    interpret = resolve_interpret(interpret)
    params = None
    if not interpret:
        need = multistep_vmem_bytes(carry, consts, step_temp_bytes)
        if need > VMEM_MAX_BYTES:
            raise ValueError(
                f"the multi-step fabric kernel keeps its whole carry in "
                f"VMEM and is budgeted {need} bytes for these shapes "
                f"(carry {[a.shape for a in carry]}), above the "
                f"{VMEM_MAX_BYTES}-byte limit; use kernel='step' or the "
                f"ring engine for a fabric this large")
        if need > VMEM_DEFAULT_BYTES:
            params = pltpu.CompilerParams(vmem_limit_bytes=need)

    def kernel(*refs):
        car = tuple(r[...] for r in refs[:n_car])
        con = tuple(r[...] for r in refs[n_car:n_car + n_con])
        b = refs[n_car + n_con][0]
        out_refs = refs[n_car + n_con + 1:]
        n = jnp.minimum(chunk, max_steps - b)

        def body(i, c):
            return step_fn(c, con, b + i)

        out = jax.lax.fori_loop(0, n, body, car)
        for o_ref, o in zip(out_refs, out):
            o_ref[...] = o

    out_shape = [jax.ShapeDtypeStruct(a.shape, a.dtype) for a in carry]
    vmem = pl.BlockSpec(memory_space=pltpu.VMEM)
    return pl.pallas_call(
        kernel,
        in_specs=[vmem] * (n_car + n_con)
        + [pl.BlockSpec(memory_space=pltpu.SMEM)],
        out_specs=[vmem] * n_car,
        out_shape=out_shape,
        compiler_params=params,
        interpret=interpret,
    )(*carry, *consts, base)
